"""The window/full-attention decoder with routed ReGLU experts
(``polyaxon_tpu/models/smallthinker.py``), from the keys of
SmallThinker-21BA3B-Instruct's published ``config.json``: per layer an
entry of ``rope_layout`` (the rotary embedding turns q and k) and of
``sliding_window_layout`` (keys within the last ``sliding_window_size``
positions), ``moe_num_primary_experts`` softmax-routed experts of width
``moe_ffn_hidden_size`` in every layer, ``moe_num_active_primary_experts``
a token, an untied head.

**The cut.** Depth keeps whole periods of the two layouts, the
published first layers in their order, and the layouts are cut with it
(``reduced`` names all three). No width, no expert and no row of the
vocabulary is cut: ``deployment`` states that one chip holds each layer
whole. `check` holds the configuration's keys, its ``reduced`` and its
``deployment`` against each other.
"""

from __future__ import annotations

PERIOD = 4


def check(config: dict) -> None:
    """What the program's decoder cannot express, and what a cut of
    this configuration may not change."""
    layers = config["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        if len(config[key]) != layers:
            raise ValueError(f"{key} names {len(config[key])} layers, "
                             f"num_hidden_layers is {layers}")
    if layers % PERIOD:
        raise ValueError(f"a cut keeps whole periods: {layers} layers are "
                         f"not a multiple of {PERIOD}")
    if len(set(config["sliding_window_layout"])) != 2:
        raise ValueError("the program's decoder has window and full layers "
                         "side by side")
    if not config.get("moe_primary_router_apply_softmax", True):
        raise ValueError("the program's router is a softmax over every "
                         "expert (`moe_primary_router_apply_softmax`)")
    if not config.get("norm_topk_prob", True):
        raise ValueError("the program's softmax router renormalises the "
                         "chosen weights (`norm_topk_prob`)")
    if config.get("tie_word_embeddings"):
        raise ValueError("the program's decoder has an untied head")
    if config.get("rope_scaling"):
        raise ValueError("the program's decoder has no rope scaling")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("num_attention_heads is not a multiple of "
                         "num_key_value_heads")
    cut = config.get("reduced", {})
    for key, entry in cut.items():
        if entry["serve"] != config[key]:
            raise ValueError(f"`reduced.{key}` says {entry['serve']}, the "
                             f"configuration {config[key]}")
    for key in ("rope_layout", "sliding_window_layout"):
        if key in cut and cut[key]["source"][:layers] != config[key]:
            raise ValueError(f"the kept {key} is not the published one's "
                             f"first {layers} entries")
    if ("num_hidden_layers" in cut) != ("rope_layout" in cut) or (
            "rope_layout" in cut) != ("sliding_window_layout" in cut):
        raise ValueError("a cut in depth cuts both layouts with it")
    if "num_hidden_layers" in cut and (
            len(cut["rope_layout"]["source"])
            != cut["num_hidden_layers"]["source"]):
        raise ValueError("the published layouts are not the published depth")
    if config.get("deployment", {}).get("chips_sharing_a_layer", 1) != 1:
        raise ValueError("this configuration holds each layer whole on one "
                         "chip")


def build(config: dict, role: str):
    import jax.numpy as jnp

    from polyaxon_tpu.models import smallthinker

    check(config)
    section = config.get(role, {})
    layers = int(section.get("num_hidden_layers",
                             config["num_hidden_layers"]))
    if layers != config["num_hidden_layers"]:
        raise ValueError(f"the `{role}` section's depth {layers} is not the "
                         "depth the layouts state")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return smallthinker, smallthinker.SmallThinkerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=layers, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        rope_layout=tuple(config["rope_layout"]),
        window_layout=tuple(config["sliding_window_layout"]),
        sliding_window=int(config["sliding_window_size"]),
        n_experts=config["moe_num_primary_experts"],
        experts_per_token=config["moe_num_active_primary_experts"],
        moe_ffn_dim=config["moe_ffn_hidden_size"],
        norm_eps=float(config["rms_norm_eps"]), dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def parameters(config: dict) -> dict:
    """Parameters by part, from the file's own keys: a layer's
    attention, its router, one expert, its two norms, a vocabulary
    table."""
    d, hd = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    return {
        "attn": 2 * d * q + 2 * d * kv,
        "router": d * config["moe_num_primary_experts"],
        "expert": 3 * d * config["moe_ffn_hidden_size"],
        "norms": 2 * d,
        "table": d * config["vocab_size"],
    }


def parameters_here(config: dict, layers: int, active: bool = False) -> int:
    """Parameters of `layers` layers with the table, the head and the
    final norm; ``active``: those a token's pass reads."""
    n = parameters(config)
    experts = config["moe_num_active_primary_experts" if active
                     else "moe_num_primary_experts"]
    return (layers * (n["attn"] + n["router"] + n["norms"]
                      + experts * n["expert"])
            + 2 * n["table"] + config["hidden_size"])


def forward_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    """Matmul flops of the forward pass a token at the depth as cut: the
    projections, the router, six experts, the head, and the score and
    value matmuls over the keys a layer's mask leaves (``seq_len`` on a
    full layer, at most the window on a window layer)."""
    n = parameters(config)
    q = config["num_attention_heads"] * config["head_dim"]
    window = config["sliding_window_size"]
    keys = sum(min(seq_len, window) if windowed else seq_len
               for windowed in config["sliding_window_layout"][:layers])
    return float(2 * layers * (
        n["attn"] + n["router"]
        + config["moe_num_active_primary_experts"] * n["expert"])
        + 2 * 2 * q * keys + 2 * n["table"])
