"""The window/full-attention decoder with sigmoid-routed experts behind
a leading dense layer (``polyaxon_tpu/models/exaone_moe.py``), from the
keys of K-EXAONE-236B-A23B's published ``config.json`` (``model_type:
exaone_moe``): per layer an entry of ``layer_types``
(``sliding_attention``: keys within the last ``sliding_window``
positions, rotary; ``full_attention``: every earlier key, no positions)
and of ``mlp_layer_types`` (``dense``: ``intermediate_size``;
``sparse``: ``num_experts`` experts of ``moe_intermediate_size`` scored
by sigmoid, ``num_experts_per_tok`` a token, beside
``num_shared_experts`` shared); an untied head.

**The cut.** Depth keeps whole periods of ``layer_types``, the published
first layers in their order (the dense layer first), and the three
per-layer lists are cut with it. The other two cuts are the chip's share
of a stated deployment (``deployment``: so many chips share each layer,
this is rank ``rank`` of them): ``num_experts`` counts the routed
experts held here, the contiguous block of that rank, while the router
keeps its published width (``reduced.num_experts.source``);
``vocab_size`` counts the rows of the table and of the head held here
(``deployment.vocab_shards`` ways). `check` holds the configuration's
keys, its ``reduced`` and its ``deployment`` against each other. The
next-token-prediction module's keys stay as published and build nothing
(``assumed.multi_token_prediction``).
"""

from __future__ import annotations

PERIOD = 4
KINDS = {"sliding_attention": 1, "full_attention": 0}
PER_LAYER = ("layer_types", "mlp_layer_types", "sliding_windows")


def held(config: dict) -> tuple:
    """(first, count, routed): the routed experts held here among those
    the router scores."""
    count = config["num_experts"]
    cut = config.get("reduced", {}).get("num_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def check(config: dict) -> None:
    """What the program's decoder cannot express, and what a cut of
    this configuration may not change."""
    layers = config["num_hidden_layers"]
    for key in PER_LAYER:
        if len(config[key]) != layers:
            raise ValueError(f"{key} names {len(config[key])} layers, "
                             f"num_hidden_layers is {layers}")
    if layers % PERIOD:
        raise ValueError(f"a cut keeps whole periods: {layers} layers are "
                         f"not a multiple of {PERIOD}")
    if set(config["layer_types"]) != set(KINDS):
        raise ValueError("the program's decoder has sliding_attention and "
                         "full_attention layers side by side")
    dense = config["first_k_dense_replace"]
    if config["mlp_layer_types"] != (["dense"] * dense
                                     + ["sparse"] * (layers - dense)):
        raise ValueError("the program's decoder has `first_k_dense_replace` "
                         "dense layers and an expert block in every other")
    if layers - dense < 1:
        raise ValueError("a cut keeps the dense layers and an expert layer")
    window = config["sliding_window"]
    if config["sliding_windows"] != [
            window * KINDS[kind] for kind in config["layer_types"]]:
        raise ValueError("sliding_windows is not `sliding_window` on the "
                         "sliding_attention layers and 0 elsewhere")
    if config.get("scoring_func") != "sigmoid":
        raise ValueError("the program's router scores by sigmoid")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("the program's router chooses among all experts "
                         "(`n_group` = `topk_group` = 1)")
    if config.get("num_shared_experts") != 1:
        raise ValueError("the program's expert block has one shared expert")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLPs are SwiGLU (silu)")
    if config.get("tie_word_embeddings"):
        raise ValueError("the program's decoder has an untied head")
    if not config.get("norm_topk_prob", True):
        raise ValueError("the program's router renormalises the chosen "
                         "weights (`norm_topk_prob`)")
    if config["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("the program's decoder has no rope scaling")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("num_attention_heads is not a multiple of "
                         "num_key_value_heads")
    cut = config.get("reduced", {})
    for key, entry in cut.items():
        if entry["serve"] != config[key]:
            raise ValueError(f"`reduced.{key}` says {entry['serve']}, the "
                             f"configuration {config[key]}")
    listed = [key in cut for key in ("num_hidden_layers",) + PER_LAYER]
    if any(listed) and not all(listed):
        raise ValueError("a cut in depth cuts the three per-layer lists "
                         "with it")
    for key in PER_LAYER:
        if key in cut and cut[key]["source"][:layers] != config[key]:
            raise ValueError(f"the kept {key} is not the published one's "
                             f"first {layers} entries")
        if key in cut and (len(cut[key]["source"])
                           != cut["num_hidden_layers"]["source"]):
            raise ValueError(f"the published {key} is not the published "
                             "depth")
    _, count, routed = held(config)
    deployment = config.get("deployment", {})
    shared_by = deployment.get("chips_sharing_a_layer", 1)
    if count * shared_by != routed:
        raise ValueError(f"{shared_by} chips of {count} experts do not hold "
                         f"the router's {routed}")
    if "vocab_size" in cut and (
            config["vocab_size"] * deployment.get("vocab_shards", shared_by)
            != cut["vocab_size"]["source"]):
        raise ValueError("the vocabulary slice is not this deployment's")
    if ("num_experts" in cut or "vocab_size" in cut) and shared_by < 2:
        raise ValueError("a share of the experts or of the vocabulary "
                         "needs a deployment of several chips a layer")


def build(config: dict, role: str):
    import jax.numpy as jnp

    from polyaxon_tpu.models import exaone_moe

    check(config)
    section = config.get(role, {})
    layers = int(section.get("num_hidden_layers",
                             config["num_hidden_layers"]))
    if layers != config["num_hidden_layers"]:
        raise ValueError(f"the `{role}` section's depth {layers} is not the "
                         "depth the per-layer lists state")
    first, count, routed = held(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return exaone_moe, exaone_moe.ExaoneMoEConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=layers, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        window_layout=tuple(KINDS[kind] for kind in config["layer_types"]),
        sliding_window=int(config["sliding_window"]),
        ffn_dim=config["intermediate_size"],
        first_dense=config["first_k_dense_replace"],
        n_experts=routed, experts_per_token=config["num_experts_per_tok"],
        moe_ffn_dim=config["moe_intermediate_size"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        held_experts=(first, count),
        norm_eps=float(config["rms_norm_eps"]), dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def parameters(config: dict) -> dict:
    """Parameters by part, from the file's own keys: a layer's attention
    (q, k, v, o), a dense MLP, an expert block beside its routed experts
    (the router, the shared expert), one routed expert, a layer's norm
    gains (two over the hidden size, two over a head), the router's
    selection bias, a vocabulary table."""
    d, hd = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    fm = config["moe_intermediate_size"]
    _, _, routed = held(config)
    return {
        "attn": 2 * d * q + 2 * d * kv,
        "dense": 3 * d * config["intermediate_size"],
        "beside": d * routed + config["num_shared_experts"] * 3 * d * fm,
        "expert": 3 * d * fm,
        "norms": 2 * d + 2 * hd,
        "bias": routed,
        "table": d * config["vocab_size"],
    }


def parameters_here(config: dict, layers: int, active: bool = False,
                    gains: bool = True) -> int:
    """Parameters of `layers` layers as cut with the table and the head;
    ``active``: with the experts a token's pass reads, wherever they are
    held; ``gains``: with the norm gains (the final norm's among them)
    and the selection bias, which the matrices' count leaves out."""
    n = parameters(config)
    dense = min(config["first_k_dense_replace"], layers)
    experts = (config["num_experts_per_tok"] if active else held(config)[1])
    total = (layers * n["attn"] + dense * n["dense"]
             + (layers - dense) * (n["beside"] + experts * n["expert"])
             + 2 * n["table"])
    if gains:
        total += (layers * n["norms"] + (layers - dense) * n["bias"]
                  + config["hidden_size"])
    return total


def forward_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    """Matmul flops of the forward pass a token at the depth as cut and
    with the share of the experts held here: the projections, the dense
    MLP, the router, the shared expert, this chip's share of the eight
    routed experts, the head, and the score and value matmuls over the
    keys a layer's mask leaves (``seq_len`` on a full layer, at most the
    window on a window layer)."""
    n = parameters(config)
    q = config["num_attention_heads"] * config["head_dim"]
    window = config["sliding_window"]
    _, count, routed = held(config)
    pairs = config["num_experts_per_tok"] * count / routed
    dense = min(config["first_k_dense_replace"], layers)
    keys = sum(min(seq_len, window) if kind == "sliding_attention"
               else seq_len for kind in config["layer_types"][:layers])
    return float(2 * layers * n["attn"] + 2 * 2 * q * keys
                 + dense * 2 * n["dense"]
                 + (layers - dense) * 2 * (n["beside"] + pairs * n["expert"])
                 + 2 * n["table"])
