"""The Gated DeltaNet / gated attention / routed SwiGLU decoder
(``polyaxon_tpu/models/qwen3_next.py``), from the keys of
Qwen3-Next-80B-A3B-Instruct's published ``config.json`` (``model_type:
qwen3_next``): every layer a mixer and an expert block, the mixer full
attention where ``(l + 1) % full_attention_interval == 0`` and Gated
DeltaNet elsewhere, an untied head.

**The cut.** Depth keeps whole periods of the plan (a multiple of
``full_attention_interval`` layers, so the kept layers are the
published first ones in their order and ratio). The other two cuts are
the chip's share of a stated deployment (``deployment``: so many chips
share each layer, this is rank ``rank`` of them): ``num_experts``
counts the routed experts held here, the contiguous block of that rank,
while the router keeps its published width
(``reduced.num_experts.source``); ``vocab_size`` counts the rows of the
table and of the head held here. `check` holds the configuration's
keys, its ``reduced`` and its ``deployment`` against each other.
"""

from __future__ import annotations


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
    layers, every = (config["num_hidden_layers"],
                     config["full_attention_interval"])
    if layers % every:
        raise ValueError(f"a cut keeps whole periods: {layers} layers are "
                         f"not a multiple of full_attention_interval {every}")
    if config.get("decoder_sparse_step", 1) != 1 or config.get(
            "mlp_only_layers"):
        raise ValueError("the program's decoder has an expert block in "
                         "every layer (`decoder_sparse_step`, "
                         "`mlp_only_layers`)")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's experts are SwiGLU (silu)")
    if config.get("tie_word_embeddings"):
        raise ValueError("the program's decoder has an untied head")
    if config.get("rope_scaling") or config.get("use_sliding_window"):
        raise ValueError("the program's gated attention has no rope "
                         "scaling and no sliding window")
    if not config.get("norm_topk_prob", True):
        raise ValueError("the program's softmax router renormalises the "
                         "chosen weights (`norm_topk_prob`)")
    if config["linear_num_value_heads"] % config["linear_num_key_heads"]:
        raise ValueError("linear_num_value_heads is not a multiple of "
                         "linear_num_key_heads")
    turned = config["head_dim"] * config["partial_rotary_factor"]
    if turned != int(turned) or int(turned) % 2:
        raise ValueError("partial_rotary_factor does not give the rotary "
                         "embedding an even number of dimensions")
    cut = config.get("reduced", {})
    for key, entry in cut.items():
        if entry["serve"] != config[key]:
            raise ValueError(f"`reduced.{key}` says {entry['serve']}, the "
                             f"configuration {config[key]}")
    if "num_hidden_layers" in cut and (
            cut["num_hidden_layers"]["source"] % every):
        raise ValueError("the published depth is not whole periods")
    _, count, routed = held(config)
    shared_by = config.get("deployment", {}).get("chips_sharing_a_layer", 1)
    if count * shared_by != routed:
        raise ValueError(f"{shared_by} chips of {count} experts do not hold "
                         f"the router's {routed}")
    if "vocab_size" in cut and (
            config["vocab_size"] * shared_by != cut["vocab_size"]["source"]):
        raise ValueError("the vocabulary slice is not this deployment's")
    if ("num_experts" in cut or "vocab_size" in cut) and shared_by < 2:
        raise ValueError("a share of the experts or of the vocabulary "
                         "needs a deployment of several chips a layer")


def build(config: dict, role: str):
    import jax.numpy as jnp

    from polyaxon_tpu.models import qwen3_next

    check(config)
    section = config.get(role, {})
    layers = int(section.get("num_hidden_layers",
                             config["num_hidden_layers"]))
    if layers != config["num_hidden_layers"]:
        raise ValueError(f"the `{role}` section's depth {layers} is not the "
                         "depth the configuration states")
    first, count, routed = held(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return qwen3_next, qwen3_next.Qwen3NextConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=layers,
        full_attention_interval=config["full_attention_interval"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        n_experts=routed, experts_per_token=config["num_experts_per_tok"],
        moe_ffn_dim=config["moe_intermediate_size"],
        shared_ffn_dim=config["shared_expert_intermediate_size"],
        held_experts=(first, count),
        norm_eps=float(config["rms_norm_eps"]), dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def parameters(config: dict) -> dict:
    """Parameters held here by kind of layer, from the file's own keys:
    a delta mixer, an attention mixer, an expert block beside its routed
    experts, one routed expert, a vocabulary table."""
    d, hd = config["hidden_size"], config["head_dim"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    _, _, routed = held(config)
    f, fs = (config["moe_intermediate_size"],
             config["shared_expert_intermediate_size"])
    return {
        "gdn": (d + d * (2 * key + 2 * value)
                + d * 2 * config["linear_num_value_heads"]
                + (2 * key + value) * config["linear_conv_kernel_dim"]
                + 2 * config["linear_num_value_heads"]
                + config["linear_value_head_dim"] + value * d),
        "attn": d + d * 2 * q + 2 * d * kv + 2 * hd + q * d,
        "beside": d + d * routed + 3 * d * fs + d,
        "expert": 3 * d * f,
        "table": d * config["vocab_size"],
    }


def parameters_here(config: dict, layers: int) -> int:
    every = config["full_attention_interval"]
    n, attn = parameters(config), layers // every
    return (attn * n["attn"] + (layers - attn) * n["gdn"]
            + layers * (n["beside"] + held(config)[1] * n["expert"])
            + 2 * n["table"] + config["hidden_size"])


def forward_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    """Matmul flops of the forward pass a token at the depth as cut and
    with the share of the experts held here: a routed pair counts where
    its expert is held (a quarter of them on one chip of four)."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    _, count, routed = held(config)
    pairs = config["num_experts_per_tok"] * count / routed
    every = config["full_attention_interval"]
    attn = 2 * (2 * d * q + 2 * d * kv + q * d) + 2 * 2 * seq_len * q
    # The projections, and about seven operations an element of the
    # state (decay, its two reads, the outer product).
    gdn = (2 * d * (2 * key + 2 * value + 2 * config["linear_num_value_heads"])
           + 2 * value * d + 7 * value * config["linear_key_head_dim"])
    experts = 2 * (d * routed + pairs * 3 * d * config["moe_intermediate_size"]
                   + 3 * d * config["shared_expert_intermediate_size"] + d)
    n_attn = layers // every
    return float(n_attn * attn + (layers - n_attn) * gdn + layers * experts
                 + 2 * d * config["vocab_size"])
