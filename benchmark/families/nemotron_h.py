"""The Mamba-2 / attention / latent-expert decoder
(``polyaxon_tpu/models/nemotron_h.py``), from the keys of
NVIDIA-Nemotron-3-Super-120B-A12B's published ``config.json``
(``model_type: nemotron_h``): per layer one mixer alone, named by
``hybrid_override_pattern`` (``M`` Mamba-2, ``*`` grouped-query
attention, ``E`` the latent expert layer), an untied head.

**The cut.** Depth keeps whole periods of the pattern: a slice of the
published string that starts at an attention layer and runs up to the
next one, and holds its three kinds of layer in the published ratio.
The other two cuts are the chip's share of a stated deployment
(``deployment``: so many chips share each layer, this is rank ``rank``
of them): ``n_routed_experts`` counts the routed experts held here, the
contiguous block of that rank, while the router keeps its published
width (``reduced.n_routed_experts.source``); ``vocab_size`` counts the
rows of the table and of the head held here. `check` holds the
configuration's keys, its ``reduced`` and its ``deployment`` against
each other.
"""

from __future__ import annotations


def held(config: dict) -> tuple:
    """(first, count, routed): the routed experts held here among those
    the router scores."""
    count = config["n_routed_experts"]
    cut = config.get("reduced", {}).get("n_routed_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def check(config: dict) -> None:
    """What the program's decoder cannot express, and what a cut of
    this configuration may not change."""
    pattern = config["hybrid_override_pattern"]
    layers = config["num_hidden_layers"]
    if len(pattern) != layers:
        raise ValueError(f"hybrid_override_pattern names {len(pattern)} "
                         f"layers, num_hidden_layers is {layers}")
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias"):
        if config.get(key):
            raise ValueError(f"the program's projections have no bias "
                             f"(`{key}`)")
    if not config.get("use_conv_bias", True):
        raise ValueError("the program's Mamba-2 convolution has a bias")
    if config.get("tie_word_embeddings"):
        raise ValueError("the program's decoder has an untied head")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("the program's router has no group limit")
    if config.get("n_shared_experts", 1) != 1:
        raise ValueError("the program's expert layer has one shared expert")
    if (config["mlp_hidden_act"], config["mamba_hidden_act"]) != (
            "relu2", "silu"):
        raise ValueError("the program's experts are relu², its mixer silu")
    if config["expand"] * config["hidden_size"] != (
            config["mamba_num_heads"] * config["mamba_head_dim"]):
        raise ValueError("expand x hidden_size is not the mixer's width")
    cut = config.get("reduced", {})
    if "hybrid_override_pattern" in cut:
        published = cut["hybrid_override_pattern"]["source"]
        if len(published) != cut["num_hidden_layers"]["source"]:
            raise ValueError("the published pattern and depth disagree")
        at = config["deployment"]["first_layer"]
        if pattern != published[at:at + layers]:
            raise ValueError("the kept layers are not the published layers "
                             f"{at}..{at + layers - 1} in their order")
        if pattern[0] != "*" or published[at + layers:at + layers + 1] != "*":
            raise ValueError("a cut keeps whole periods: from an attention "
                             "layer up to the next one")
    first, count, routed = held(config)
    shared_by = config.get("deployment", {}).get("chips_sharing_a_layer", 1)
    if count * shared_by != routed:
        raise ValueError(f"{shared_by} chips of {count} experts do not hold "
                         f"the router's {routed}")
    if "vocab_size" in cut and (
            config["vocab_size"] * shared_by != cut["vocab_size"]["source"]):
        raise ValueError("the vocabulary slice is not this deployment's")


def build(config: dict, role: str):
    import jax.numpy as jnp

    from polyaxon_tpu.models import nemotron_h

    check(config)
    section = config.get(role, {})
    layers = int(section.get("num_hidden_layers",
                             config["num_hidden_layers"]))
    if layers != config["num_hidden_layers"]:
        raise ValueError(f"the `{role}` section's depth {layers} is not the "
                         "depth the pattern states")
    first, count, routed = held(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return nemotron_h, nemotron_h.NemotronHConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        n_experts=routed, experts_per_token=config["num_experts_per_tok"],
        moe_latent_dim=config["moe_latent_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        shared_ffn_dim=config["moe_shared_expert_intermediate_size"],
        held_experts=(first, count),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        time_step_min=float(config["time_step_min"]),
        time_step_max=float(config["time_step_max"]),
        time_step_floor=float(config["time_step_floor"]),
        rope_theta=None, norm_eps=float(config["layer_norm_epsilon"]),
        dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def forward_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    """Matmul flops of the forward pass a token at the depth as cut and
    with the share of the experts held here: a routed pair counts where
    its expert is held (a quarter of them on one chip of four)."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    d_in = config["mamba_num_heads"] * config["mamba_head_dim"]
    state = config["ssm_state_size"]
    conv_dim = d_in + 2 * config["n_groups"] * state
    _, count, routed = held(config)
    dl, f = config["moe_latent_size"], config["moe_intermediate_size"]
    pairs = config["num_experts_per_tok"] * count / routed
    per_kind = {
        "*": 2 * (2 * d * q + 2 * d * kv) + 2 * seq_len * q,
        # in/out projections, the state's update and read (H·P·N each).
        "M": (2 * d * (d_in + conv_dim + config["mamba_num_heads"])
              + 2 * d_in * d + 6 * d_in * state),
        "E": 2 * (d * routed + 2 * d * dl + pairs * 2 * dl * f
                  + 2 * d * config["moe_shared_expert_intermediate_size"]),
    }
    pattern = config["hybrid_override_pattern"][:layers]
    return float(sum(per_kind[char] for char in pattern)
                 + 2 * d * config["vocab_size"])
