"""The hybrid decoder (``polyaxon_tpu/models/lfm2.py``), from the keys
of LFM2-8B-A1B's published ``config.json`` (``model_type: lfm2_moe``):
per layer a gated short convolution or grouped-query attention
(``layer_types``), a dense SwiGLU in the first ``num_dense_layers``
layers and ``num_experts`` sigmoid-routed experts in the others, a tied
head.

A depth cut keeps the pattern: the published layer 1 (a dense
convolution layer: the leading dense layers count once) followed by the
published layers 2, 3, ... in whole periods of (attention, convolution,
convolution, convolution). The configuration states the cut in three
keys (``num_hidden_layers``, ``num_dense_layers``, the kept slice as
``layer_types``) and the published values under ``reduced``; `build`
holds the three against each other.
"""

from __future__ import annotations

PERIOD = ["full_attention", "conv", "conv", "conv"]


def check(config: dict) -> None:
    """What the program's hybrid decoder cannot express, and what a cut
    of this configuration may not change."""
    kept = list(config["layer_types"])
    layers = config["num_hidden_layers"]
    if len(kept) != layers:
        raise ValueError(f"layer_types names {len(kept)} layers, "
                         f"num_hidden_layers is {layers}")
    if config["conv_bias"]:
        raise ValueError("the program's short convolution has no bias")
    if config["hidden_size"] != (config["head_dim"]
                                 * config["num_attention_heads"]):
        raise ValueError("the program derives head_dim from hidden_size")
    if not config.get("tie_embedding", True):
        raise ValueError("the program's hybrid decoder ties its head")
    cut = config.get("reduced", {})
    if "layer_types" not in cut:
        return      # uncut: the published pattern as it is
    published = list(cut["layer_types"]["source"])
    dense = cut["num_dense_layers"]["source"]
    if (len(published) != cut["num_hidden_layers"]["source"]
            or config["num_dense_layers"] != 1):
        raise ValueError("a cut keeps one of the leading dense layers and "
                         "states the published depth")
    if kept != published[dense - 1:dense - 1 + layers]:
        raise ValueError("the kept layers are not the published layers "
                         f"{dense - 1}..{dense - 2 + layers} in their order")
    tail = kept[1:]
    if len(tail) % len(PERIOD) or tail != PERIOD * (len(tail) // len(PERIOD)):
        raise ValueError("behind the dense layer a cut keeps whole periods "
                         "of (attention, conv, conv, conv)")


def build(config: dict, role: str):
    import jax.numpy as jnp

    from polyaxon_tpu.models import lfm2

    check(config)
    section = config.get(role, {})
    layers = int(section.get("num_hidden_layers",
                             config["num_hidden_layers"]))
    if layers != config["num_hidden_layers"]:
        raise ValueError(f"the `{role}` section's depth {layers} is not the "
                         "depth layer_types states")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return lfm2, lfm2.Lfm2Config(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=layers, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        n_dense_layers=config["num_dense_layers"],
        layer_types=tuple(config["layer_types"]),
        conv_kernel=config["conv_L_cache"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        use_expert_bias=bool(config["use_expert_bias"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]), dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))
