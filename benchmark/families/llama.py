"""The dense decoder (``polyaxon_tpu/models/llama.py``), from the keys
of Mistral's published ``config.json``: one kind of layer, one
feed-forward width, SwiGLU, an untied head."""

from __future__ import annotations


def decoder_fields(config: dict, role: str) -> dict:
    """The fields the program's decoder families share, at the depth and
    the context limit of `role`'s section."""
    import jax.numpy as jnp

    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError("the families here are SwiGLU with an untied head")
    if config["hidden_size"] != (config["head_dim"]
                                 * config["num_attention_heads"]):
        raise ValueError("the program derives head_dim from hidden_size")
    section = config.get(role, {})
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=int(section.get("num_hidden_layers",
                                 config["num_hidden_layers"])),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=dtype,
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def build(config: dict, role: str):
    from polyaxon_tpu.models import llama

    return llama, llama.LlamaConfig(
        sliding_window=config["sliding_window"], rope_scaling=None,
        **decoder_fields(config, role))
