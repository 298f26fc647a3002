"""The only place the benchmark reaches into the program.

The zoo's entries depart from the published files (``mistral_7b`` has a
4,096 window and a 32,000 vocabulary, ``mixtral_8x7b`` a 5e5
``rope_theta`` and 8k positions), and neither `plx serve` nor the job
spec can override every field. So the configuration's file is turned
into the family's config dataclass and registered in its ``CONFIGS``
(and the zoo's factory table) under the configuration's name; from
there on the program's normal path runs: ``ServingServer`` for serving,
``run_jaxjob`` for training.
"""

from __future__ import annotations


def _depth(config: dict, role: str) -> int:
    return int(config.get(role, {}).get("num_hidden_layers",
                                        config["num_hidden_layers"]))


def build_model_config(config: dict, role: str):
    """(family module, its config dataclass instance) for `role`
    (``serve`` or ``train``), straight from the published keys."""
    import jax.numpy as jnp

    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError("the families here are SwiGLU with an untied head")
    if config["hidden_size"] != (config["head_dim"]
                                 * config["num_attention_heads"]):
        raise ValueError("the program derives head_dim from hidden_size")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    shared = dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=_depth(config, role), n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=dtype,
        max_seq_len=int(config.get(role, {}).get(
            "max_len", config["max_position_embeddings"])))
    if config["family"] == "llama":
        from polyaxon_tpu.models import llama

        return llama, llama.LlamaConfig(
            sliding_window=config["sliding_window"], rope_scaling=None,
            **shared)
    if config["family"] == "moe":
        from polyaxon_tpu.models import moe

        if config["sliding_window"] is not None:
            raise ValueError("the moe family has no sliding window")
        section = config.get(role, {})
        return moe, moe.MoEConfig(
            n_experts=config["num_local_experts"],
            experts_per_token=config["num_experts_per_tok"],
            router_aux_coef=float(config["router_aux_loss_coef"]),
            capacity_factor=float(section.get("capacity_factor", 1.25)),
            **shared)
    raise ValueError(f"unknown family `{config['family']}`")


def register(config: dict, role: str):
    """Register the configuration in the program's zoo; returns
    (model name, family module, model config)."""
    from polyaxon_tpu import models

    family, cfg = build_model_config(config, role)
    name = config["name"]
    family.CONFIGS[name] = cfg
    models._FACTORIES[name] = (
        lambda **overrides: family.model_def(name, **overrides))
    return name, family, cfg


def runtime_section(config: dict, model: str, seed: int, seq_len: int,
                    steps: int = 1_000_000) -> dict:
    """The ``runtime:`` section of the JAXJob this cell submits."""
    train = config["train"]
    return {
        "model": model, "dataset": train["dataset"], "steps": steps,
        "optimizer": train["optimizer"],
        "learning_rate": train["learning_rate"],
        "weight_decay": train["weight_decay"],
        "grad_clip_norm": train["grad_clip_norm"],
        "seq_len": seq_len,
        "global_batch_size": train["global_batch_size"],
        "log_every": train["log_every"], "prefetch": train["prefetch"],
        "remat": train["remat"], "attention_impl": train["attention_impl"],
        "seed": seed,
    }
