"""The expert decoder (``polyaxon_tpu/models/moe.py``), from the keys of
Mixtral's published ``config.json``: the dense decoder's attention with
``num_local_experts`` SwiGLU experts of one width, ``num_experts_per_tok``
a token. Its flops are counted by ``harness/flops.py`` (active experts
and the router; every layer alike)."""

from __future__ import annotations

from families.llama import decoder_fields


def build(config: dict, role: str):
    from polyaxon_tpu.models import moe

    if config["sliding_window"] is not None:
        raise ValueError("the moe family has no sliding window")
    return moe, moe.MoEConfig(
        n_experts=config["num_local_experts"],
        experts_per_token=config["num_experts_per_tok"],
        router_aux_coef=float(config["router_aux_loss_coef"]),
        capacity_factor=float(config.get(role, {}).get("capacity_factor",
                                                       1.25)),
        **decoder_fields(config, role))
