"""Analytic training-FLOPs accounting + per-chip peak tables, shared by
``bench.py`` and the runtime loop's per-step MFU self-reporting
(SURVEY.md §5.1: every run reports its own achieved TFLOPs — the
observability NVML dashboards provide upstream).

The 6N rule (fwd 2N + bwd 4N matmul FLOPs per token) over the *active*
parameters, plus the causal-attention score/value matmuls. Families
without a derivation return None — callers report mfu as null rather
than a wrong number.
"""

from __future__ import annotations

import logging
from typing import Optional

# bf16 peak matmul throughput per chip, for MFU. Keyed by substring of
# jax's device_kind (published per-chip figures, Google Cloud TPU docs).
PEAK_FLOPS = {
    "v5 lite": 197e12,  # v5e ("TPU v5 lite")
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6": 918e12,  # Trillium
}


def peak_flops(device) -> Optional[float]:
    """bf16 peak FLOP/s of a ``jax.Device``. A TPU whose kind is not in
    the table is an error, not a default: an MFU against a guessed peak
    is a wrong number. Other platforms (the CPU test mesh) have no
    peak, and callers report ``mfu`` as null there."""
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in PEAK_FLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak recorded for TPU device_kind `{device.device_kind}`"
        " — add it to runtime/flops.py PEAK_FLOPS (with its source) "
        "before reporting MFU on this chip")


def train_flops_per_token(model: str, seq: int,
                          param_count: int) -> Optional[int]:
    """Training FLOPs per token: 6N for the *active* matmul params
    (fwd 2N + bwd 4N) plus the causal-attention score/value matmuls
    (6 * n_layers * seq * d_model fwd+bwd after halving for causality).

    For MoE models only K of E experts run per token, so N is the
    dense params plus K/E of the expert-FFN params — counting all
    experts would overstate tflops/MFU by roughly E/K on the FFN
    share. Families without a derivation (vit/bert/resnet/...) return
    None.
    """
    try:
        from polyaxon_tpu.models import llama, moe

        cfg = llama.CONFIGS.get(model)
        if cfg is not None:
            return 6 * param_count + 6 * cfg.n_layers * seq * cfg.dim
        mcfg = moe.CONFIGS.get(model)
        if mcfg is not None:
            expert_params = (mcfg.n_layers * mcfg.n_experts
                             * 3 * mcfg.dim * mcfg.ffn_dim)
            active = (param_count - expert_params
                      + expert_params * mcfg.experts_per_token
                      // mcfg.n_experts)
            return 6 * active + 6 * mcfg.n_layers * seq * mcfg.dim
    except Exception as exc:
        logging.getLogger(__name__).debug(
            "flops derivation failed for %r: %s", model, exc)
    return None
