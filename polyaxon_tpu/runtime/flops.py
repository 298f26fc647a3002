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
    """Training FLOPs per token of ``model``, by its family's own
    ``train_flops_per_token(cfg, seq, param_count)`` (beside its
    ``CONFIGS``: llama's and moe's). A family without a derivation
    (lfm2, vit/bert/resnet/...) and an unknown name give None.
    """
    try:
        from polyaxon_tpu.models import family_of

        family = family_of(model)
        derive = getattr(family, "train_flops_per_token", None)
        if derive is not None:
            return derive(family.CONFIGS[model], seq, param_count)
    except Exception as exc:
        logging.getLogger(__name__).debug(
            "flops derivation failed for %r: %s", model, exc)
    return None
