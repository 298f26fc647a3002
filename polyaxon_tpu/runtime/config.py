"""JAXJob runtime config: the ``runtime:`` section of a jaxjob run spec."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from pydantic import BaseModel, ConfigDict, Field


class RuntimeConfig(BaseModel):
    """Validated view of ``V1JAXJob.runtime``. Unknown keys are treated as
    model-config overrides (e.g. ``seq_len``, ``remat``) and filtered
    against the model's dataclass fields at build time."""

    model_config = ConfigDict(extra="allow")

    model: str
    dataset: str = "lm_synthetic"
    steps: int = 100
    eval_every: Optional[int] = None
    eval_steps: int = 8
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    warmup_steps: int = 0
    lr_schedule: str = "constant"  # constant | cosine | linear
    grad_clip_norm: Optional[float] = 1.0
    batch_size: Optional[int] = None          # per-device
    global_batch_size: Optional[int] = None   # overrides batch_size
    # Microbatch the per-update batch inside the compiled step (grads
    # accumulate in a lax.scan; peak activations / accum_steps).
    grad_accum_steps: int = 1
    seq_len: Optional[int] = None
    seed: int = 0
    log_every: int = 10
    # Input-pipeline overlap: a background thread generates and
    # device-commits batch i+k while the device runs step i, keeping up
    # to `prefetch` ready batches queued. 0 = synchronous (the host
    # pays generation + transfer inside every step).
    prefetch: int = Field(default=2, ge=0)
    # Attention/remat knobs forwarded to the model config when supported.
    remat: Optional[str] = None
    attention_impl: Optional[str] = None
    # LoRA fine-tuning (models/lora.py): rank > 0 freezes the base and
    # trains low-rank adapters on `lora_targets` (default: attention +
    # MLP projections); optimizer state exists only for the adapters.
    lora_rank: int = Field(default=0, ge=0)  # 0 = LoRA off
    lora_alpha: float = Field(default=16.0, gt=0)
    lora_targets: Optional[list[str]] = None
    # Profiling: capture a jax.profiler trace for these steps.
    profile_steps: Optional[list[int]] = None

    def model_overrides(self, config_cls) -> dict[str, Any]:
        """Extra keys + known knobs that match the model config's fields."""
        fields = {f.name for f in dataclasses.fields(config_cls)}
        out: dict[str, Any] = {}
        extras = dict(self.__pydantic_extra__ or {})
        extras.update({
            "remat": self.remat,
            "attention_impl": self.attention_impl,
            "max_seq_len": self.seq_len,
        })
        for key, value in extras.items():
            if value is not None and key in fields:
                out[key] = value
        return out
