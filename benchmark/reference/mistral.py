"""Mistral-7B-v0.3's plain reference: the shared decoder with a SwiGLU MLP.

``config.json`` of mistralai/Mistral-7B-v0.3: hidden 4096, 32 query and
8 key/value heads of 128, SwiGLU 14,336, vocabulary 32,768, rope_theta
1e6, no sliding window, RMSNorm eps 1e-5, untied head.
"""

from reference.plain import (init_weights, lm_loss, logits,  # noqa: F401
                             synthetic_batch, train_steps)

FAMILY = "dense"
