"""Mixtral-8x7B-v0.1's plain reference: the shared decoder with eight
SwiGLU experts, two a token.

``config.json`` of mistralai/Mixtral-8x7B-v0.1: Mistral-7B's attention
widths, 8 experts of 14,336, top-2 routing (softmax over all experts,
the two largest renormalised to sum to one), vocabulary 32,000,
rope_theta 1e6, router_aux_loss_coef 0.02. The two departures (the
auxiliary loss's form and the per-expert capacity) are set out in
reference/plain.py.
"""

from reference.plain import (init_weights, lm_loss, logits,  # noqa: F401
                             synthetic_batch, train_steps)

FAMILY = "moe"
