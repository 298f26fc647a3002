"""The benchmark's own count of what a model's training needs.

Matmul flops of the forward pass per token: every projection the token
goes through at the depth as cut (active experts only; the router
counts), the output head, and causal attention at the row's length.
The embedding is a row lookup and counts nothing. Training needs three
times the forward pass (one forward, two matmuls a projection in the
backward); recomputation under remat is the program's choice and does
not count.

A family whose layers are not all alike brings its own count, as
``forward_flops_per_token(config, layers, seq_len)`` in its file under
``families/``; where a family gives none, every layer is counted alike
from the keys of Mistral's and Mixtral's published files.
"""

from __future__ import annotations

from harness import spec


def forward_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    own = getattr(spec.load_family(config), "forward_flops_per_token", None)
    if own is not None:
        return float(own(config, layers, seq_len))
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    f = config["intermediate_size"]
    attn_proj = d * q + 2 * d * kv + q * d
    experts = config.get("num_local_experts")
    if experts:
        mlp = config["num_experts_per_tok"] * 3 * d * f + d * experts
    else:
        mlp = 3 * d * f
    # QK^T and PV: 2 * seq * q each for a full row, half of it causal.
    attention = 2 * seq_len * q
    per_layer = 2 * (attn_proj + mlp) + attention
    head = 2 * d * config["vocab_size"]
    return float(layers * per_layer + head)


def train_flops_per_token(config: dict, layers: int, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(config, layers, seq_len)
