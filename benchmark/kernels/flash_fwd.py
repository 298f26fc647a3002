"""`flash_fwd` (ops/flash.py): what one causal call needs.

Compute-bound at these shapes. For q [B, S, H, Hd] against k, v
[B, S, KV, Hd]: QK^T and PV are 2*S*S*Hd flops a head each for a full
square; the causal half is what the algorithm needs, so
2 * B * H * S * S * Hd in all. Bytes (q, k, v read once, o written)
are given for completeness; the bound is the flops.
"""

BOUND = "flops"


def flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    return 2.0 * batch * heads * seq * seq * head_dim


def bytes_moved(batch: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    return float(batch * seq * head_dim * itemsize * (2 * heads + 2 * kv_heads))


def least_seconds(peaks: dict, batch: int, seq: int, heads: int,
                  kv_heads: int, head_dim: int) -> float:
    return max(flops(batch, seq, heads, head_dim) / peaks["bf16_flops"],
               bytes_moved(batch, seq, heads, kv_heads, head_dim)
               / peaks["hbm_bytes_per_s"])
