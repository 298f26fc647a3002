"""`paged_decode` (ops/paged_attention.py): what one call needs.

Memory-bound: one query position a row, so the matmuls are tiny and the
K and V pages are read once. One call serves one layer. For each live
row of length n (positions 0..n-1 attended): ceil(n / page) pages of K
and of V, each KV * page * Hd elements, plus the row's q read and o
written (H * Hd each). Idle rows and pages past a row's length need
nothing: the kernel's grid still steps over them (slots x kv heads x
max_len/page), which is time the algorithm does not need, so a short
live context reads as a small share of the roofline.
"""

BOUND = "bytes"


def bytes_moved(live_lengths, page: int, kv_heads: int, heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    total = 0
    for n in live_lengths:
        if n <= 0:
            continue
        pages = -(-n // page)
        total += 2 * pages * kv_heads * page * head_dim * itemsize
        total += 2 * heads * head_dim * itemsize
    return float(total)


def flops(live_lengths, heads: int, head_dim: int) -> float:
    return float(sum(4 * n * heads * head_dim for n in live_lengths if n > 0))


def least_seconds(peaks: dict, live_lengths, page: int, kv_heads: int,
                  heads: int, head_dim: int) -> float:
    return max(bytes_moved(live_lengths, page, kv_heads, heads, head_dim)
               / peaks["hbm_bytes_per_s"],
               flops(live_lengths, heads, head_dim) / peaks["bf16_flops"])
