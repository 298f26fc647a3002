"""`window_decode` (ops/paged_attention.py with ``window=``): what one
call needs.

Memory-bound, as `paged_decode` is: one query position a row, the K and
V pages read once. One call serves one window layer. A live row of
length n attends positions ``[max(0, n - window), n)``: the pages that
hold one of them, from page ``max(0, n - window) // page`` to page
``(n - 1) // page`` (at most ``window / page + 1``: the window reaches
one token into that many pages unless it starts on a boundary), K and
V, each ``KV * page * Hd`` elements, plus the row's q read and o
written (``H * Hd`` each). Idle rows, and whatever a row holds behind
its window, need nothing. FLOPs over the same columns: the score and
the value matmul, 2 operations a column a head dimension each.
"""

BOUND = "bytes"


def window_pages(n: int, page: int, window: int) -> int:
    """Pages that hold a position of [max(0, n - window), n)."""
    if n <= 0:
        return 0
    return (n - 1) // page - max(0, n - window) // page + 1


def bytes_moved(live_lengths, page: int, kv_heads: int, heads: int,
                head_dim: int, window: int, itemsize: int = 2) -> float:
    total = 0
    for n in live_lengths:
        if n <= 0:
            continue
        total += (2 * window_pages(n, page, window) * kv_heads * page
                  * head_dim * itemsize)
        total += 2 * heads * head_dim * itemsize
    return float(total)


def flops(live_lengths, heads: int, head_dim: int, window: int) -> float:
    return float(sum(4 * min(n, window) * heads * head_dim
                     for n in live_lengths if n > 0))


def least_seconds(peaks: dict, live_lengths, page: int, kv_heads: int,
                  heads: int, head_dim: int, window: int) -> float:
    return max(bytes_moved(live_lengths, page, kv_heads, heads, head_dim,
                           window) / peaks["hbm_bytes_per_s"],
               flops(live_lengths, heads, head_dim, window)
               / peaks["bf16_flops"])
