"""The Mamba-2 decode update (``polyaxon_tpu/ops/mamba2.py ssd_step``):
what one layer's update needs for one step.

Memory-bound: for each live row the state ``S`` [H, P, N] float32 is
read once and written once (``S ← exp(ΔA)·S + Δ·x⊗B``, then ``y = S·C``
from the value just computed); the vectors beside it (x, B, C, Δ: H·P +
2·G·N + H numbers a row) are a thousandth of that and are left out. Four
multiply-adds an element are far under the chip's peak. An idle row
needs nothing. A program that copies the whole pool of states, or reads
the new state back for ``y``, takes longer than this and reads as a
smaller share of the roofline: that is what the share is for.
"""

BOUND = "bytes"


def bytes_moved(live_rows: int, heads: int, head_dim: int, state: int,
                itemsize: int = 4) -> float:
    return float(2 * live_rows * heads * head_dim * state * itemsize)


def flops(live_rows: int, heads: int, head_dim: int, state: int) -> float:
    # decay·S, Δx⊗B (a product and an add), S·C (a product and an add).
    return float(5 * live_rows * heads * head_dim * state)


def least_seconds(peaks: dict, live_rows: int, heads: int, head_dim: int,
                  state: int) -> float:
    return max(bytes_moved(live_rows, heads, head_dim, state)
               / peaks["hbm_bytes_per_s"],
               flops(live_rows, heads, head_dim, state)
               / peaks["bf16_flops"])
