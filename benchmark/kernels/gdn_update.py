"""The gated delta rule's decode update (``polyaxon_tpu/ops/
gated_delta.py step``): what one layer's update needs for one step.

Memory-bound: for each live row the matrix state ``S`` [Hv, dk, dv]
float32 is read once and written once (``S ← e^g·S + k ⊗ β(v − e^g·Sᵀk)``
and ``o = Sᵀq``: both products with the state can be taken from the one
read, ``step``'s docstring); the vectors beside it (q, k, v, g, β: 2·dk
+ dv + 2 numbers a head a row) are a hundredth of a percent of that and
are left out. About seven operations an element (the decay, two
multiply-adds for the two reads, a multiply-add for the outer product)
are far under the chip's peak. An idle row needs nothing. A program that
copies the whole pool of states, or passes over the state a third time,
takes longer than this and reads as a smaller share of the roofline:
that is what the share is for.
"""

BOUND = "bytes"


def bytes_moved(live_rows: int, heads: int, key_dim: int, value_dim: int,
                itemsize: int = 4) -> float:
    return float(2 * live_rows * heads * key_dim * value_dim * itemsize)


def flops(live_rows: int, heads: int, key_dim: int, value_dim: int) -> float:
    # e^g·S, Sᵀk and Sᵀq (a product and an add each), k ⊗ δ added.
    return float(7 * live_rows * heads * key_dim * value_dim)


def least_seconds(peaks: dict, live_rows: int, heads: int, key_dim: int,
                  value_dim: int) -> float:
    return max(bytes_moved(live_rows, heads, key_dim, value_dim)
               / peaks["hbm_bytes_per_s"],
               flops(live_rows, heads, key_dim, value_dim)
               / peaks["bf16_flops"])
