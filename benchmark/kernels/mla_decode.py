"""`mla_decode` (ops/mla_decode.py): what one call needs.

Latent attention's decode in the absorbed form: one query position a
row, 64 heads each as wide as the cached latent, over the row's latents
read once. A token's latent is ``kv_lora_rank + qk_rope_head_dim``
values (576 published, 1,152 B at bfloat16) whatever the pool pads it
to: the bytes counted are the published ones, so a pool that pads
(640) reads lower, never higher. One call serves one layer. A live row
of length n reads n latents, plus its queries (``H x latent``) and its
output (``H x kv_lora_rank``). FLOPs: the score over the whole latent
and the value product over c_kv's columns, ``2 x H x (latent +
kv_lora_rank)`` a position. At 121 FLOP a byte the call sits under the
chip's ridge (240) and is memory-bound at full matmul rate; the least
time is the larger of the two all the same.
"""

BOUND = "bytes"


def bytes_moved(live_lengths, heads: int, latent: int, value: int,
                itemsize: int = 2) -> float:
    return float(sum(n * latent * itemsize
                     + heads * (latent + value) * itemsize
                     for n in live_lengths if n > 0))


def flops(live_lengths, heads: int, latent: int, value: int) -> float:
    return float(sum(2 * n * heads * (latent + value)
                     for n in live_lengths if n > 0))


def least_seconds(peaks: dict, live_lengths, heads: int, latent: int,
                  value: int) -> float:
    return max(bytes_moved(live_lengths, heads, latent, value)
               / peaks["hbm_bytes_per_s"],
               flops(live_lengths, heads, latent, value)
               / peaks["bf16_flops"])
