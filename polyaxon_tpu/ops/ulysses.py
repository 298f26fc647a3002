"""Ulysses attention: sequence↔heads all-to-all context parallelism.

The second long-context strategy from SURVEY.md §2b: instead of
rotating K/V blocks around a ring (ops/ring.py), re-shard inside the
attention block with an all-to-all so each device sees the FULL
sequence for a SUBSET of heads:

    [B, S/n, H, D]  --all_to_all-->  [B, S, H/n, D]
          (seq sharded)                 (heads sharded)

then exact (flash or einsum) attention runs locally per head group —
no online-softmax recombination needed — and a second all-to-all
restores sequence sharding. On TPU both all-to-alls ride the ICI
all-to-all fabric; cost is 2 resharding passes of Q/K/V/O vs ring's
cp-step KV rotation, and it requires heads % cp == 0 (GQA KV heads are
repeated up to the group count first when necessary).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from polyaxon_tpu.ops.ring import _axis_bound
from polyaxon_tpu.parallel import compat
from polyaxon_tpu.parallel.compat import ambient_mesh


def _ulysses_sharded(
    q: jax.Array,  # [B, S_loc, H, D]
    k: jax.Array,  # [B, S_loc, KV, D]
    v: jax.Array,
    *,
    causal: bool,
    scale: Optional[float],
    axis_name: str,
    attn_impl: str,
) -> jax.Array:
    from polyaxon_tpu.ops.attention import repeat_kv, xla_attention

    n = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"Ulysses needs heads ({h}) % axis size ({n}) == 0")
    kv = k.shape[2]
    if kv % n:  # not enough kv heads to split: repeat groups up to n
        rep = n // kv if kv < n else 1
        if kv * rep != n and (kv * rep) % n:
            raise ValueError(f"kv heads {kv} incompatible with axis size {n}")
        k = repeat_kv(k, max(rep, 1))
        v = repeat_kv(v, max(rep, 1))

    # seq-sharded -> heads-sharded: split heads (axis 2), gather seq (1).
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    q_full = a2a(q)  # [B, S, H/n, D]
    k_full = a2a(k)
    v_full = a2a(v)

    if attn_impl == "flash":
        from polyaxon_tpu.ops.flash import flash_attention

        o = flash_attention(
            q_full, k_full, v_full, causal=causal, softmax_scale=scale
        )
    else:
        o = xla_attention(q_full, k_full, v_full, causal=causal, softmax_scale=scale)

    # heads-sharded -> seq-sharded: split seq (1), gather heads (2).
    return jax.lax.all_to_all(
        o, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
    q: jax.Array,  # [B, S, H, D] (global, seq sharded over the axis)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    axis_name: str = "cp",
    attn_impl: str = "xla",
    mesh=None,
) -> jax.Array:
    if _axis_bound(axis_name):
        return _ulysses_sharded(
            q, k, v, causal=causal, scale=softmax_scale, axis_name=axis_name,
            attn_impl=attn_impl,
        )
    mesh = mesh if mesh is not None else ambient_mesh()
    if mesh is None or axis_name not in mesh.axis_names:
        raise ValueError(
            f"ulysses_attention needs mesh axis `{axis_name}`: call inside "
            "shard_map, pass mesh=, or enter `with mesh:`"
        )
    # Batch stays sharded over dp/fsdp THROUGH the shard_map: leaving
    # the batch dim unmentioned would all-gather Q/K/V over dp at the
    # boundary and run attention dp-redundantly, then re-shard O — the
    # avoidable reshard the collective audit flagged around the ulysses
    # all-to-all passes (4 extra all-gathers/step on dp2xcp4; see
    # docs/performance.md "Communication audit").
    spec = P(compat.batch_axes_in(mesh), axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_sharded, causal=causal, scale=softmax_scale,
            axis_name=axis_name, attn_impl=attn_impl,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
