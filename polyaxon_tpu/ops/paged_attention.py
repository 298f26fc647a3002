"""Pallas paged-attention decode kernel (TPU).

The XLA gather formulation of paged decode (``models/llama.py``
``paged_attn_step``) materializes every row's gathered pages
([B, maxp·page, KV, Hd]) in HBM each step — 2× the cache traffic of
reading it once. This kernel streams each row's pages straight from
the pool through VMEM with an online-softmax accumulator (the flash
recipe from ``ops/flash.py``, specialized to q-length 1), using
scalar-prefetched block tables to drive the page DMA — and pages that
are unallocated or wholly past the row's position are skipped, so
compute tracks actual sequence lengths, not the table width.

Decode attention is HBM-bandwidth-bound (tiny matmuls, whole-cache
reads), which is exactly the regime where cutting bytes moved wins.
Reference for the paged memory model: vLLM; for the TPU scalar-
prefetch pattern: the Pallas guide §PrefetchScalarGridSpec. Written
against this repo's own flash kernel conventions — not a port.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from polyaxon_tpu.ops.flash import resolve_interpret
from polyaxon_tpu.parallel import compat

NEG_INF = -1e30
LANES = 128


def _decode_kernel(
    tables_ref,  # scalar prefetch: [B, maxp] int32 page ids (-1 = hole)
    pos_ref,  # scalar prefetch: [B] int32 row positions (-1 = idle)
    q_ref,  # [1, 1, rep, Hd]
    k_ref,  # [1, 1, page, Hd] — page selected by the index map
    v_ref,  # [1, 1, page, Hd]
    o_ref,  # [1, 1, rep, Hd]
    acc_ref,  # VMEM [rep, Hd] f32
    m_ref,  # VMEM [rep, LANES] f32
    l_ref,  # VMEM [rep, LANES] f32
    *,
    scale: float,
    page: int,
):
    b, j = pl.program_id(0), pl.program_id(2)
    n_pages = pl.num_programs(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # A page contributes iff the row is live, the page is allocated,
    # and it starts at or before the row's current position.
    @pl.when((pos >= 0) & (tables_ref[b, j] >= 0) & (j * page <= pos))
    def _compute():
        q = q_ref[0, 0]  # [rep, Hd]
        k = k_ref[0, 0]  # [page, Hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s *= scale  # [rep, page]

        cols = j * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = cols <= pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[0, 0]  # [page, Hd]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_pages - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # idle row → zeros
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # [B, H, Hd] — the single decode position per row
    k_pages: jax.Array,  # [P, KV, page, Hd]
    v_pages: jax.Array,
    tables: jax.Array,  # [B, maxp] int32 (-1 = unallocated)
    pos: jax.Array,  # [B] int32 (-1 = idle row → zeros out)
    *,
    interpret: bool | None = None,  # None = interpret on the CPU backend
) -> jax.Array:
    """Attention of each row's query against its pages (positions
    0..pos inclusive — the current step's K/V must already be written
    to the pool). Returns [B, H, Hd].

    The pool is laid out ``[P, KV, page, Hd]`` so a page block is
    ``(1, 1, page, Hd)``: its two trailing dims are the array's own,
    which is what the Mosaic lowering demands of a block that is not a
    multiple of the (8, 128) tile. Under a multi-device mesh the call
    runs per ``tp`` shard of the kv heads (``compat.shard_kernel``)."""
    interpret = resolve_interpret(interpret)
    B, H, Hd = q.shape
    KV = k_pages.shape[1]
    _, head_axis = compat.kernel_axes(B, KV)
    heads = P(None, head_axis, None)
    pool = P(None, head_axis, None, None)
    return compat.shard_kernel(
        functools.partial(_paged_decode, interpret=interpret),
        in_specs=(heads, pool, pool, P(), P()),
        out_specs=heads,
    )(q, k_pages, v_pages, tables.astype(jnp.int32), pos.astype(jnp.int32))


def _paged_decode(q, k_pages, v_pages, tables, pos, *, interpret: bool):
    B, H, Hd = q.shape
    _, KV, page, _ = k_pages.shape
    maxp = tables.shape[1]
    rep = H // KV

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    def page_map(b, h, j, tables_ref, pos_ref):
        # The page DMA: block index along the pool axis comes from the
        # row's block table (clamped — holes are skipped by the kernel
        # predicate, the clamp only keeps the index legal).
        return (jnp.maximum(tables_ref[b, j], 0), h, 0, 0)

    def row_map(b, h, j, tables_ref, pos_ref):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, rep, Hd), row_map),
            pl.BlockSpec((1, 1, page, Hd), page_map),
            pl.BlockSpec((1, 1, page, Hd), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, Hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((rep, Hd), jnp.float32),
            pltpu.VMEM((rep, LANES), jnp.float32),
            pltpu.VMEM((rep, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=Hd ** -0.5, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, Hd), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="paged_decode",
    )(tables, pos, q.reshape(B, KV, rep, Hd), k_pages, v_pages)
    return out.reshape(B, H, Hd)
