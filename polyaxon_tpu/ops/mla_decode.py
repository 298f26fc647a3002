"""Pallas decode kernel for latent attention (MLA) over the page pool.

A latent-attention layer caches one vector a token: the compressed
``c_kv`` (512 wide at the published size) and, behind it, the one
rotary key every head shares (64), in a page ``[1, page, W]`` of the
pool ``[L, P, 1, page, W]``. ``W`` is the latent padded with zeros to
whole lane tiles (576 → 640): the streamed page copy below is the one
form the TPU compiler lets through, and it wants the pool's last
dimension to fill the lanes (``ops/paged_attention.py`` keeps its
words).

Decode is the *absorbed* form: the caller folds each head's key
up-projection into its query (``q_lat = q_nope · W_UK^T``), so every
head's query is ``W`` wide like the cached vector, the scores are ``q ·
latent`` over the row's pages, and the value read is the first
``value_width`` columns of the very block the scores read:
``o = softmax(q · latent^T · scale) · latent[:, :value_width]``. One
read of the latent serves every head, key and value both; the caller
takes ``o`` through ``W_UV``.

The call is ``paged_decode``'s streamed form with one "KV head" and
``H`` queries on it: every layer's pool stacked and the layer a
prefetched scalar, grid ``(B,)``, a loop over the row's live pages, 16 a
turn (256 tokens at the serving page), each page copied by its own DMA
into one of two buffers while the other is computed on; nothing is
fetched past a row's length, an idle row (``pos = -1``) costs no copy
and gives zeros, a hole (``tables[b, p] < 0``) is masked by its page's
columns. The kernel is named ``mla_decode``. Off the chip the model
runs `mla_decode_reference`, the same sums over gathered pages in plain
``jnp``; under ``interpret`` the kernel runs on the CPU for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from polyaxon_tpu.ops.flash import resolve_interpret
from polyaxon_tpu.ops.paged_attention import (LANES, NEG_INF, _live_columns,
                                              _quot, _rem)

# Pages a turn of the streamed loop: 256 tokens at the serving page.
GROUP = 16


def _mla_kernel(
    tables_ref,  # scalar prefetch: [B, maxp] int32 page ids (-1 = hole)
    pos_ref,  # scalar prefetch: [B] int32 row positions (-1 = idle)
    layer_ref,  # scalar prefetch: [1] int32, the layer whose pages to read
    q_ref,  # [1, H, W]
    c_hbm,  # [L, P, 1, page, W], left in HBM
    o_ref,  # [1, H, C]
    c_buf,  # VMEM [2, G·page, W]: the turn computed on, the next
    sem,  # DMA semaphores [2 (buffer), G]
    acc_ref,  # VMEM [H, C] f32
    m_ref,  # VMEM [H, LANES] f32
    l_ref,  # VMEM [H, LANES] f32
    *,
    scale: float,
    page: int,
    group: int,
    value_width: int,
):
    b = pl.program_id(0)
    maxp = tables_ref.shape[1]
    pos = pos_ref[b]
    layer = layer_ref[0]
    n_pages = jnp.minimum(_quot(pos + page, page), maxp)  # 0 when idle
    n_turns = _quot(n_pages + group - 1, group)

    def each_live_copy(turn, buf, act):
        for i in range(group):
            p = turn * group + i
            # A hole's page is masked; the clamps keep the reads legal.
            pid = jnp.maximum(tables_ref[b, jnp.minimum(p, maxp - 1)], 0)
            copy = pltpu.make_async_copy(
                c_hbm.at[layer, pid, 0],
                c_buf.at[buf, pl.ds(i * page, page)], sem.at[buf, i])

            @pl.when(p < n_pages)
            def _act():
                getattr(copy, act)()

    @pl.when(b == 0)
    def _clear():
        # A turn's unfetched pages are masked out of the probabilities,
        # and 0 x what an earlier row left there is 0; only what the
        # buffer held before the first copy is not known to be finite.
        c_buf[:] = jnp.zeros_like(c_buf)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(n_turns > 0)
    def _first():
        each_live_copy(0, 0, "start")

    def turn_body(turn, carry):
        buf = _rem(turn, 2)

        @pl.when(turn + 1 < n_turns)
        def _next():
            each_live_copy(turn + 1, 1 - buf, "start")

        each_live_copy(turn, buf, "wait")
        mask = _live_columns(tables_ref, b, turn * group, pos,
                             page=page, group=group)[0]  # [1, T]
        c = c_buf[buf]  # [T, W]
        s = jax.lax.dot_general(
            q_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.where(mask, s * scale, NEG_INF)  # [H, T]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(c.dtype), c[:, :value_width],
                     preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, n_turns, turn_body, None)
    l = l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)  # idle row → zeros
    o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def mla_decode_attention(
    q: jax.Array,  # [B, H, W]: each head's absorbed query, one position a row
    pool: jax.Array,  # [L, P, 1, page, W]: every layer's latent pages
    layer,  # int or traced int32 scalar: the layer whose pages are read
    tables: jax.Array,  # [B, maxp] int32 (-1 = unallocated)
    pos: jax.Array,  # [B] int32 (-1 = idle row → zeros out)
    *,
    scale: float,
    value_width: int,
    interpret: bool | None = None,  # None = interpret on the CPU backend
) -> jax.Array:
    """Each row's ``H`` queries against its pages of layer ``layer``
    (positions 0..pos inclusive: the step's latent must already be in
    the pool). Returns the probability-weighted sum of the latents'
    first ``value_width`` columns, [B, H, value_width]."""
    interpret = resolve_interpret(interpret)
    B, H, W = q.shape
    _, _, one, page, width = pool.shape
    if one != 1 or width != W or W % LANES or value_width % LANES:
        raise ValueError(
            f"mla_decode takes a pool [L, P, 1, page, W] and queries "
            f"[B, H, W] with W and value_width whole lane tiles; got pool "
            f"{pool.shape}, q {q.shape}, value_width {value_width}")
    maxp = tables.shape[1]
    group = min(GROUP, maxp)
    compiler_params = None
    if not interpret:
        # Rows run in order: row 0 clears the buffer.
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, page=page, group=group,
                          value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_width),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group * page, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2, group)),
                pltpu.VMEM((H, value_width), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="mla_decode",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


def mla_decode_reference(q: jax.Array, pool: jax.Array, layer,
                         tables: jax.Array, pos: jax.Array, *, scale: float,
                         value_width: int) -> jax.Array:
    """`mla_decode_attention` in plain ``jnp``: every row's table
    gathered whole ([B, maxp·page, W]), the columns past its position
    and its holes masked, the softmax in float32."""
    page = pool.shape[-2]
    got = pool[layer, jnp.maximum(tables, 0), 0]  # [B, maxp, page, W]
    latent = got.reshape(got.shape[0], -1, got.shape[-1])
    col = jnp.arange(latent.shape[1])[None, :]
    valid = ((col <= pos[:, None]) & (pos[:, None] >= 0)
             & jnp.repeat(tables >= 0, page, axis=1))
    s = jnp.einsum("bhw,btw->bht", q, latent).astype(jnp.float32) * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jnp.where(valid[:, None, :],
                  jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / jnp.where(denom == 0.0, 1.0, denom)).astype(q.dtype)
    return jnp.einsum("bht,btc->bhc", p, latent[..., :value_width])
