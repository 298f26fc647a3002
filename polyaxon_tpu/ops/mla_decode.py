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
prefetched scalar, grid ``(B,)``, a loop over the row's live pages, each
page copied by its own DMA; nothing is fetched past a row's length, an
idle row (``pos = -1``) costs no copy and gives zeros, a hole
(``tables[b, p] < 0``) is masked by its page's columns. What the loop
adds is its schedule, so that the copy engine, the matrix unit and the
vector units work at the same time (counted on the chip: PERF.md §5-6,
PR 49):

- a *turn* is ``BLOCKS`` *sub-blocks* of ``SUB_PAGES`` pages (2 x 512
  keys at the serving page) in one of ``STAGES`` buffers. The copies
  run ``STAGES - 1`` turns ahead of the turn computed on, and past the
  row's end into the next row's first turns (the stage and the count
  of turns already begun are carried from row to row in SMEM), so that
  a row's first turn does not wait for its pages from a standing start;
- a whole turn with a whole turn to begin (all of a row but its edges)
  is one straight line under one condition: one wait (a stage's copies
  signal one semaphore, the wait is for the bytes of them all), then
  the sums, with the next copies' starts laid between the sub-blocks,
  so that a descriptor's scalar work goes under the matrix and vector
  work. The compiler's bounds checks of every descriptor are off
  (they were a third of a turn's instructions); the page id is clamped
  to the pool instead. Only a row's edges go page by page, in a scalar
  loop. Every run of starts or waits is one ``fori_loop`` traced once
  (the whole turn's unrolled by the compiler): a turn unrolled in
  Python cost the server a quarter of a minute of tracing at every
  start, whatever the compile cache held;
- each sub-block has its scores, maximum, ``exp``, sum and value
  product to itself, so in straight-line code one sub-block's matmuls
  lie under another's softmax; the parts are merged into the running
  float32 maximum, sum and accumulator once a turn (the online-softmax
  merge, between sub-blocks as between turns). The straight line has
  nothing to mask (no hole, which the starts of a whole turn note a
  stage as they read the table, and no column past the row's position)
  and takes the sums without the mask; a turn that has is an edge.

A table narrower than a turn makes as many sub-blocks as cover it, one
narrower than a sub-block the one sub-block of its width: decided from
the shape (`turn_shape`). The kernel is named ``mla_decode``, one call a
layer a step. Off the chip the model runs `mla_decode_reference`, the
same sums over gathered pages in plain ``jnp``; under ``interpret`` the
kernel runs on the CPU for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from polyaxon_tpu.ops.flash import resolve_interpret
from polyaxon_tpu.ops.paged_attention import (LANES, NEG_INF, _live_columns,
                                              _quot)

# The streamed loop's schedule (PERF.md §5-6, PR 49: counted on the
# chip at the serving cell's shape). A sub-block is what one softmax
# chain covers, 512 keys at the serving page; a turn is BLOCKS of them,
# copied and waited for as one; STAGES turns' buffers, so that
# STAGES - 1 turns' pages are in flight while one is computed on.
SUB_PAGES = 32
BLOCKS = 2
STAGES = 3


class _Table:
    """The prefetched block table, flat in SMEM, read as ``[B, maxp]``:
    an entry of a flat array costs the scalar core one add, where the
    tiles of two dimensions cost it eight operations a page copied."""

    def __init__(self, ref, maxp: int):
        self.ref, self.shape = ref, (ref.shape[0] // maxp, maxp)

    def __getitem__(self, at):
        row, p = at
        return self.ref[row * self.shape[1] + p]


def _mla_kernel(
    tables_ref,  # scalar prefetch: [B · maxp] int32 page ids (-1 = hole)
    pos_ref,  # scalar prefetch: [B] int32 row positions (-1 = idle)
    layer_ref,  # scalar prefetch: [1] int32, the layer whose pages to read
    q_ref,  # [1, H, W]
    c_hbm,  # [L, P, 1, page, W], left in HBM
    o_ref,  # [1, H, C]
    c_buf,  # VMEM [STAGES, G·page, W]: a turn's pages a stage
    sem,  # DMA semaphores [STAGES]: a stage's copies share one
    carry,  # SMEM [2]: the stage of this row's first turn, its turns begun
    holes,  # SMEM [STAGES]: negative unless the stage's turn is whole, no hole
    acc_ref,  # VMEM [H, C] f32
    m_ref,  # VMEM [H, LANES] f32
    l_ref,  # VMEM [H, LANES] f32
    *,
    scale: float,
    page: int,
    maxp: int,
    sub_pages: int,
    blocks: int,
    value_width: int,
):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    table = _Table(tables_ref, maxp)  # for `_live_columns`
    group = sub_pages * blocks  # pages a turn
    sub = sub_pages * page  # keys a sub-block
    ahead = STAGES - 1  # turns in flight beside the one computed on
    layer = layer_ref[0]
    pos = pos_ref[b]

    def pages_of(row):
        """The pages row ``row`` holds: 0 when idle, and past the last."""
        n = jnp.minimum(
            _quot(pos_ref[jnp.minimum(row, rows - 1)] + page, page), maxp)
        return jnp.where(row < rows, n, 0)

    n_pages, next_pages = pages_of(b), pages_of(b + 1)
    n_turns = _quot(n_pages + group - 1, group)
    next_turns = _quot(next_pages + group - 1, group)

    @pl.when(b == 0)
    def _clear():
        # A turn's unfetched pages are masked out of the probabilities,
        # and 0 x what an earlier row left there is 0; only what the
        # buffer held before the first copy is not known to be finite.
        c_buf[:] = jnp.zeros_like(c_buf)
        carry[0] = 0
        carry[1] = 0

    first_stage, begun = carry[0], carry[1]

    def turn_ahead(turn):
        """(row, first page, pages the row holds) of the turn ``ahead``
        turns on: this row's, or the next row's once this row's are all
        begun, so that a row finds its first turns under way."""
        own = turn + ahead < n_turns
        return (jnp.where(own, b, jnp.minimum(b + 1, rows - 1)),
                jnp.where(own, turn + ahead, turn + ahead - n_turns) * group,
                jnp.where(own, n_pages, next_pages))

    def begin(row, first, stage, lo, hi, holes_or, unroll: bool = False):
        """Begin the copies of pages ``first + lo .. first + hi`` of
        ``row`` into their places of ``stage``; returns ``holes_or``
        or-ed with the table's entries they read (page ids are not
        negative: the bitwise or is, with a hole). A hole's page is
        masked; the clamp keeps every read inside the pool, whatever the
        entry (the compiler's own checks of every descriptor are off:
        they were a third of a turn's instructions). One loop, traced
        once: unrolled where the bounds are static (a whole turn's
        starts, laid between the sums), a scalar loop at a row's edges."""
        base = row * maxp + first

        def one(i, entries):
            entry = tables_ref[base + i]
            pltpu.make_async_copy(
                c_hbm.at[layer, jax.lax.clamp(0, entry, c_hbm.shape[1] - 1),
                         0],
                c_buf.at[stage, pl.ds(pl.multiple_of(i * page, page), page)],
                sem.at[stage]).start()
            return entries | entry

        return jax.lax.fori_loop(lo, hi, one, holes_or, unroll=unroll)

    def begin_edge(ahead_turn, stage):
        """Begin what its row holds of a turn, page by page, and note
        whether that was a whole turn without a hole."""
        row, first, live = ahead_turn
        count = jnp.clip(live - first, 0, group)
        entries = begin(row, first, stage, 0, count, jnp.int32(0))
        holes[stage] = jnp.where(count == group, entries, -1)

    def wait(turn, stage, whole: bool):
        if whole:
            # The stage's copies signal one semaphore: one wait for the
            # bytes of them all.
            pltpu.make_async_copy(c_buf.at[stage], c_buf.at[stage],
                                  sem.at[stage]).wait()
        else:
            # One wait a page the turn holds, each for a page's bytes.
            one_page = pltpu.make_async_copy(
                c_buf.at[stage, pl.ds(0, page)],
                c_buf.at[stage, pl.ds(0, page)], sem.at[stage])

            def wait_one(_, carried):
                one_page.wait()
                return carried

            jax.lax.fori_loop(
                0, jnp.minimum(n_pages - turn * group, group), wait_one,
                None)

    def accumulate(turn, stage, masked: bool, between):
        """One online-softmax update over a turn: each sub-block's
        scores, maximum, sum and value product on their own (no
        sub-block waits for another's softmax), merged into the
        running float32 maximum, sum and accumulator at the end.
        ``between(j)`` is laid behind sub-block ``j``."""
        m_prev = m_ref[:, :1]
        parts = []
        for j in range(blocks):
            c = c_buf[stage, pl.ds(j * sub, sub)]  # [T, W]
            s = jax.lax.dot_general(
                q_ref[0], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, T]
            if masked:
                mask = _live_columns(
                    table, b, turn * group + j * sub_pages, pos,
                    page=page, group=sub_pages)[0]  # [1, T]
                s = jnp.where(mask, s, NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            if masked:
                p = jnp.where(mask, p, 0.0)
            pv = jnp.dot(p.astype(c.dtype), c[:, :value_width],
                         preferred_element_type=jnp.float32)
            parts.append((m, jnp.sum(p, axis=-1, keepdims=True), pv))
            between(j)
        m_new = functools.reduce(jnp.maximum, [m for m, _, _ in parts],
                                 m_prev)
        alpha = jnp.exp(m_prev - m_new)
        acc, l_new = acc_ref[:] * alpha, l_ref[:, :1] * alpha
        for m, l, pv in parts:
            weight = jnp.exp(m - m_new)
            acc, l_new = acc + pv * weight, l_new + l * weight
        acc_ref[:] = acc
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def take_turn(turn, stage, ahead_stage, ahead_turn, steady: bool):
        """A steady turn (whole and with nothing to mask, a whole turn
        to begin: all of a row but its edges) is one straight line: the
        one wait, then the sums with the starts of the turn ``ahead`` on
        laid between the sub-blocks, so that the scalar work of a
        descriptor goes under the matrix and vector work. At a row's
        edges the starts and the waits go page by page, and the sums
        take the mask."""
        if not steady:
            begin_edge(ahead_turn, ahead_stage)
            wait(turn, stage, False)
            accumulate(turn, stage, True, lambda j: None)
            return
        row, first, _ = ahead_turn
        entries = [jnp.int32(0)]

        def between(j):
            entries[0] = begin(row, first, ahead_stage, j * sub_pages,
                               (j + 1) * sub_pages, entries[0], unroll=True)

        wait(turn, stage, True)
        accumulate(turn, stage, False, between)
        holes[ahead_stage] = entries[0]

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def wrapped(stage):
        return jnp.where(stage >= STAGES, stage - STAGES, stage)

    def begin_unbegun(v, carried):
        # Turn v of this row (or of the next, past this row's end),
        # unless the row before began it.
        pl.when(v >= begun)(lambda: begin_edge(
            turn_ahead(v - ahead), wrapped(first_stage + v)))
        return carried

    jax.lax.fori_loop(0, ahead, begin_unbegun, None)

    def turn_body(turn, stage):
        ahead_stage = wrapped(stage + ahead)
        ahead_turn = _, first, live = turn_ahead(turn)
        whole = (turn + 1) * group
        # Whole, with a whole turn to begin, no hole and no column past
        # the row's position.
        steady = ((whole <= n_pages) & (first + group <= live)
                  & (holes[stage] >= 0) & (whole * page <= pos + 1))
        for case in (True, False):
            pl.when(steady == case)(functools.partial(
                take_turn, turn, stage, ahead_stage, ahead_turn, case))
        return wrapped(stage + 1)

    carry[0] = jax.lax.fori_loop(0, n_turns, turn_body, first_stage)
    carry[1] = jnp.minimum(ahead, next_turns)
    l = l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)  # idle row → zeros
    o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def turn_shape(maxp: int) -> tuple[int, int]:
    """(pages a sub-block, sub-blocks a turn) over a table ``maxp``
    wide: one narrower than a sub-block makes the one sub-block of its
    width, one narrower than a turn as many sub-blocks as cover it."""
    sub_pages = min(SUB_PAGES, maxp)
    return sub_pages, min(BLOCKS, pl.cdiv(maxp, sub_pages))


def schedule_stats(maxp: int, page: int) -> dict:
    """The shape the loop runs at over tables ``maxp`` wide of pages of
    ``page`` tokens, as ``/v1/stats`` says it."""
    sub_pages, blocks = turn_shape(maxp)
    return {"mla_decode_keys_per_turn": sub_pages * blocks * page,
            "mla_decode_turns_in_flight": STAGES - 1}


def _attend(q, pool, layer, tables, pos, scale, value_width, interpret):
    B, H, W = q.shape
    page = pool.shape[-2]
    sub_pages, blocks = turn_shape(tables.shape[1])
    compiler_params = None
    if not interpret:
        # Rows run in order: row 0 clears the buffers, and each row
        # begins the next one's first copies. No descriptor is checked
        # against its bounds: `copy` clips every page id to the pool.
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True)
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, page=page,
                          maxp=tables.shape[1], sub_pages=sub_pages,
                          blocks=blocks, value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_width),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((STAGES, blocks * sub_pages * page, W),
                           pool.dtype),
                pltpu.SemaphoreType.DMA((STAGES,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SMEM((STAGES,), jnp.int32),
                pltpu.VMEM((H, value_width), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="mla_decode",
    )(tables.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32), layer, q,
      pool)


# Jitted, so that a program's call sites (one a layer) share a lowering
# of the unrolled turn.
_call = jax.jit(_attend, static_argnums=(5, 6, 7))


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
    W = q.shape[-1]
    _, _, one, _, width = pool.shape
    if one != 1 or width != W or W % LANES or value_width % LANES:
        raise ValueError(
            f"mla_decode takes a pool [L, P, 1, page, W] and queries "
            f"[B, H, W] with W and value_width whole lane tiles; got pool "
            f"{pool.shape}, q {q.shape}, value_width {value_width}")
    return _call(q, pool, jnp.asarray(layer, jnp.int32).reshape(1), tables,
                 pos, float(scale), value_width, resolve_interpret(interpret))


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
