"""Pallas paged-attention decode kernel (TPU).

The XLA gather formulation of paged decode (``models/llama.py``
``paged_attn_step``) materializes every row's gathered pages
([B, maxp·page, KV, Hd]) in HBM each step — 2× the cache traffic of
reading it once. This kernel streams each row's pages straight from
the pool through VMEM with an online-softmax accumulator (the flash
recipe from ``ops/flash.py``, specialized to q-length 1), the
scalar-prefetched block tables and positions naming the pages.

The call takes every layer's pool, stacked ``[L, P, KV, page, Hd]``,
and is told the layer as a prefetched scalar. The decode program then
never slices one layer's pool out to hand it over, which it did by
copying it; with the step's K and V written by whole pages
(``models/llama.py paged_write_step``), this kernel and that write are
all that touches the pool there, and neither moves it.

The work a call issues follows the pages that live rows hold, not
``slots × kv heads × table width``:

- a page of one layer is taken whole, all KV heads of the shard in one
  copy (``[KV, page, Hd]`` is contiguous in the pool), and the two
  matmuls are batched over KV on ``q`` as ``[KV, rep, Hd]``;
- several pages make one step (8 or 16: 128 or 256 tokens at the
  serving page of 16), so a step's fixed cost is paid that much less
  often and the matmuls see whole lane tiles of columns;
- nothing is fetched and nothing computed past a row's length, idle
  rows (``pos = -1``) included; a hole (``tables[b, p] < 0``) inside a
  live range is masked by its page's columns.

One algorithm in the two forms the TPU compiler lets through, chosen
by the one shape that decides it, the pool's last dimension:

- *streamed* (``Hd % 128 == 0``): grid ``(B,)``, the pools left in
  HBM, a loop over the row's live pages with each page
  (``pool.at[layer, page id]``) copied by its own DMA into one of two
  ``[KV, G·page, Hd]`` buffers while the other is computed on. Time
  is proportional to the live context: an idle row costs about 2 µs,
  and at the benchmark's Mistral shape (16 rows of ≈ 600 tokens of a
  4,096-token table) a call took 0.12 ms on a v5e where the pipelined
  form took 0.74 ms.
- *pipelined* (any other head size): grid ``(B, maxp / G)``, the pool
  passed G times as K and G times as V, each input a
  ``(None, 1, KV, page, Hd)`` block whose index map names the layer
  and the row's page ``j·G + i`` clamped to the last live page that
  input took, so a step past the row's length names the blocks the
  step before named and the pipeline fetches nothing anew. What such
  a step still costs is its index maps: about 0.05 µs an input a step
  on a v5e, 0.4 ms a call at 4,096 table entries whatever is live
  (which is why they shift and mask where they would divide).

What the compiler refused (jax 0.9.0 / libtpu 0.0.34, asked about a
described ``v5e:2x2``): the streamed form from a pool whose last
dimension is 64 — "Slice shape along dimension 3 must be aligned to
tiling (128), but is 64" — for ``k_hbm.at[pid]``, for
``.at[pl.ds(pid, 1)]``, for a whole-page destination and for the pool
seen as ``[P·KV·page, 64]`` rows; seen as ``[P, KV·page·Hd/128, 128]``
it compiles, behind a copy of the whole pool a call. The BlockSpec
pipeline takes the same page, its block's two trailing dims being the
array's own.

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


def _quot(x, n: int):
    """x // n for x >= 0: a shift where n is a power of two (the scalar
    core has no divider, and the index maps run once an input a step)."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return jax.lax.div(x, jnp.int32(n))


def _rem(x, n: int):
    """x % n for x >= 0."""
    if n & (n - 1) == 0:
        return x & (n - 1)
    return jax.lax.rem(x, jnp.int32(n))


def _live_columns(tables_ref, b, first, pos, *, page: int, group: int,
                  window=None):
    """[1, 1, G·page] mask of the step's columns that count: at or
    before the row's position (and, under a ``window``, less than that
    many positions before it), in a page that is allocated (a hole, or
    an entry past the table's width, reads -1). ``first`` is the step's
    first page of the row."""
    maxp = tables_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, group * page), 2)
    slot = _quot(col, page)
    entry = jnp.full_like(col, -1)
    for i in range(group):
        p = first + i
        t = jnp.where(p < maxp, tables_ref[b, jnp.minimum(p, maxp - 1)], -1)
        entry = jnp.where(slot == i, t, entry)
    live = (first * page + col <= pos) & (entry >= 0)
    if window is not None:
        live &= first * page + col > pos - window
    return live


def _accumulate(q, k, v, mask, acc_ref, m_ref, l_ref, *, scale: float):
    """One online-softmax update over a step's columns: q [KV, rep, Hd],
    k and v [KV, T, Hd], mask [1, 1, T]; the running maximum, sum and
    accumulator stay float32."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    s = jnp.where(mask, s * scale, NEG_INF)  # [KV, rep, T]

    m_prev = m_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _reset(acc_ref, m_ref, l_ref):
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)


def _write_out(o_ref, acc_ref, l_ref):
    l = l_ref[:, :, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)  # idle row → zeros
    o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _streamed_kernel(
    tables_ref,  # scalar prefetch: [B, maxp] int32 page ids (-1 = hole)
    pos_ref,  # scalar prefetch: [B] int32 row positions (-1 = idle)
    layer_ref,  # scalar prefetch: [1] int32, the layer whose pages to read
    q_ref,  # [1, KV, rep, Hd]
    k_hbm,  # [L, P, KV, page, Hd], left in HBM
    v_hbm,
    o_ref,  # [1, KV, rep, Hd]
    k_buf,  # VMEM [2, KV, G·page, Hd]: the step computed on, the next
    v_buf,
    sem,  # DMA semaphores [2 (K, V), 2 (buffer), G]
    acc_ref,  # VMEM [KV, rep, Hd] f32
    m_ref,  # VMEM [KV, rep, LANES] f32
    l_ref,  # VMEM [KV, rep, LANES] f32
    *,
    scale: float,
    page: int,
    group: int,
    window=None,
):
    """One row a grid step; a loop over the row's live pages, G a turn,
    each page copied from the pool by its own DMA into the buffer the
    next turn computes on. An idle row starts no copy and no turn.
    Under a ``window`` the loop starts at the turn that holds the
    window's first page, and no page before that one is copied."""
    b = pl.program_id(0)
    maxp = tables_ref.shape[1]
    pos = pos_ref[b]
    layer = layer_ref[0]
    n_pages = jnp.minimum(_quot(pos + page, page), maxp)  # 0 when idle
    n_turns = _quot(n_pages + group - 1, group)
    first_page = first_turn = 0
    if window is not None:
        first_page = _quot(jnp.maximum(pos - (window - 1), 0), page)
        first_turn = _quot(first_page, group)

    def each_live_copy(turn, buf, act):
        """`act` ("start" or "wait") on the K and the V copy of each of
        the turn's pages the row holds, into buffer `buf`."""
        for i in range(group):
            p = turn * group + i
            # A hole's page is masked; the clamps keep the reads legal.
            pid = jnp.maximum(tables_ref[b, jnp.minimum(p, maxp - 1)], 0)
            rows = pl.ds(i * page, page)
            copies = (
                pltpu.make_async_copy(
                    k_hbm.at[layer, pid], k_buf.at[buf, :, rows],
                    sem.at[0, buf, i]),
                pltpu.make_async_copy(
                    v_hbm.at[layer, pid], v_buf.at[buf, :, rows],
                    sem.at[1, buf, i]))

            held = p < n_pages
            if window is not None:
                held &= p >= first_page

            @pl.when(held)
            def _act():
                for copy in copies:
                    getattr(copy, act)()

    @pl.when(b == 0)
    def _clear():
        # A turn's unfetched pages are masked out of the probabilities,
        # and 0 x what an earlier row left there is 0; only what the
        # buffer held before the first copy is not known to be finite.
        v_buf[:] = jnp.zeros_like(v_buf)

    _reset(acc_ref, m_ref, l_ref)

    @pl.when(n_turns > 0)
    def _first():
        each_live_copy(first_turn, _rem(first_turn, 2), "start")

    def turn_body(turn, carry):
        buf = _rem(turn, 2)

        @pl.when(turn + 1 < n_turns)
        def _next():
            each_live_copy(turn + 1, 1 - buf, "start")

        each_live_copy(turn, buf, "wait")
        mask = _live_columns(tables_ref, b, turn * group, pos,
                             page=page, group=group, window=window)
        _accumulate(q_ref[0], k_buf[buf], v_buf[buf], mask,
                    acc_ref, m_ref, l_ref, scale=scale)
        return carry

    jax.lax.fori_loop(first_turn, n_turns, turn_body, None)
    _write_out(o_ref, acc_ref, l_ref)


def _pipelined_kernel(
    tables_ref,  # scalar prefetch: [B, maxp] int32 page ids (-1 = hole)
    pos_ref,  # scalar prefetch: [B] int32 row positions (-1 = idle)
    layer_ref,  # scalar prefetch: [1] int32; only the index maps read it
    q_ref,  # [1, KV, rep, Hd]
    *refs,  # G K pages and G V pages, each [1, KV, page, Hd], selected
    # by the index maps; then o_ref [1, KV, rep, Hd] and the VMEM
    # scratch: acc [KV, rep, Hd] f32, m and l [KV, rep, LANES] f32
    scale: float,
    page: int,
    group: int,
    window=None,
):
    """Grid (rows, table width / G): the BlockSpec pipeline brings a
    step's G pages; a step past the row's length computes nothing and,
    by the index maps, fetches nothing. Under a ``window`` a step whose
    pages all lie behind it computes nothing either (its pages are
    still fetched: the streamed form is the one a window is served
    by)."""
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * group:]
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    first = j * group  # the step's first page of the row

    @pl.when(j == 0)
    def _init():
        _reset(acc_ref, m_ref, l_ref)

    reached = (pos >= 0) & (first * page <= pos)
    if window is not None:
        reached &= (first + group) * page > pos - (window - 1)

    @pl.when(reached)
    def _compute():
        def pages(page_refs):  # [KV, G·page, Hd]
            return jnp.concatenate([r[0] for r in page_refs], axis=1)

        mask = _live_columns(tables_ref, b, first, pos,
                             page=page, group=group, window=window)
        _accumulate(q_ref[0], pages(k_refs), pages(v_refs), mask,
                    acc_ref, m_ref, l_ref, scale=scale)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        _write_out(o_ref, acc_ref, l_ref)


def paged_decode_attention(
    q: jax.Array,  # [B, H, Hd] — the single decode position per row
    k_pool: jax.Array,  # [L, P, KV, page, Hd]: every layer's pages
    v_pool: jax.Array,
    layer,  # int or traced int32 scalar: the layer whose pages are read
    tables: jax.Array,  # [B, maxp] int32 (-1 = unallocated)
    pos: jax.Array,  # [B] int32 (-1 = idle row → zeros out)
    *,
    window: int | None = None,  # attend the last `window` positions only
    interpret: bool | None = None,  # None = interpret on the CPU backend
) -> jax.Array:
    """Attention of each row's query against its pages of layer
    ``layer`` (positions 0..pos inclusive — the current step's K/V must
    already be written to the pool). Returns [B, H, Hd].

    With ``window`` (static) a row attends positions ``pos - window +
    1 .. pos``: the streamed loop starts at the window's first page, so
    a table whose entries before it were released (-1;
    ``serving/paged.py WindowedPagePool``) is never read there. Such a
    call is named ``window_decode`` and not ``paged_decode``: a reader
    of the device trace that counts a row's whole length for every call
    of the latter name would count too much for this one.

    The pools come stacked over the layers and the layer as a
    prefetched scalar, so the program around the call never slices a
    layer's pool out (a copy of it, a layer a step; models/llama.py,
    the paged surface's comment); one layer's pool goes in as
    ``pool[None]`` with layer 0. A layer is laid out
    ``[P, KV, page, Hd]`` so a page is one contiguous ``[KV, page, Hd]``
    block whose two trailing dims are the array's own, which is what
    the Mosaic lowering demands of a block that is not a multiple of
    the (8, 128) tile. Under a multi-device mesh the call runs per
    ``tp`` shard of the kv heads (``compat.shard_kernel``)."""
    interpret = resolve_interpret(interpret)
    B, H, Hd = q.shape
    KV = k_pool.shape[2]
    _, head_axis = compat.kernel_axes(B, KV)
    heads = P(None, head_axis, None)
    pool = P(None, None, head_axis, None, None)
    return compat.shard_kernel(
        functools.partial(_paged_decode, window=window, interpret=interpret),
        in_specs=(heads, pool, pool, P(), P(), P()),
        out_specs=heads,
    )(q, k_pool, v_pool, jnp.asarray(layer, jnp.int32).reshape(1),
      tables.astype(jnp.int32), pos.astype(jnp.int32))


def _paged_decode(q, k_pool, v_pool, layer, tables, pos, *, window,
                  interpret: bool):
    B, H, Hd = q.shape
    _, _, KV, page, _ = k_pool.shape
    maxp = tables.shape[1]
    rep = H // KV
    # The one thing the kernel adapts on: Mosaic copies a page out of a
    # pool by hand only where the pool's last dimension fills the lanes.
    streamed = Hd % LANES == 0
    # Pages a step: 256 tokens a turn of the streamed loop, 128 a step
    # of the pipeline (two inputs a page), at the serving page of 16.
    group = min(16 if streamed else 8, maxp)

    def row_map(b, *_):
        return (b, 0, 0, 0)

    row_spec = pl.BlockSpec((1, KV, rep, Hd), row_map)
    softmax_state = [
        pltpu.VMEM((KV, rep, Hd), jnp.float32),
        pltpu.VMEM((KV, rep, LANES), jnp.float32),
        pltpu.VMEM((KV, rep, LANES), jnp.float32),
    ]
    if streamed:
        kernel, grid = _streamed_kernel, (B,)
        page_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        pools = (k_pool, v_pool)
        scratch = [
            pltpu.VMEM((2, KV, group * page, Hd), k_pool.dtype),
            pltpu.VMEM((2, KV, group * page, Hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, group)),
            *softmax_state]
    else:
        def page_map(i):
            def index(b, j, tables_ref, pos_ref, layer_ref):
                # The page DMA: the layer, then the row's block table
                # gives the block index along the pool axis. Past the
                # row's last live page, input i names the last live
                # page it took itself (the largest p <= last with
                # p % G == i), so those steps name the block the step
                # before named and the pipeline fetches nothing. The
                # clamp to 0 only keeps a hole's index legal.
                last = jnp.minimum(
                    _quot(jnp.maximum(pos_ref[b], 0), page), maxp - 1)
                own = jnp.where(
                    last >= i, last - _rem(last - i, group), last)
                p = jnp.minimum(j * group + i, own)
                return (layer_ref[0], jnp.maximum(tables_ref[b, p], 0),
                        0, 0, 0)
            return index

        kernel, grid = _pipelined_kernel, (B, pl.cdiv(maxp, group))
        page_specs = [pl.BlockSpec((None, 1, KV, page, Hd), page_map(i))
                      for i in range(group)] * 2
        pools = (*[k_pool] * group, *[v_pool] * group)
        scratch = softmax_state

    compiler_params = None
    if not interpret:
        # The streamed kernel's rows run in order (row 0 clears the
        # buffer); the pipeline's are independent.
        compiler_params = pltpu.CompilerParams(dimension_semantics=(
            ("arbitrary",) if streamed else ("parallel", "arbitrary")))
    out = pl.pallas_call(
        functools.partial(kernel, scale=Hd ** -0.5, page=page, group=group,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[row_spec, *page_specs],
            out_specs=row_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, Hd), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="paged_decode" if window is None else "window_decode",
    )(tables, pos, layer, q.reshape(B, KV, rep, Hd), *pools)
    return out.reshape(B, H, Hd)
