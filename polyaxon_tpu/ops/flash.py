"""Blocked flash attention as a Pallas TPU kernel.

TPU-first design (pallas_guide.md): a grid step of the forward keeps a
block of ``block_q`` query rows, copies a block of ``block_k`` keys and
values that is several sub-blocks long and walks it with an inner loop,
the online-softmax running max/denominator and the output accumulator
in f32 VMEM scratch — O(S) memory instead of the O(S²) logits tensor,
with every matmul on the MXU (``preferred_element_type=f32``). The
loop's trip counts come from the block's indices (``_turn_ranges``):
sub-blocks above the causal diagonal or below the window's band are
never visited, a sub-block that lies wholly inside takes a turn with no
mask (no iota, compare or select), only one that meets the diagonal,
the window's edge or packed segments builds one. A grid step with
nothing to visit names the block the step before left resident
(``_resident_block``), so it copies nothing. GQA is handled in the K/V
index maps (kv head = q head // n_rep) so grouped heads are never
materialized ``n_rep`` times in HBM. The tiles follow what a call can
see (lengths, head size, window, which kernel) in one place:
``auto_blocks``.

What a start of a server pays is part of the design (PERF.md §6, PRs 37,
49-51): a ``pallas_call`` is traced and lowered at every call site of
every program whatever the compile cache holds, and a static layer
plan's prefill has a site a layer. So each loop here is one
``fori_loop`` whose body is traced once (two bodies a kernel, the plain
turn and the masked one, whatever the sequence length), and the entry
is jitted (``_call``): a program's sites of one shape and window share
one lowering. ``tests/test_ops.py TestFlashText`` holds both.

The backward pass under ``jax.custom_vjp`` has two implementations:

- **Pallas** (default on real TPU): the FlashAttention-2 split — a
  dk/dv kernel that keeps a block of keys and walks the query rows it
  copies (GQA groups accumulate onto their shared kv head inside VMEM
  scratch, so dk/dv never materialize per-q-head), its score tiles
  transposed (``s^T = k q^T``, ``dp^T = v dO^T``) so that both
  accumulating products are plain ``[bk, bq] @ [bq, D]`` and ``lse`` /
  ``delta`` are lane-dense rows, and a dq kernel built like the
  forward. Both recompute P from the saved logsumexp residual, keep
  every matmul on the MXU in f32 accumulation, and walk and skip
  sub-blocks as the forward does.
- **Chunked XLA** (the CPU test mesh's interpret mode, and the parity
  reference): recomputes attention probabilities one K/V block at a
  time from the same residual, so it also never materializes S×S.

Interpret mode exists for the CPU test mesh only: ``resolve_interpret``
refuses it on any other backend, so a chip run cannot land on an
interpreted kernel. Shapes that do not tile give way to the einsum
reference with a warning per shape (``implementation_for`` is the one
decision, so callers can ask which implementation a shape gets).

The reference delegates attention entirely to user frameworks
(SURVEY.md §2b: no model math in-repo); this kernel is owned surface.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from polyaxon_tpu.parallel import compat

NEG_INF = -1e30
LANES = 128  # TPU lane width: scratch vectors are kept lane-broadcast


class KernelFallbackWarning(UserWarning):
    """A Pallas kernel gave way to its reference implementation."""


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether a Pallas TPU kernel runs interpreted. Interpret mode is
    the CPU backend's only way to run these kernels (the test mesh);
    anywhere else it is refused, so no chip path can end up on an
    interpreted kernel. ``interpret=False`` under the CPU backend is
    the AOT case: compiling for a described TPU topology."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            "Pallas interpret mode is for the CPU backend only; the "
            f"default backend here is `{jax.default_backend()}`")
    return bool(interpret)


def pick_block(seq: int, preferred: int) -> int:
    """Largest power-of-two block <= preferred that divides seq (the
    shared tiling rule — also used by models.common.chunked_lm_loss)."""
    block = min(preferred, seq)
    while block > 1 and seq % block:
        block //= 2
    return block


# What a grid cell may hold by `_tile_bytes`' estimate: Mosaic's scoped
# limit is 16 MiB a kernel on v5e unless a call raises it, and none here
# does; the rest is headroom for what the estimate leaves out
# (tests/test_aot_tpu_compile.py compiles the cells' shapes: the fit is
# the compiler's word, this is the screen before it).
VMEM_BUDGET = 14 * 2**20

# A grid step of each kernel: the rows it keeps (RESIDENT: query rows in
# the forward and dq, keys in dk/dv), the positions of the other operand
# it copies (COPIED) and the sub-block of those a turn of its inner loop
# takes (SUB). Counted on a TPU v5e at Mistral-7B's training shape
# (3 x 4,096, 32 / 8 heads of 128; scripts/bench_flash.py, PERF.md §5-6,
# PRs 50-51): a step's fixed cost is paid once a copied block, a row's
# keys are copied once a KV head where the block is the whole row, and a
# 512 x 512 score tile keeps the MXU fed where 256 or 1,024 do not.
RESIDENT, COPIED, SUB = 512, 4096, 512
KERNELS = ("fwd", "dkdv", "dq")


def _tile_bytes(resident: int, copied: int, d: int) -> int:
    """Estimated VMEM residency of one grid cell of the forward (the
    backward kernels hold about as much: two operands kept and copied,
    two accumulators): the resident rows in and out, the
    double-buffered copied blocks of two streamed operands, a turn's
    float32 score tiles (scores, probabilities and a mask) and the
    float32 accumulator with its lane-broadcast m / l."""
    return (2 * 2 * resident * d * 2      # q in, o out (bf16, 2 buffers)
            + 2 * 2 * copied * d * 2      # k + v, double-buffered (bf16)
            + 3 * resident * min(SUB, copied) * 4  # score tiles (f32)
            + resident * d * 4            # o accumulator (f32)
            + 2 * resident * LANES * 4)   # m / l scratch (f32)


def auto_blocks(seq_q: int, seq_k: int, head_dim: int, *,
                window: Optional[int] = None,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                vmem_budget: int = VMEM_BUDGET) -> dict:
    """The one place the tiles are decided: per kernel (``fwd``,
    ``dkdv``, ``dq``) the ``(block_q, block_k, sub)`` of a grid step and
    of a turn of its inner loop, from what the call can see. A copied
    block is no longer than the window (a longer one copies keys its
    rows cannot see), and copied then resident are halved while the
    cell does not fit ``vmem_budget`` (wide heads); ``pick_block`` then
    makes them divide the sequence, as it does explicit sizes. An
    explicit ``block_q`` / ``block_k`` (the model's ``flash_block_q/k``)
    is the forward's tile as given and an upper limit on the
    backward's."""
    resident, copied = RESIDENT, COPIED
    while window and copied > max(window, LANES):
        copied //= 2
    while (_tile_bytes(resident, copied, head_dim) > vmem_budget
           and max(resident, copied) > LANES):
        if copied >= resident:
            copied //= 2
        else:
            resident //= 2
    out = {}
    for kernel in KERNELS:
        # dk/dv keeps the keys and walks the query rows.
        bq, bk = ((copied, resident) if kernel == "dkdv"
                  else (resident, copied))
        if block_q is not None:
            bq = block_q if kernel == "fwd" else min(bq, block_q)
        if block_k is not None:
            bk = block_k if kernel == "fwd" else min(bk, block_k)
        bq, bk = pick_block(seq_q, bq), pick_block(seq_k, bk)
        out[kernel] = (bq, bk, pick_block(bq if kernel == "dkdv" else bk,
                                          SUB))
    return out


def _clip(x, lo, hi):
    if all(isinstance(n, int) for n in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _count(y, sub: int, n: int):
    """How many of the sub-blocks ``0 .. n-1`` start at or under ``y``
    (``j * sub <= y``): plain arithmetic for static indices, two
    scalar operations a kernel's traced ones."""
    if isinstance(y, int):
        return max(0, min((y + sub) // sub, n))
    return jnp.minimum(jax.lax.div(jnp.maximum(y + sub, 0), sub), n)


def _turn_ranges(fixed0, fixed_n: int, base, sub: int, n_sub: int, *,
                 over_cols: bool, causal: bool, window: int):
    """``(lo, a, b, hi)`` over the ``n_sub`` sub-blocks of ``sub``
    positions that start at ``base`` on the looped axis, against the
    ``fixed_n`` positions from ``fixed0`` on the other: the sub-blocks
    ``[lo, hi)`` hold a position that contributes, and of those
    ``[a, b)`` hold none that is masked. ``over_cols``: the loop runs
    over keys and the fixed span is query rows (forward, dq); else over
    query rows against fixed keys (dk/dv). The loops' trip counts, the
    index maps' clamps and the choice of the masked turn are all this
    one rule: causal positions above the diagonal contribute nothing,
    with a sliding window those below the band neither (packed segments
    can mask anywhere: ``_walk`` then takes ``[lo, hi)`` masked)."""
    if not causal:
        return 0, 0, n_sub, n_sub
    count = functools.partial(_count, sub=sub, n=n_sub)
    last = fixed0 + fixed_n - 1 - base  # relative to the looped axis
    first = fixed0 - base
    if over_cols:  # keys at or under a row; the band's edge below
        hi, b = count(last), count(first + 1 - sub)
        lo = count(first + 1 - window - sub) if window else 0
        a = count(last - window) if window else 0
    else:  # rows at or over a key; the band's edge above
        lo, a = count(first - sub), count(last - 1)
        hi = count(last + window - 1) if window else n_sub
        b = count(first + window - sub) if window else n_sub
    hi = _clip(hi, lo, n_sub)
    a = _clip(a, lo, hi)
    return lo, a, _clip(b, a, hi), hi


def _resident_block(i, fixed0, fixed_n: int, block: int, n_blocks: int,
                    **rule):
    """The copied block a grid step names on the streamed axis: its own
    where it holds a visible position, else the nearest one that does,
    which the step before left resident, so that a skipped step copies
    nothing."""
    if not rule["causal"]:
        return i  # every block is visible
    lo, _, _, hi = _turn_ranges(fixed0, fixed_n, 0, block, n_blocks, **rule)
    return jnp.clip(i, jnp.minimum(lo, n_blocks - 1),
                    jnp.maximum(hi - 1, lo))


def _walk(ranges, turn, segments: bool) -> None:
    """``turn(j, masked)`` for the sub-blocks ``[lo, hi)`` of a copied
    block: one loop over those that lie wholly inside (the plain turn)
    and one over those that meet the diagonal or the window's edge, on
    either side of them (the masked turn), each a ``fori_loop`` traced
    once whose trip count the block's indices decide. Runs that a
    configuration cannot have are not traced."""
    lo, a, b, hi = ranges

    def never(n):  # a run this configuration cannot have
        return isinstance(n, int) and n == 0

    def loop(n, at, masked):
        def body(t, carry):
            turn(at(t), masked)
            return carry
        if not never(n):
            jax.lax.fori_loop(0, n, body, None)

    if segments:  # a mask anywhere: every visible sub-block takes one
        return loop(hi - lo, lambda t: lo + t, True)
    loop(b - a, lambda t: a + t, False)
    lead, tail = a - lo, hi - b  # masked sub-blocks under / over them
    if never(lead):
        loop(tail, lambda t: b + t, True)
    elif never(tail):
        loop(lead, lambda t: lo + t, True)
    else:
        loop(lead + tail,
             lambda t: jnp.where(t < lead, lo + t, b + t - lead), True)


def _sub_mask(diff0, shape, rows_axis: int, causal: bool, window: int,
              qseg, kseg):
    """The validity mask of a score tile on the masked turn: ``diff0``
    is row minus column at the tile's origin, ``rows_axis`` the tile
    axis the query rows lie on (1 in dk/dv's transposed tiles),
    ``qseg`` / ``kseg`` the segment ids shaped to broadcast over the
    tile. One source for the causal triangle, the window band and
    packed segments in all three kernels."""
    mask = None
    if causal:
        diff = (diff0 + jax.lax.broadcasted_iota(jnp.int32, shape, rows_axis)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis))
        mask = diff >= 0
        if window:
            mask &= diff < window
    if qseg is not None:
        seg = qseg == kseg
        mask = seg if mask is None else mask & seg
    return mask


def _lanes(x, width: int):
    """A lane-broadcast [rows, LANES] value over ``width`` lanes: whole
    lane tiles repeated where the width is a multiple of them (a block
    of a sequence shorter than the tiles may not be)."""
    if width <= LANES:
        return x[:, :width]
    if width % LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return pltpu.repeat(x, width // LANES, axis=1)


def _rows_to_lanes(row, n: int):
    """A lane-dense [1, n] row as the lane-broadcast [n, LANES] column
    the score tiles of the forward's orientation take."""
    return jnp.broadcast_to(row, (LANES, n)).T


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _segment_rows(segments: jax.Array) -> jax.Array:
    """[B, S] ids as [B, 1, S], so a (1, 1, block) tile's two trailing
    dims are whole: the Mosaic lowering refuses a (1, block) tile of a
    [B, S] array for any B > 1 (its second-to-last dim is neither the
    array's nor a multiple of 8)."""
    return segments.astype(jnp.int32)[:, None, :]


def _sub_rows(x: jax.Array, sub: int) -> jax.Array:
    """[..., S] values of the looped axis as [..., S / sub, 1, sub]: a
    sub-block is a leading index, since a lane offset cannot be
    traced."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // sub, 1, sub)


def _compiler_params(interpret: bool, n_parallel: int, n_arbitrary: int):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_arbitrary)


def _fwd_kernel(
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    *rest,  # [qseg [1,1,block_q], kseg [1,block_k/sub,1,sub] when
            # use_segments,] o [1,1,block_q,D], lse [1,1,1,block_q],
            # acc/m/l VMEM scratch
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    sub: int,     # keys a turn of the inner loop takes
    window: int,  # 0 = unbounded
    use_segments: bool,
):
    if use_segments:
        qseg_ref, kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        qseg_ref = kseg_ref = None
    qi, kc = pl.program_id(2), pl.program_id(3)
    n_kc = pl.num_programs(3)

    @pl.when(kc == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def turn(j, masked: bool):
        col = pl.multiple_of(j * sub, sub)
        k = k_ref[0, 0, pl.ds(col, sub), :]
        v = v_ref[0, 0, pl.ds(col, sub), :]
        s = _dot(q_ref[0, 0], k, (1, 1)) * scale  # [block_q, sub]
        if masked:
            mask = _sub_mask(
                qi * block_q - kc * block_k - col, s.shape, 0, causal,
                window,
                qseg_ref[0, 0][:, None] if use_segments else None,
                kseg_ref[0, j] if use_segments else None)
            s = jnp.where(mask, s, NEG_INF)
        # m and l stay lane-broadcast [block_q, LANES] from one turn to
        # the next: a row's value is spread over the lanes once, by the
        # reduction that made it, and whole lane tiles of it are
        # repeated over a wider tile for nothing.
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, sub))
        if masked:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = (acc_ref[:] * _lanes(alpha, acc_ref.shape[-1])
                      + _dot(p.astype(v.dtype), v, (1, 0)))
        m_ref[:] = m_new

    _walk(_turn_ranges(qi * block_q, block_q, kc * block_k, sub,
                       block_k // sub, over_cols=True, causal=causal,
                       window=window), turn, use_segments)

    @pl.when(kc == n_kc - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / _lanes(l_safe, acc_ref.shape[-1])
                       ).astype(o_ref.dtype)
        # Rows along the lanes: a [block_q, 1] block of float32 is one
        # value a 128-lane tile in HBM, 128 times the bytes.
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l_safe)).T[:1]


def _flash_fwd_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, KV, Sk, D]
    v: jax.Array,
    segments,  # [B, Sq] int32 or None (packed-sequence ids)
    causal: bool,
    scale: float,
    blocks: tuple[int, int, int],  # (block_q, block_k, sub): auto_blocks
    interpret: bool,
    window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    b, h, sq, d = q.shape
    kv = k.shape[1]
    sk = k.shape[2]
    n_rep = h // kv
    block_q, block_k, sub = blocks
    n_kc = sk // block_k
    use_segments = segments is not None
    rule = dict(over_cols=True, causal=causal, window=window)

    def qmap(b_, h_, qi, kc):
        return (b_, h_, qi, 0)

    def k_block(qi, kc):
        return _resident_block(kc, qi * block_q, block_q, block_k, n_kc,
                               **rule)

    def kmap(b_, h_, qi, kc):
        return (b_, h_ // n_rep, k_block(qi, kc), 0)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, sub=sub, window=window,
            use_segments=use_segments),
        grid=(b, h, sq // block_q, n_kc),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
        ] + ([
            pl.BlockSpec((1, 1, block_q), lambda b_, h_, qi, kc: (b_, 0, qi)),
            pl.BlockSpec((1, block_k // sub, 1, sub),
                         lambda b_, h_, qi, kc: (b_, k_block(qi, kc), 0, 0)),
        ] if use_segments else []),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b_, h_, qi, kc: (b_, h_, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret, 3, 1),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, *([_segment_rows(segments),
                  _sub_rows(segments.astype(jnp.int32), sub)]
                 if use_segments else []))
    return o, lse[:, :, 0]


def _flash_bwd_xla(
    causal: bool,
    scale: float,
    block_k: int,
    window: int,
    res,
    do: jax.Array,
    dlse: jax.Array,  # [B,H,Sq] cotangent of the lse output
):
    """Chunked recompute backward: O(Sq·block_k) live logits."""
    q, k, v, segments, o, lse = res  # q,o: [B,H,Sq,D]; lse: [B,H,Sq]
    b, h, sq, dh = q.shape
    kv = k.shape[1]
    sk = k.shape[2]
    n_rep = h // kv
    n_blocks = sk // block_k

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,Sq]
    rows = jnp.arange(sq)

    # [n_blocks, B, KV, block_k, D] views of K/V for the scan.
    k_blocks = jnp.moveaxis(k.reshape(b, kv, n_blocks, block_k, dh), 2, 0)
    v_blocks = jnp.moveaxis(v.reshape(b, kv, n_blocks, block_k, dh), 2, 0)

    # With a sliding window only q rows in [kb, kb + block_k + window)
    # can touch key block kb — restrict the recompute to that span so the
    # backward, like the forward, does O(S·window) work instead of O(S²).
    span = min(sq, block_k + window) if (causal and window) else sq

    def body(dq_acc, inputs):
        ki, kj, vj = inputs  # kj/vj: [B, KV, block_k, D]
        # GQA: expand kv heads to q heads for this block only.
        kj_h = jnp.repeat(kj, n_rep, axis=1) if n_rep > 1 else kj
        vj_h = jnp.repeat(vj, n_rep, axis=1) if n_rep > 1 else vj
        if span < sq:
            start = jnp.clip(ki * block_k, 0, sq - span)
            q_b = jax.lax.dynamic_slice_in_dim(q, start, span, axis=2)
            do_b = jax.lax.dynamic_slice_in_dim(do, start, span, axis=2)
            delta_b = jax.lax.dynamic_slice_in_dim(delta, start, span, axis=2)
            lse_b = jax.lax.dynamic_slice_in_dim(lse, start, span, axis=2)
            dlse_b = jax.lax.dynamic_slice_in_dim(dlse, start, span, axis=2)
            rows_b = start + jnp.arange(span)
        else:
            q_b, do_b, delta_b, lse_b, rows_b = q, do, delta, lse, rows
            dlse_b = dlse
        if segments is not None:
            seg_k = jax.lax.dynamic_slice_in_dim(
                segments, ki * block_k, block_k, axis=1)  # [B, block_k]
            seg_q = (jax.lax.dynamic_slice_in_dim(segments, start, span, axis=1)
                     if span < sq else segments)  # [B, span]
        s = (
            jnp.einsum(
                "bhqd,bhkd->bhqk", q_b, kj_h, preferred_element_type=jnp.float32
            )
            * scale
        )
        mask = None  # broadcastable [B?, 1, span, block_k]
        if causal:
            cols = ki * block_k + jnp.arange(block_k)
            mask = (rows_b[:, None] >= cols[None, :])[None, None]
            if window:
                mask &= (rows_b[:, None] - cols[None, :] < window)[None, None]
        if segments is not None:
            seg_mask = (seg_q[:, :, None] == seg_k[:, None, :])[:, None]
            mask = seg_mask if mask is None else mask & seg_mask
        if mask is not None:
            p = jnp.where(mask, jnp.exp(s - lse_b[..., None]), 0.0)
        else:
            p = jnp.exp(s - lse_b[..., None])
        dv_h = jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(do.dtype), do_b,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bhqd,bhkd->bhqk", do_b, vj_h, preferred_element_type=jnp.float32
        )
        # d lse/d s_j = p_j, so the lse cotangent enters ds additively.
        ds = p * (dp - delta_b[..., None] + dlse_b[..., None]) * scale
        dk_h = jnp.einsum(
            "bhqk,bhqd->bhkd", ds.astype(q.dtype), q_b,
            preferred_element_type=jnp.float32,
        )
        dq_contrib = jnp.einsum(
            "bhqk,bhkd->bhqd", ds.astype(q.dtype), kj_h,
            preferred_element_type=jnp.float32,
        )
        if span < sq:
            cur = jax.lax.dynamic_slice_in_dim(dq_acc, start, span, axis=2)
            dq_acc = jax.lax.dynamic_update_slice_in_dim(
                dq_acc, cur + dq_contrib, start, axis=2)
        else:
            dq_acc = dq_acc + dq_contrib
        if n_rep > 1:  # fold grouped q-heads back onto their kv head
            dk_h = dk_h.reshape(b, kv, n_rep, block_k, dh).sum(axis=2)
            dv_h = dv_h.reshape(b, kv, n_rep, block_k, dh).sum(axis=2)
        return dq_acc, (dk_h, dv_h)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0, (jnp.arange(n_blocks), k_blocks, v_blocks)
    )
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, kv, sk, dh)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, kv, sk, dh)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_dkdv_kernel(
    q_ref,    # [1, 1, block_q, D]   (q head = kv*n_rep + r)
    k_ref,    # [1, 1, block_k, D]
    v_ref,    # [1, 1, block_k, D]
    do_ref,   # [1, 1, block_q, D]
    lse_ref,  # [1, 1, block_q/sub, 1, sub]
    dd_ref,   # [1, 1, block_q/sub, 1, sub]  delta - dlse
    *rest,    # [qseg [1,block_q/sub,1,sub], kseg [1,1,block_k] when
              # use_segments,] dk [1,1,block_k,D], dv [1,1,block_k,D],
              # scratch x2
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    sub: int,  # query rows a turn of the inner loop takes
    window: int,
    use_segments: bool,
):
    """Score tiles in the transposed orientation (``s^T = k q^T``,
    ``dp^T = v dO^T``: keys down the sublanes, query rows along the
    lanes), so that ``dv += p^T dO`` and ``dk += ds^T q`` contract over
    a tile's last dimension and no tile is transposed; ``lse`` and
    ``delta`` are then lane-dense rows."""
    if use_segments:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        qseg_ref = kseg_ref = None
    ki = pl.program_id(2)
    r, qc = pl.program_id(3), pl.program_id(4)
    n_rep, n_qc = pl.num_programs(3), pl.num_programs(4)

    @pl.when(jnp.logical_and(r == 0, qc == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def turn(j, masked: bool):
        row = pl.multiple_of(j * sub, sub)
        q = q_ref[0, 0, pl.ds(row, sub), :]
        do = do_ref[0, 0, pl.ds(row, sub), :]
        s = _dot(k_ref[0, 0], q, (1, 1)) * scale  # [block_k, sub]
        p = jnp.exp(s - lse_ref[0, 0, j])  # lse row: [1, sub]
        if masked:
            mask = _sub_mask(
                qc * block_q + row - ki * block_k, s.shape, 1, causal,
                window,
                qseg_ref[0, j] if use_segments else None,
                kseg_ref[0, 0][:, None] if use_segments else None)
            p = jnp.where(mask, p, 0.0)
        dp = _dot(v_ref[0, 0], do, (1, 1))  # v @ do^T → [block_k, sub]
        # d lse/d s_j = p_j, so an lse cotangent enters ds additively:
        # dd is delta less that cotangent.
        ds = p * (dp - dd_ref[0, 0, j]) * scale
        dv_acc[:] += _dot(p.astype(do.dtype), do, (1, 0))
        dk_acc[:] += _dot(ds.astype(q.dtype), q, (1, 0))

    _walk(_turn_ranges(ki * block_k, block_k, qc * block_q, sub,
                       block_q // sub, over_cols=False, causal=causal,
                       window=window), turn, use_segments)

    @pl.when(jnp.logical_and(r == n_rep - 1, qc == n_qc - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref,    # [1, 1, block_q, D]
    k_ref,    # [1, 1, block_k, D]
    v_ref,    # [1, 1, block_k, D]
    do_ref,   # [1, 1, block_q, D]
    lse_ref,  # [1, 1, 1, block_q]
    dd_ref,   # [1, 1, 1, block_q]  delta - dlse
    *rest,    # [qseg, kseg as the forward's when use_segments,] dq,
              # dq_acc / lse / dd scratch
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    sub: int,
    window: int,
    use_segments: bool,
):
    if use_segments:
        qseg_ref, kseg_ref, dq_ref, dq_acc, lse_rows, dd_rows = rest
    else:
        dq_ref, dq_acc, lse_rows, dd_rows = rest
        qseg_ref = kseg_ref = None
    qi, kc = pl.program_id(2), pl.program_id(3)
    n_kc = pl.num_programs(3)

    @pl.when(kc == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        # A row's lse and dd come in along the lanes (dense in HBM) and
        # are spread over a row's lanes once a row block, not once a
        # turn.
        lse_rows[:] = _rows_to_lanes(lse_ref[0, 0], block_q)
        dd_rows[:] = _rows_to_lanes(dd_ref[0, 0], block_q)

    def turn(j, masked: bool):
        col = pl.multiple_of(j * sub, sub)
        k = k_ref[0, 0, pl.ds(col, sub), :]
        v = v_ref[0, 0, pl.ds(col, sub), :]
        s = _dot(q_ref[0, 0], k, (1, 1)) * scale  # [block_q, sub]
        p = jnp.exp(s - _lanes(lse_rows[:], sub))
        if masked:
            mask = _sub_mask(
                qi * block_q - kc * block_k - col, s.shape, 0, causal,
                window,
                qseg_ref[0, 0][:, None] if use_segments else None,
                kseg_ref[0, j] if use_segments else None)
            p = jnp.where(mask, p, 0.0)
        dp = _dot(do_ref[0, 0], v, (1, 1))  # do @ v^T → [block_q, sub]
        ds = p * (dp - _lanes(dd_rows[:], sub)) * scale
        dq_acc[:] += _dot(ds.astype(k.dtype), k, (1, 0))

    _walk(_turn_ranges(qi * block_q, block_q, kc * block_k, sub,
                       block_k // sub, over_cols=True, causal=causal,
                       window=window), turn, use_segments)

    @pl.when(kc == n_kc - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(
    causal: bool,
    scale: float,
    dkdv_blocks: tuple[int, int, int],  # (block_q, block_k, sub) of each
    dq_blocks: tuple[int, int, int],
    window: int,
    interpret: bool,
    res,
    do: jax.Array,
    dlse: jax.Array,  # [B,H,Sq] cotangent of the lse output
):
    """FlashAttention-2 backward as two Pallas kernels (see module
    docstring). Gradients accumulate in f32 VMEM scratch; dk/dv for a
    GQA group accumulate onto the shared kv head inside the kernel, so
    per-q-head dk/dv tensors are never materialized in HBM."""
    q, k, v, segments, o, lse = res  # q,o: [B,H,Sq,D]; lse: [B,H,Sq]
    b, h, sq, d = q.shape
    kv = k.shape[1]
    sk = k.shape[2]
    n_rep = h // kv
    use_segments = segments is not None
    if use_segments:
        segments = segments.astype(jnp.int32)

    # d lse/d s_j = p_j: the lse cotangent enters ds beside delta.
    dd = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
          - dlse.astype(jnp.float32))  # [B,H,Sq]
    common = dict(causal=causal, scale=scale, window=window,
                  use_segments=use_segments)

    # dk/dv: grid (b, kv, k_block, group_rep, q_block); the two inner
    # dims revisit the same (b, kv, k_block) output block, so the
    # accumulators live in scratch and are written once at the end. The
    # rows of Q and dO are the copied operand, lse and dd sub-block
    # rows of it.
    block_q, block_k, sub = dkdv_blocks
    n_qc = sq // block_q
    rule = dict(over_cols=False, causal=causal, window=window)

    def q_block(ki, qc):
        return _resident_block(qc, ki * block_k, block_k, block_q, n_qc,
                               **rule)

    def qmap(b_, kvh, ki, r, qc):
        return (b_, kvh * n_rep + r, q_block(ki, qc), 0)

    def qsub(b_, kvh, ki, r, qc):
        return (b_, kvh * n_rep + r, q_block(ki, qc), 0, 0)

    def kmap(b_, kvh, ki, r, qc):
        return (b_, kvh, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, block_q=block_q, block_k=block_k,
                          sub=sub, **common),
        grid=(b, kv, sk // block_k, n_rep, n_qc),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_q // sub, 1, sub), qsub),
            pl.BlockSpec((1, 1, block_q // sub, 1, sub), qsub),
        ] + ([
            pl.BlockSpec((1, block_q // sub, 1, sub),
                         lambda b_, kvh, ki, r, qc: (b_, q_block(ki, qc),
                                                     0, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b_, kvh, ki, r, qc: (b_, 0, ki)),
        ] if use_segments else []),
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), kmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, kv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret, 3, 2),
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, do, _sub_rows(lse, sub), _sub_rows(dd, sub),
      *([_sub_rows(segments, sub), _segment_rows(segments)]
        if use_segments else []))

    # dq: gridded like the forward, K and V the copied operand.
    block_q, block_k, sub = dq_blocks
    n_kc = sk // block_k
    rule = dict(over_cols=True, causal=causal, window=window)

    def qmap(b_, h_, qi, kc):
        return (b_, h_, qi, 0)

    def rowmap(b_, h_, qi, kc):
        return (b_, h_, 0, qi)

    def k_block(qi, kc):
        return _resident_block(kc, qi * block_q, block_q, block_k, n_kc,
                               **rule)

    def kmap(b_, h_, qi, kc):
        return (b_, h_ // n_rep, k_block(qi, kc), 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          sub=sub, **common),
        grid=(b, h, sq // block_q, n_kc),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
            pl.BlockSpec((1, 1, block_k, d), kmap),
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, 1, block_q), rowmap),
            pl.BlockSpec((1, 1, 1, block_q), rowmap),
        ] + ([
            pl.BlockSpec((1, 1, block_q), lambda b_, h_, qi, kc: (b_, 0, qi)),
            pl.BlockSpec((1, block_k // sub, 1, sub),
                         lambda b_, h_, qi, kc: (b_, k_block(qi, kc), 0, 0)),
        ] if use_segments else []),
        out_specs=[pl.BlockSpec((1, 1, block_q, d), qmap)],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32)],
        compiler_params=_compiler_params(interpret, 3, 1),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse[:, :, None], dd[:, :, None],
      *([_segment_rows(segments), _sub_rows(segments, sub)]
        if use_segments else []))[0]
    return dq, dk, dv


def _flash(q, k, v, segments, causal, scale, tiles, interpret, window,
           bwd_impl):
    """Returns (o, lse). Differentiable in both outputs — an lse
    cotangent (ring attention's online merge uses lse) enters the bwd
    as an additive term in ds. Callers that only need o discard lse;
    its cotangent is then structurally zero. ``tiles``: the (block_q,
    block_k, sub) of the forward, dk/dv and dq kernels, in that order
    (``auto_blocks``)."""
    return _flash_fwd_pallas(q, k, v, segments, causal, scale, tiles[0],
                             interpret, window)


def _flash_fwd_rule(q, k, v, segments, causal, scale, tiles, interpret,
                    window, bwd_impl):
    o, lse = _flash_fwd_pallas(q, k, v, segments, causal, scale, tiles[0],
                               interpret, window)
    return (o, lse), (q, k, v, segments, o, lse)


def _flash_bwd_rule(causal, scale, tiles, interpret, window, bwd_impl, res,
                    cts):
    do, dlse = cts
    if bwd_impl == "pallas":
        return _flash_bwd_pallas(causal, scale, tiles[1], tiles[2], window,
                                 interpret, res, do, dlse) + (None,)
    return _flash_bwd_xla(causal, scale, tiles[0][2], window, res, do,
                          dlse) + (None,)


_STATIC = (4, 5, 6, 7, 8, 9)
_flash = jax.custom_vjp(_flash, nondiff_argnums=_STATIC)
_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)
# Jitted, so that a program's call sites of one shape and window (a
# static layer plan's prefill has one a layer) share one tracing and
# lowering of the kernels: PERF.md §6, PRs 37 and 51.
_call = jax.jit(_flash, static_argnums=_STATIC)


def _kernel_takes(block_q: int, block_k: int, head_dim: int) -> bool:
    return (block_q >= 128 and block_k >= 128
            and (head_dim % 128 == 0 or head_dim == 64))


def implementation_for(seq_q: int, seq_k: int, head_dim: int,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None) -> str:
    """Which implementation ``flash_attention`` runs for a shape:
    ``"pallas"`` when the sequence tiles into >=128 blocks and head_dim
    is lane-compatible, else ``"einsum"`` (the reference)."""
    bq, bk, _ = auto_blocks(seq_q, seq_k, head_dim, block_q=block_q,
                            block_k=block_k)["fwd"]
    return "pallas" if _kernel_takes(bq, bk, head_dim) else "einsum"


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KV, D]
    v: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_q: Optional[int | str] = None,  # None / "auto": auto_blocks
    block_k: Optional[int | str] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,  # [B, S] packed-sequence ids
    bwd_impl: Optional[str] = None,  # "pallas" | "xla"; None = auto
) -> jax.Array:
    """Flash attention over [B, S, H, D] layouts with GQA support.

    ``window``: sliding-window (Mistral-style) causal attention — each
    query attends to its last ``window`` positions; K/V blocks entirely
    outside the band are skipped, so compute is O(S·window).

    ``segment_ids``: packed sequences — attention is additionally
    restricted to equal segment ids (requires Sq == Sk).

    ``block_q`` / ``block_k``: the query rows a grid step of the
    forward keeps and the keys it copies; left out (or ``"auto"``) they
    follow the shapes (``auto_blocks``).

    Gives way to the einsum reference (``ops.attention.xla_attention``),
    with a warning per shape, when shapes don't tile (seq not divisible
    into >=128 blocks, or head_dim not lane-aligned) — see
    ``implementation_for``.
    """
    return flash_attention_with_lse(
        q, k, v, causal=causal, softmax_scale=softmax_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window, segment_ids=segment_ids, bwd_impl=bwd_impl)[0]


def flash_attention_with_lse(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KV, D]
    v: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_q: Optional[int | str] = None,  # None / "auto": auto_blocks
    block_k: Optional[int | str] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
    bwd_impl: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    """``flash_attention`` that also returns the row logsumexp
    ``[B, H, Sq]`` (f32) — the residual ring attention needs to merge
    per-block partial attentions exactly. Differentiable in both
    outputs (the lse cotangent flows through the bwd kernels). Same
    give-way rule: non-tiling shapes use the einsum reference, which
    also returns lse."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kv}")
    if window is not None and (window < 1 or not causal):
        raise ValueError("window must be >= 1 and requires causal attention")
    if segment_ids is not None and sq != sk:
        raise ValueError(
            f"segment_ids requires Sq == Sk, got {sq} vs {sk}")
    if bwd_impl not in (None, "pallas", "xla"):
        # Validate before the shape-based give-way so a typo can't ride
        # silently on non-tiling shapes.
        raise ValueError(f"unknown bwd_impl `{bwd_impl}`")
    blocks = auto_blocks(
        sq, sk, d, window=window,
        block_q=None if block_q == "auto" else block_q,
        block_k=None if block_k == "auto" else block_k)
    bq, bk, _ = blocks["fwd"]
    if not _kernel_takes(bq, bk, d):
        from polyaxon_tpu.ops.attention import xla_attention_with_lse

        # One warning per shape (the default warnings filter dedups on
        # the message): on a chip this is the difference between the
        # kernel and an O(S²) score tensor.
        warnings.warn(
            f"flash_attention: shape Sq={sq} Sk={sk} head_dim={d} does "
            f"not tile (blocks {bq}x{bk}, need >=128 and head_dim 64 or "
            "a multiple of 128) — running the einsum reference instead "
            "of the Pallas kernel", KernelFallbackWarning, stacklevel=3)
        return xla_attention_with_lse(
            q, k, v, causal=causal, softmax_scale=softmax_scale,
            window=window, segment_ids=segment_ids)
    interpret = resolve_interpret(interpret)
    if bwd_impl is None:
        # Pallas bwd wherever the kernel compiles; the chunked-XLA bwd
        # is faster than an interpreted Pallas kernel on the CPU mesh.
        bwd_impl = "xla" if interpret else "pallas"
    scale = float(softmax_scale) if softmax_scale is not None else d**-0.5
    tiles = tuple(blocks[kernel] for kernel in KERNELS)

    def kernel(qT, kT, vT, *segments):
        return _call(qT, kT, vT, segments[0] if segments else None,
                     causal, scale, tiles, interpret, window or 0, bwd_impl)

    # Kernel layout: heads-major [B, H, S, D] so (seq, head_dim) is the
    # trailing (sublane, lane) tile. Under a multi-device mesh the call
    # runs per shard of (batch, kv heads): each kv head's whole GQA
    # group shards with it, so the kernel's h // n_rep map stays local.
    batch_axes, head_axis = compat.kernel_axes(b, kv)
    bhsd = P(batch_axes, head_axis, None, None)
    seg_args = () if segment_ids is None else (segment_ids,)
    o, lse = compat.shard_kernel(
        kernel,
        in_specs=(bhsd,) * 3 + (P(batch_axes, None),) * len(seg_args),
        out_specs=(bhsd, P(batch_axes, head_axis, None)),
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), *seg_args)
    return o.transpose(0, 2, 1, 3), lse
