"""Blocked flash attention as a Pallas TPU kernel.

TPU-first design (pallas_guide.md): the forward pass tiles Q into
``block_q`` × head_dim VMEM blocks and streams K/V blocks through the
innermost (sequential) grid dimension, keeping the online-softmax
running max/denominator and the output accumulator in f32 VMEM scratch
— O(S) memory instead of the O(S²) logits tensor, with every matmul on
the MXU (``preferred_element_type=f32``). Causal blocks strictly above
the diagonal are skipped with ``pl.when`` (no wasted MXU cycles), and
GQA is handled in the K/V index maps (kv head = q head // n_rep) so
grouped heads are never materialized ``n_rep`` times in HBM.

The backward pass under ``jax.custom_vjp`` has two implementations:

- **Pallas** (default on real TPU): the FlashAttention-2 split — a
  dk/dv kernel gridded over K/V blocks that streams Q blocks (GQA
  groups accumulate onto their shared kv head inside VMEM scratch, so
  dk/dv never materialize per-q-head), and a dq kernel gridded like
  the forward. Both recompute P from the saved logsumexp residual,
  keep every matmul on the MXU in f32 accumulation, and skip causal /
  out-of-window blocks with ``pl.when``.
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
import json
import os
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


_pick_block = pick_block  # internal alias

# Per-core VMEM is ~128 MiB on v5e/v4; the budget leaves headroom for
# Mosaic's double-buffered input pipelining and the bwd kernels' extra
# accumulators (dk/dv scratch ≈ the fwd footprint again).
VMEM_BUDGET = 48 * 2**20


def _tile_bytes(bq: int, bk: int, d: int) -> int:
    """Estimated fwd-kernel VMEM residency for one grid cell: bf16 Q
    tile + double-buffered bf16 K/V streams + f32 scores + f32 output
    accumulator + lane-broadcast m/l scratch."""
    return (bq * d * 2          # q tile (bf16)
            + 2 * 2 * bk * d * 2  # k + v, double-buffered (bf16)
            + bq * bk * 4       # scores (f32)
            + bq * d * 4        # o accumulator (f32)
            + 2 * bq * LANES * 4)  # m / l scratch (f32)


# Committed per-device-kind tile picks from the AOT topology probe
# (perf/aot.py flash_pick): each entry is a tile set Mosaic actually
# compiled for that chip, i.e. VMEM-fit EVIDENCE rather than the
# heuristic's estimate. Keyed by `jax.Device.device_kind`.
FLASH_TILES_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "perf", "flash_tiles.json")


@functools.lru_cache(maxsize=1)
def _committed_tile_picks() -> dict:
    # Committed data: a missing or corrupt table is a broken checkout
    # and raises, rather than quietly tuning from the heuristic alone.
    with open(FLASH_TILES_PATH) as fh:
        table = json.load(fh)
    return {k: v for k, v in table.items() if not k.startswith("_")}


def auto_blocks(seq_q: int, seq_k: int, head_dim: int,
                *, vmem_budget: int = VMEM_BUDGET,
                device_kind: Optional[str] = None) -> tuple[int, int]:
    """Trace-time (block_q, block_k) choice keyed on (seq, head_dim,
    VMEM budget) — VERDICT r4 item 3's staged MFU lever. Larger tiles
    amortize the online-softmax rescale and grid overhead (fewer
    passes over the K/V stream per Q tile) but must leave VMEM room
    for pipelining; the historical fixed 512x512 default is kept as
    the FLOOR of preference order so auto never picks worse than the
    measured r3/r4 configuration, and 1024-tiles are tried first where
    the budget allows (small head_dim). Shapes that don't tile fall
    back through ``pick_block`` exactly as explicit sizes do.

    ``device_kind`` (ISSUE 12): a chip with a committed pick in
    ``perf/flash_tiles.json`` uses that compile-validated tile set
    first — still subject to the same seq-tiling and VMEM-budget
    screens, so a probed pick can never select tiles the budget math
    or the shape would reject."""
    pick = _committed_tile_picks().get(device_kind or "")
    if pick:
        bq, bk = int(pick["block_q"]), int(pick["block_k"])
        if _tile_bytes(bq, bk, head_dim) <= vmem_budget:
            got_q = _pick_block(seq_q, bq)
            got_k = _pick_block(seq_k, bk)
            if got_q == min(bq, seq_q) and got_k == min(bk, seq_k):
                return got_q, got_k
    for bq in (1024, 512, 256, 128):
        for bk in (1024, 512, 256, 128):
            if bk > bq * 2:
                continue  # tall score tiles win nothing; skip extremes
            if _tile_bytes(bq, bk, head_dim) <= vmem_budget:
                got_q = _pick_block(seq_q, bq)
                got_k = _pick_block(seq_k, bk)
                if got_q == min(bq, seq_q) and got_k == min(bk, seq_k):
                    return got_q, got_k
    return _pick_block(seq_q, 512), _pick_block(seq_k, 512)


def _block_visible(qi, ki, block_q: int, block_k: int, causal: bool,
                   window: int):
    """Whether block (qi, ki) contributes at all — the grid-skip
    predicate shared by the fwd and both bwd kernels. Causal blocks
    strictly above the diagonal contribute nothing; with a sliding
    window, blocks entirely below the band neither."""
    if not causal:
        return True
    visible = qi * block_q + block_q > ki * block_k
    if window:
        in_band = ki * block_k + block_k > qi * block_q - (window - 1)
        visible = jnp.logical_and(visible, in_band)
    return visible


def _block_mask(qi, ki, block_q: int, block_k: int, causal: bool,
                window: int, qseg_ref, kseg_ref):
    """The in-block [block_q, block_k] validity mask (or None when the
    whole block is valid) — single source of truth for the causal
    triangle, window band, and packed-segment masking used identically
    by all three kernels."""
    mask = None
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = rows >= cols
        if window:
            mask &= rows - cols < window
    if qseg_ref is not None:
        seg = qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :]
        mask = seg if mask is None else mask & seg
    return mask


def _segment_rows(segments: jax.Array) -> jax.Array:
    """[B, S] ids as [B, 1, S], so a (1, 1, block) tile's two trailing
    dims are whole: the Mosaic lowering refuses a (1, block) tile of a
    [B, S] array for any B > 1 (its second-to-last dim is neither the
    array's nor a multiple of 8)."""
    return segments.astype(jnp.int32)[:, None, :]


def _fwd_kernel(
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    *rest,  # [qseg [1,1,block_q], kseg [1,1,block_k] when use_segments,]
            # o [1,1,block_q,D], lse [1,1,block_q,1],
            # acc/m/l VMEM scratch
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    window: int,  # 0 = unbounded
    use_segments: bool,
):
    if use_segments:
        qseg_ref, kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        qseg_ref = kseg_ref = None
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_block_visible(qi, ki, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s *= scale  # [block_q, block_k]

        mask = _block_mask(qi, ki, block_q, block_k, causal, window,
                           qseg_ref, kseg_ref)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:, :1] + jnp.log(l_safe)).astype(lse_ref.dtype)


def _flash_fwd_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, KV, Sk, D]
    v: jax.Array,
    segments,  # [B, Sq] int32 or None (packed-sequence ids)
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    b, h, sq, d = q.shape
    kv = k.shape[1]
    sk = k.shape[2]
    n_rep = h // kv
    grid = (b, h, sq // block_q, sk // block_k)

    use_segments = segments is not None

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, window=window, use_segments=use_segments,
    )
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        )
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h_, qi, ki, n_rep=n_rep: (b_, h_ // n_rep, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h_, qi, ki, n_rep=n_rep: (b_, h_ // n_rep, ki, 0),
            ),
        ] + ([
            pl.BlockSpec((1, 1, block_q), lambda b_, h_, qi, ki: (b_, 0, qi)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, qi, ki: (b_, 0, ki)),
        ] if use_segments else []),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=compiler_params,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, *([_segment_rows(segments)] * 2 if use_segments else []))
    return o, lse[..., 0]


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
    q_ref,      # [1, 1, block_q, D]   (q head = kv*n_rep + r)
    k_ref,      # [1, 1, block_k, D]
    v_ref,      # [1, 1, block_k, D]
    do_ref,     # [1, 1, block_q, D]
    delta_ref,  # [1, 1, block_q, 1]
    lse_ref,    # [1, 1, block_q, 1]
    dlse_ref,   # [1, 1, block_q, 1]  cotangent of the lse output
    *rest,      # [qseg [1,1,block_q], kseg [1,1,block_k] when use_segments,]
                # dk [1,1,block_k,D], dv [1,1,block_k,D], scratch x2
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    window: int,
    use_segments: bool,
):
    if use_segments:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        qseg_ref = kseg_ref = None
    ki = pl.program_id(2)
    r, qi = pl.program_id(3), pl.program_id(4)
    n_rep, n_q = pl.num_programs(3), pl.num_programs(4)

    @pl.when(jnp.logical_and(r == 0, qi == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_visible(qi, ki, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]

        mask = _block_mask(qi, ki, block_q, block_k, causal, window,
                           qseg_ref, kseg_ref)
        p = jnp.exp(s - lse_ref[0, 0])  # lse block: [block_q, 1]
        if mask is not None:
            p = jnp.where(mask, p, 0.0)

        do = do_ref[0, 0]
        dv_acc[:] += jax.lax.dot_general(  # p^T @ do → [block_k, D]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(  # do @ v^T → [block_q, block_k]
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # d lse/d s_j = p_j, so an lse cotangent enters ds additively.
        ds = p * (dp - delta_ref[0, 0] + dlse_ref[0, 0]) * scale
        dk_acc[:] += jax.lax.dot_general(  # ds^T @ q → [block_k, D]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(r == n_rep - 1, qi == n_q - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref,      # [1, 1, block_q, D]
    k_ref,      # [1, 1, block_k, D]
    v_ref,      # [1, 1, block_k, D]
    do_ref,     # [1, 1, block_q, D]
    delta_ref,  # [1, 1, block_q, 1]
    lse_ref,    # [1, 1, block_q, 1]
    dlse_ref,   # [1, 1, block_q, 1]  cotangent of the lse output
    *rest,      # [qseg, kseg when use_segments,] dq, dq_acc scratch
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    window: int,
    use_segments: bool,
):
    if use_segments:
        qseg_ref, kseg_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        qseg_ref = kseg_ref = None
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_visible(qi, ki, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

        mask = _block_mask(qi, ki, block_q, block_k, causal, window,
                           qseg_ref, kseg_ref)
        p = jnp.exp(s - lse_ref[0, 0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)

        do = do_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0] + dlse_ref[0, 0]) * scale
        dq_acc[:] += jax.lax.dot_general(  # ds @ k → [block_q, D]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
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

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,Sq,1]
    lse4 = lse[..., None]  # [B,H,Sq,1]
    dlse4 = dlse.astype(jnp.float32)[..., None]  # [B,H,Sq,1]
    use_segments = segments is not None
    seg_args = ([_segment_rows(segments)] * 2) if use_segments else []

    n_q, n_k = sq // block_q, sk // block_k
    common = dict(causal=causal, scale=scale, block_q=block_q,
                  block_k=block_k, window=window, use_segments=use_segments)

    def cparams(n_parallel: int, n_arbitrary: int):
        if interpret:
            return None
        return pltpu.CompilerParams(
            dimension_semantics=("parallel",) * n_parallel
            + ("arbitrary",) * n_arbitrary)

    # dk/dv: grid (b, kv, k_block, group_rep, q_block); the two inner
    # dims revisit the same (b, kv, k_block) output block, so the
    # accumulators live in scratch and are written once at the end.
    dkdv_grid = (b, kv, n_k, n_rep, n_q)
    qmap = lambda b_, kvh, ki, r, qi, n=n_rep: (b_, kvh * n + r, qi, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, **common),
        grid=dkdv_grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kvh, ki, r, qi: (b_, kvh, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kvh, ki, r, qi: (b_, kvh, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_q, 1), qmap),
            pl.BlockSpec((1, 1, block_q, 1), qmap),
            pl.BlockSpec((1, 1, block_q, 1), qmap),
        ] + ([
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, kvh, ki, r, qi: (b_, 0, qi)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b_, kvh, ki, r, qi: (b_, 0, ki)),
        ] if use_segments else []),
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kvh, ki, r, qi: (b_, kvh, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kvh, ki, r, qi: (b_, kvh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, kv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=cparams(3, 2),
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, do, delta, lse4, dlse4, *seg_args)

    # dq: gridded like the forward, accumulating over k blocks.
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, n=n_rep: (b_, h_ // n, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, n=n_rep: (b_, h_ // n, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ] + ([
            pl.BlockSpec((1, 1, block_q), lambda b_, h_, qi, ki: (b_, 0, qi)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, qi, ki: (b_, 0, ki)),
        ] if use_segments else []),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=cparams(3, 1),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, delta, lse4, dlse4, *seg_args)[0]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, segments, causal, scale, block_q, block_k, interpret,
           window, bwd_impl):
    """Returns (o, lse). Differentiable in both outputs — an lse
    cotangent (ring attention's online merge uses lse) enters the bwd
    as an additive term in ds. Callers that only need o discard lse;
    its cotangent is then structurally zero."""
    return _flash_fwd_pallas(q, k, v, segments, causal, scale, block_q,
                             block_k, interpret, window)


def _flash_fwd_rule(q, k, v, segments, causal, scale, block_q, block_k,
                    interpret, window, bwd_impl):
    o, lse = _flash_fwd_pallas(q, k, v, segments, causal, scale, block_q,
                               block_k, interpret, window)
    return (o, lse), (q, k, v, segments, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, window,
                    bwd_impl, res, cts):
    do, dlse = cts
    if bwd_impl == "pallas":
        # Smaller default tiles than the fwd: the bwd keeps three
        # [block_q, block_k] f32 intermediates (s, p, ds) plus two
        # accumulators live in VMEM at once.
        bq = pick_block(res[0].shape[2], min(block_q, 256))
        bk = pick_block(res[1].shape[2], min(block_k, 256))
        return _flash_bwd_pallas(causal, scale, bq, bk, window, interpret,
                                 res, do, dlse) + (None,)
    return _flash_bwd_xla(causal, scale, block_k, window, res, do,
                          dlse) + (None,)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def implementation_for(seq_q: int, seq_k: int, head_dim: int,
                       block_q: int = 512, block_k: int = 512) -> str:
    """Which implementation ``flash_attention`` runs for a shape:
    ``"pallas"`` when the sequence tiles into >=128 blocks and head_dim
    is lane-compatible, else ``"einsum"`` (the reference)."""
    bq = _pick_block(seq_q, block_q)
    bk = _pick_block(seq_k, block_k)
    if bq < 128 or bk < 128 or (head_dim % 128 and head_dim != 64):
        return "einsum"
    return "pallas"


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KV, D]
    v: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_q: int | str = 512,  # tile size, or "auto" (auto_blocks)
    block_k: int | str = 512,
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
    block_q: int | str = 512,  # tile size, or "auto" (auto_blocks)
    block_k: int | str = 512,
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
    if block_q == "auto" or block_k == "auto":
        # Trace-time auto-pick keyed on (seq, head_dim, VMEM budget).
        # On a TPU backend the committed per-chip pick table is
        # consulted first (compile-validated tiles beat the estimate).
        kind = (jax.devices()[0].device_kind
                if jax.default_backend() == "tpu" else None)
        abq, abk = auto_blocks(sq, sk, d, device_kind=kind)
        block_q = abq if block_q == "auto" else block_q
        block_k = abk if block_k == "auto" else block_k
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    if implementation_for(sq, sk, d, block_q, block_k) == "einsum":
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
    scale = softmax_scale if softmax_scale is not None else d**-0.5

    def kernel(qT, kT, vT, *segments):
        return _flash(qT, kT, vT, segments[0] if segments else None,
                      causal, scale, bq, bk, interpret, window or 0,
                      bwd_impl)

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
