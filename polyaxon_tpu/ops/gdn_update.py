"""Pallas kernel (TPU) for the gated delta rule's one-position update
(``ops/gated_delta.py step``) over the rows' matrix states *where they
lie* in the decode cache's leaf.

A decode step's delta layer reads each live row's state ``S`` (``Hv``
heads of ``dk x dv`` float32: 2.10 MB a row at 32 x 128 x 128) to form
what it writes::

    δ = β (v − α Sᵀk)      o = α Sᵀq + δ (k·q)      S ← α S + k ⊗ δ

``δ`` needs a whole reduction over ``S`` before the first element of the
new ``S`` exists, so the compiler makes it two operations: one that
reads the state for the two products, one that reads it again and writes
it (0.365 + 0.815 ms a layer at 128 rows where one read and one write
are 0.655 ms at a v5e's 819 GB/s). A head's state is 64 KB and the
dependence stays inside it, so this kernel brings a block of (rows x
heads) into fast memory **once**, takes both products and the update
from that copy, and writes the block back **once**: 0.818 ms a layer on
that chip, which is what a pass that only copies a layer's rows in
place takes there (0.818-0.823 ms, the compiler's or a kernel's: 656
GB/s with reads and writes mixed; `PERF.md` §5-6, PR 40):

- the leaf arrives **whole** (``[L, rows, Hv, dk, dv]``, aliased to the
  result) with the layer as a prefetched scalar: a block's index map
  names ``(layer, row block, head block)`` and the pipeline fetches and
  writes it where it lies. Blocks of other layers and of rows past the
  ``B`` that step are never visited and keep what they hold. (Handed a
  layer's slice, the program would copy it out and back: 268 MB each
  way.) One lowering serves every layer of a program;
- a row that has not ``started`` (position 0: the leaf holds an earlier
  sequence's state, or garbage) reads as zeros;
- the two products reduce over ``dk``, the state's second-to-last axis:
  whole registers added, then one fold of eight sublanes a head. ``k``
  and ``q`` are wanted one value a sublane, the same in every lane: the
  caller's side of `gdn_update` lays each block's keys and queries out
  as one ``[dk, 2·rows·heads]`` tile (a column a head), so the kernel
  takes a column and spreads it over the lanes, and never transposes.
  ``α``, ``β`` and ``k·q`` are a number a head and ride in scalar
  memory;
- float32 throughout, on the vector unit: the same products and sums as
  `step`, in another order (a sum of 128 terms folded by eights), so the
  two agree to rounding, not to the bit;
- the block from the shapes (`_block`): the most (rows x heads) whose
  blocks in and out, two buffers each, fit under `VMEM_LIMIT` with a
  quarter to spare. At the benchmark's 32 heads of 128 x 128 that is two
  rows, 4 MB a block and 64 grid steps a layer at 128 rows; the
  compiler's schedule of a step is 5,389 bundles (the lane permutes of
  the columns fill it: 32 a head over three units), a third of the time
  its copies take. Blocks of 2, 4, 8 and 16 MB took the same time to
  three digits, so nothing is tuned here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from polyaxon_tpu.ops.flash import resolve_interpret

# What the kernel may keep in fast memory (a v5e core has 128 MiB; the
# compiler's default scope is 16).
VMEM_LIMIT = 32 * 2**20


def _largest_divisor(n: int, most: int) -> int:
    return max(d for d in range(1, n + 1) if n % d == 0 and d <= max(most, 1))


def _block(rows: int, heads: int, dk: int, dv: int) -> tuple:
    """(rows, heads) of a block: divisors of ``rows`` and ``heads``,
    whole rows of heads first, the most whose state blocks in and out
    (two buffers each) fit in `VMEM_LIMIT` with a quarter to spare. One
    head of one row where nothing larger fits."""
    fit = int(0.75 * VMEM_LIMIT) // (4 * dk * dv * 4)    # heads that fit
    if fit >= heads:
        return _largest_divisor(rows, fit // heads), heads
    return 1, _largest_divisor(heads, fit)


def _kernel(layer_ref, started_ref, alpha_ref, beta_ref, kdotq_ref,
            kq_ref, v_ref, s_ref, o_ref, new_ref, *, rb, hb, heads):
    del layer_ref  # the state block's index map reads it
    r0 = pl.program_id(0) * rb
    h0 = pl.program_id(1) * hb
    n = rb * hb
    for r in range(rb):
        live = started_ref[r0 + r] > 0
        for h in range(hb):
            j = r * hb + h
            at = (r0 + r) * heads + h0 + h
            alpha, beta = alpha_ref[at], beta_ref[at]
            s = jnp.where(live, s_ref[r, h], 0.0)            # [dk, dv]
            k = kq_ref[:, j:j + 1]                           # [dk, 1]
            q = kq_ref[:, n + j:n + j + 1]
            s_k = jnp.sum(s * k, axis=0, keepdims=True)      # [1, dv]
            s_q = jnp.sum(s * q, axis=0, keepdims=True)
            delta = beta * (v_ref[r, h:h + 1] - alpha * s_k)
            o_ref[r, h:h + 1] = alpha * s_q + delta * kdotq_ref[at]
            new_ref[r, h] = alpha * s + k * delta


def _forward(stack, layer, q, k, v, g, beta, started, interpret):
    _, rows, heads, dk, dv = stack.shape
    batch = q.shape[0]
    if stack.dtype != jnp.float32 or batch > rows or q.shape != (
            batch, heads, dk) or v.shape != (batch, heads, dv):
        raise ValueError(
            f"gdn_update: a float32 leaf [L, rows, H, dk, dv] and q, k "
            f"[B <= rows, H, dk], v [B, H, dv]; got {stack.dtype}"
            f"{stack.shape}, {q.shape}, {v.shape}")
    rb, hb = _block(batch, heads, dk, dv)
    n_r, n_h = batch // rb, heads // hb

    def columns(x):
        # [B, H, dk] -> [row block, head block, dk, rb·hb]: a column a head.
        x = x.reshape(n_r, rb, n_h, hb, dk)
        return x.transpose(0, 2, 4, 1, 3).reshape(n_r, n_h, dk, rb * hb)

    kq = jnp.concatenate([columns(k), columns(q)], axis=-1)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               started.astype(jnp.int32),
               jnp.exp(g).reshape(-1), beta.reshape(-1),
               jnp.sum(k * q, axis=-1).reshape(-1))
    n_prefetch = len(scalars)

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT)
    by_head = pl.BlockSpec(
        (rb, None, hb, dv), lambda r, h, *_: (r, h, 0, 0))

    def state_block(r, h, layer_ref, *_):
        return (layer_ref[0], r, h, 0, 0)

    state_spec = pl.BlockSpec((None, rb, hb, dk, dv), state_block)
    o, new = pl.pallas_call(
        functools.partial(_kernel, rb=rb, hb=hb, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(n_r, n_h),
            in_specs=[
                pl.BlockSpec((None, None, dk, 2 * rb * hb),
                             lambda r, h, *_: (r, h, 0, 0)),
                by_head,
                state_spec,
            ],
            out_specs=[by_head, state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((batch, n_h, hb, dv), jnp.float32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        input_output_aliases={n_prefetch + 2: 1},
        compiler_params=compiler_params,
        cost_estimate=pl.CostEstimate(
            flops=7 * batch * heads * dk * dv, transcendentals=0,
            bytes_accessed=2 * batch * heads * dk * dv * 4),
        interpret=interpret,
        name="gdn_update",
    )(*scalars, kq, v.reshape(batch, n_h, hb, dv), stack)
    return o.reshape(batch, heads, dv), new


# Jitted: a decode program's delta layers call it with one set of shapes
# (the layer is an operand), so they are traced and lowered once
# (`ops/grouped_matmul.py _call`).
_call = jax.jit(_forward, static_argnums=(8,))


def gdn_update(stack: jax.Array, layer, q: jax.Array, k: jax.Array,
               v: jax.Array, g: jax.Array, beta: jax.Array,
               started: jax.Array, *, interpret: bool | None = None):
    """``ops/gated_delta.py step`` for the first B rows of layer
    ``layer`` (may be traced) of ``stack`` [L, rows ≥ B, H, dk, dv]
    float32, in place: ``q``/``k`` [B, H, dk], ``v`` [B, H, dv], ``g``/
    ``beta`` [B, H] float32, ``started`` [B] bool (a row that has not
    reads its state as zeros) → (o [B, H, dv], the leaf with those rows'
    states written). Donate the leaf, or the program copies it.
    ``interpret``: None = interpreted on the CPU backend
    (``ops/flash.py resolve_interpret``)."""
    return _call(stack, layer, q, k, v, g, beta, started,
                 resolve_interpret(interpret))
