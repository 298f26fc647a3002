"""Pallas grouped matmul (TPU): rows sorted by group, each group's rows
times that group's own matrix, ``jax.lax.ragged_dot``'s contract for
the sizes a prefill's expert dispatch has.

``models/moe.py sorted_dispatch`` sorts a prompt's (token, expert)
pairs by expert and multiplies each held expert's few rows (16 an
expert for a 384-token prompt at 22 choices over 128 held of 512) by
that expert's block of the stacked weights. The arithmetic is nothing
and the weights are everything: 128 experts of 1,024 x 2,688 are 0.70
GB a call, 0.86 ms at a v5e's 819 GB/s. The compiler's own kernel for
``ragged_dot`` took 2.0-5.2 ms for them, in blocks 128 lanes wide
(`PERF.md` §5-6, PR 37). This one reads each expert that holds a row
once, and nothing else (0.74-1.12 ms on the same prompts):

- a **row tile of 128** (`ROW_TILE`): the row count is a multiple of
  it (the caller pads); a tile's rows by one expert's block took 5.8 µs
  on a v5e where the block takes 6.7 µs to arrive, so a wider tile
  would make the matrix unit the bound;
- a **visit** is one (row tile, group) pair that share a row. Groups
  are laid over the tiles in order, so visits number at most ``tiles +
  groups − 1``, counted from the sizes on the device and given to the
  grid as its (dynamic) length: tiles past the last group's row are
  never visited, a group without a row is never read;
- grid ``(output tile, visit)``. A visit multiplies its row tile by
  its group's ``[K, tn]`` block (bfloat16 operands, float32 sums on
  the matrix unit, cast to the rows' dtype once) and keeps the rows
  that are the group's; the first visit of a row tile clears it, so a
  row of a visited tile that no group holds reads zero. A row of a tile
  never visited holds whatever the buffer held: the caller masks rows
  past its groups, and never scales them;
- the stack arrives **whole** (``[G, K, N]``, every layer's experts:
  handed a layer's slice the program copies it first) with the
  layer's first group as a prefetched scalar: the block's index map
  names ``first + group`` and the pipeline fetches the block where it
  lies. Two visits of one group in a row (a group that straddles two
  row tiles) name the same block, which is not fetched again; nor is
  the row tile when only the group changes;
- ``tn`` from the shapes (`_tile_n`): the widest split of N into lane
  tiles whose double-buffered blocks fit under `VMEM_LIMIT`, which is
  the whole of N for both expert shapes the benchmark serves (1,024 x
  2,688 and 2,688 x 1,024: 5.5 MB a block; 2,048 x 512: 2 MB), one
  contiguous copy an expert. K is never split: the widest here is
  2,688.

No gradient of its own: differentiated, the call is ``ragged_dot``'s
(`_bwd`), so a family's ``apply`` trains on a TPU as it does off it.
The grid and metadata are in the manner of
``jax.experimental.pallas.ops.tpu.megablox``; written for this repo's
conventions (``ops/paged_attention.py``), not a port.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from polyaxon_tpu.ops.flash import resolve_interpret

ROW_TILE = 128
LANES = 128
# What the kernel may keep in fast memory (a v5e core has 128 MiB; the
# compiler's default scope is 16): room for two whole 5.5 MB blocks.
VMEM_LIMIT = 32 * 2**20


def _tile_n(k: int, n: int, itemsize: int) -> int:
    """The output tile's width: N whole, or the widest of its even
    splits into multiples of a lane tile, such that two blocks of the
    weights, two row tiles, two output tiles and the float32 product
    fit in `VMEM_LIMIT` with a quarter to spare."""
    def need(tn):
        return (2 * k * tn * itemsize + 2 * ROW_TILE * k * itemsize
                + 2 * ROW_TILE * tn * itemsize + 2 * ROW_TILE * tn * 4)

    splits = [n] + [n // parts for parts in range(2, n // LANES + 1)
                    if n % parts == 0 and (n // parts) % LANES == 0]
    for tn in splits:
        if need(tn) <= 0.75 * VMEM_LIMIT:
            return tn
    return splits[-1]


def visits(sizes: jax.Array, rows: int):
    """The kernel's prefetched scalars, from the groups' sizes [E] over
    `rows` rows: (group offsets [E + 1], the group and the row tile of
    each visit [rows / ROW_TILE + E − 1], the number of visits)."""
    n_groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // ROW_TILE
    tiles = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first_tile + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    at = jnp.arange(rows // ROW_TILE + n_groups - 1, dtype=jnp.int32)
    # The group of visit v: how many groups' visits end at or before v.
    group = jnp.minimum(
        jnp.sum(at[:, None] >= visit_ends[None, :], axis=1), n_groups - 1)
    tile = first_tile[group] + at - (visit_ends - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, group, jnp.clip(tile, 0, rows // ROW_TILE - 1),
            visit_ends[-1])


def _kernel(offsets_ref, group_ref, tile_ref, first_ref, x_ref, w_ref,
            o_ref):
    del first_ref  # the weight block's index map reads it
    v = pl.program_id(1)
    group, tile = group_ref[v], tile_ref[v]

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _clear():
        o_ref[...] = jnp.zeros_like(o_ref)

    row = tile * ROW_TILE + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
    product = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
    o_ref[...] = jnp.where(mine, product.astype(o_ref.dtype), o_ref[...])


def _forward(x, stack, sizes, first, interpret):
    rows, k = x.shape
    n = stack.shape[-1]
    if rows % ROW_TILE or stack.ndim != 3 or stack.shape[1] != k:
        raise ValueError(
            f"grouped_matmul: rows {x.shape} (a multiple of {ROW_TILE}) by "
            f"a stack [G, {k}, N], got {stack.shape}")
    if stack.dtype != x.dtype:
        raise ValueError(f"grouped_matmul: rows {x.dtype} by a stack of "
                         f"{stack.dtype}")
    tn = _tile_n(k, n, x.dtype.itemsize)
    offsets, group, tile, n_visits = visits(sizes, rows)
    first = jnp.asarray(first, jnp.int32).reshape(1)

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT)
    held = sizes.shape[0]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((ROW_TILE, k),
                             lambda j, v, off, grp, til, fst: (til[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, off, grp, til, fst:
                             (fst[0] + grp[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (ROW_TILE, tn), lambda j, v, off, grp, til, fst: (til[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=compiler_params,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(held * k * n + rows * (k + n))
            * x.dtype.itemsize),
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, group, tile, first, x, stack)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped(x, stack, sizes, first, interpret):
    return _forward(x, stack, sizes, first, interpret)


def _fwd(x, stack, sizes, first, interpret):
    return _forward(x, stack, sizes, first, interpret), (x, stack, sizes,
                                                         first)


def _bwd(interpret, saved, g):
    """``ragged_dot``'s own gradient, over the whole stack's groups."""
    del interpret
    x, stack, sizes, first = saved
    # A row past the groups may hold anything on its way here.
    row = jax.lax.broadcasted_iota(jnp.int32, (g.shape[0], 1), 0)
    g = jnp.where(row < jnp.sum(sizes), g, jnp.zeros_like(g))
    every = jax.lax.dynamic_update_slice(
        jnp.zeros((stack.shape[0],), jnp.int32), sizes,
        (jnp.asarray(first, jnp.int32),))
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, every), x, stack)
    dx, dstack = vjp(g)
    return dx, dstack, None, None


_grouped.defvjp(_fwd, _bwd)
# Jitted: a program's calls of one shape (a layer walk's, unrolled) are
# traced and lowered once, not once a call site; at 40 ms a lowering and
# 21 sites in each of 13 prefill programs that was 8% of a server's
# set-up (`PERF.md` §6, PR 37).
_call = jax.jit(_grouped, static_argnums=(4,))


def grouped_matmul(x: jax.Array, stack: jax.Array, sizes: jax.Array,
                   first=0, *, interpret: bool | None = None) -> jax.Array:
    """``x`` [R, K], rows sorted by group, times ``stack`` [G, K, N]:
    the first ``sizes[0]`` rows by ``stack[first]``, the next
    ``sizes[1]`` by ``stack[first + 1]`` and so on → [R, N] in ``x``'s
    dtype. R is a multiple of `ROW_TILE`; ``first`` may be traced. Rows
    past the last group are zero where their tile holds a group's row
    and undefined elsewhere. ``interpret``: None = interpreted on the
    CPU backend (``ops/flash.py resolve_interpret``)."""
    return _call(x, stack, sizes.astype(jnp.int32), first,
                 resolve_interpret(interpret))
