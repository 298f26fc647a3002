"""GPipe-style SPMD pipeline parallelism over the ``pp`` mesh axis.

The §2b "PP" obligation (absent upstream — replica orchestration only).
TPU-first shape, per the scaling-book recipe: every stage is the SAME
compiled program (SPMD), layer params are stacked [n_stages, L/stage,
...] and sharded on the leading dim over ``pp``; activations flow
stage→stage via ``lax.ppermute`` over ICI while a ``lax.scan`` drives
the microbatch schedule:

    tick t: stage 0 injects microbatch t; every stage applies its local
    layers; outputs rotate (i → i+1); after n_stages-1 warmup ticks the
    last stage emits one finished microbatch per tick (pipeline bubble
    = (S-1)/(T+S-1), standard GPipe).

The whole schedule is differentiable (scan + ppermute + where), so the
backward pass runs the pipeline in reverse automatically. Collectives
stay inside shard_map over {pp} only — dp/fsdp/tp axes remain in GSPMD
auto mode and compose (partial manual sharding).

``double_buffer=True`` (ISSUE 12) decouples the stage→stage hop from
the compute that feeds it: the carry holds (arrived, to_send), each
tick permutes LAST tick's output while stage_fn runs on what arrived
two ticks ago, so within a tick the ppermute and the stage compute
have no data dependency and the scheduler can fly the transfer under
the matmuls. Stage s then sees microbatch m at tick m + 2s (vs m + s
single-buffered): one extra warmup tick per stage boundary buys the
overlap window. Per-microbatch outputs are IDENTICAL — the schedule
shifts ticks, not values — which the parity test asserts.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def spmd_pipeline(
    stage_fn: Callable,  # (local_params, x [mb, ...]) -> [mb, ...]
    local_params,  # this stage's slice of the stacked layer params
    microbatches: jax.Array,  # [n_micro, mb, ...] (stage-0 inputs, replicated)
    *,
    axis_name: str = "pp",
    double_buffer: bool = False,
) -> jax.Array:
    """Run the pipeline INSIDE shard_map; returns [n_micro, mb, ...]
    stage outputs, valid on the LAST stage (callers psum-select)."""
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    zero = jnp.zeros_like(microbatches[0])

    def inject_at(t):
        return jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)

    if double_buffer:
        # (arrived, to_send): permute LAST tick's output while compute
        # runs on the activation that arrived two ticks ago — no data
        # dependency between the two inside a tick, so the transfer can
        # hide under stage compute. Stage s sees microbatch m at tick
        # m + 2s; warmup bubble is 2(S-1) ticks.
        total_ticks = n_micro + 2 * (n_stages - 1)

        def tick(carry, t):
            arrived, to_send = carry
            incoming = jax.lax.ppermute(to_send, axis_name, perm)
            x_in = jnp.where(stage == 0, inject_at(t), arrived)
            out = stage_fn(local_params, x_in)
            return (incoming, out), out

        _, outs = jax.lax.scan(
            tick, (zero, zero), jnp.arange(total_ticks))
        first_valid = 2 * (n_stages - 1)
    else:
        total_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            x_in = jnp.where(stage == 0, inject_at(t), carry)
            out = stage_fn(local_params, x_in)
            nxt = jax.lax.ppermute(out, axis_name, perm)
            return nxt, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(total_ticks))
        first_valid = n_stages - 1
    # Last stage's outputs for ticks [first_valid, total) are
    # microbatches [0, n_micro); earlier ticks are warmup bubble.
    return jax.lax.slice_in_dim(outs, first_valid, total_ticks, axis=0)


def pipeline_forward(
    mesh,
    stage_fn: Callable,
    stacked_params,  # pytree with leading stage dim [n_stages, ...]
    x: jax.Array,  # [B, ...] stage-0 input activations
    *,
    n_microbatches: int,
    axis_name: str = "pp",
    double_buffer: bool = False,
) -> jax.Array:
    """jit-land wrapper: shards params over pp, microbatches x, runs the
    schedule, and returns last-stage outputs re-assembled to [B, ...].

    Other mesh axes stay in GSPMD auto mode (partial manual over {pp}).

    Boundary dtypes are chosen so no bf16 all-reduce is ever emitted
    (XLA's all-reduce promotion miscompiles mixed-dtype combined
    all-reduces on the CPU backend, and f32 boundary grads are also the
    numerically safe choice): x crosses INTO shard_map as f32 — its
    transpose-psum is therefore f32 — and outputs cross OUT stage-
    sharded (transpose = pad, no collective at all). Internal
    stage→stage ppermutes stay in the compute dtype (bf16 on ICI).
    """
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(
            f"batch {batch} must divide into {n_microbatches} microbatches")
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis_name, 1)
    stacked_dim = jax.tree.leaves(stacked_params)[0].shape[0]
    if stacked_dim != n_stages:
        raise ValueError(
            f"stacked params declare {stacked_dim} stages but mesh axis "
            f"`{axis_name}` has {n_stages} devices — they must match "
            "(a mismatch would silently drop layers)")
    mb = batch // n_microbatches
    compute_dtype = x.dtype
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:]).astype(jnp.float32)

    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)

    def sharded(local_params, x_micro):
        # local_params leaves arrive as [1, ...]: squeeze the stage dim.
        local = jax.tree.map(lambda a: a[0], local_params)
        outs = spmd_pipeline(
            stage_fn, local, x_micro.astype(compute_dtype),
            axis_name=axis_name, double_buffer=double_buffer)
        return outs[None]  # [1(stage), n_micro, mb, ...]

    fn = jax.shard_map(
        sharded,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(axis_name),
        axis_names={axis_name},
        check_vma=False,
    )
    out = fn(stacked_params, x_mb)  # [n_stages, n_micro, mb, ...]
    out = out[n_stages - 1]  # only the last stage's outputs are real
    return out.reshape((batch,) + out.shape[2:])


def stack_stages(layer_params, n_stages: int):
    """[L, ...] stacked layer params → [n_stages, L/n_stages, ...]."""

    def split(leaf):
        L = leaf.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers do not divide into {n_stages} stages")
        return leaf.reshape((n_stages, L // n_stages) + leaf.shape[1:])

    return jax.tree.map(split, layer_params)
