"""Sharded train/eval step construction (the only true hot loop —
SURVEY.md §3 boundary summary: everything else orchestrates around the
compiled step function).

Placement strategy: params/state get explicit NamedShardings from the
model's logical axes + the mesh's rule table, and every optimizer-state
leaf shaped like its parameter is pinned to that parameter's sharding
(``_place_opt_state``) in the init AND in the step. Propagation alone
does not do it: a ``zeros_like`` in the jitted init comes out
replicated, which costs a full unsharded mu/nu per device at start-up
and makes step 2's arguments differ from step 1's. Gradients are
reduced by the compiler-inserted psums over dp/fsdp. ``donate`` on the
state keeps HBM flat across steps.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from polyaxon_tpu.models.common import ModelDef
from polyaxon_tpu.parallel.sharding import Rules, tree_shardings

TrainState = dict[str, Any]  # {"params", "state", "opt_state", "step"}


def state_shardings(model_def: ModelDef, mesh: Mesh, rules: Rules) -> dict:
    logical = model_def.logical_axes()
    return {
        "params": tree_shardings(logical["params"], mesh, rules),
        "state": tree_shardings(logical.get("state", {}), mesh, rules),
    }


def _place_opt_state(optimizer, opt_state, params, param_shardings):
    """Pin each params-shaped optimizer leaf (adam mu/nu, momentum) to
    its parameter's sharding; leaves of another shape (adafactor's
    factored rows/columns, counters) are left to the compiler."""

    def masked(leaf):  # optax.masked's placeholder for frozen params
        return isinstance(leaf, optax.MaskedNode)

    def place(leaf, param, sharding):
        if masked(leaf) or leaf.shape != param.shape:
            return leaf
        return jax.lax.with_sharding_constraint(leaf, sharding)

    return optax.tree_map_params(optimizer, place, opt_state, params,
                                 param_shardings, is_leaf=masked)


def build_init(
    model_def: ModelDef,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Rules,
) -> Callable[[jax.Array], TrainState]:
    shardings = state_shardings(model_def, mesh, rules)

    def init_fn(rng: jax.Array) -> TrainState:
        variables = model_def.init(rng)
        params = jax.lax.with_sharding_constraint(variables["params"], shardings["params"])
        mutable = variables.get("state", {})
        if mutable:
            mutable = jax.lax.with_sharding_constraint(mutable, shardings["state"])
        opt_state = _place_opt_state(optimizer, optimizer.init(params),
                                     params, shardings["params"])
        return {
            "params": params,
            "state": mutable,
            "opt_state": opt_state,
            "step": jnp.zeros((), dtype=jnp.int32),
        }

    return jax.jit(init_fn)


def build_train_step(
    model_def: ModelDef,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Rules,
    accum_steps: int = 1,
) -> Callable[[TrainState, dict, jax.Array], tuple[TrainState, dict]]:
    """One optimizer update per call. With ``accum_steps > 1`` the batch
    (still the full per-update global batch) is split into that many
    microbatches and gradients accumulate inside a ``lax.scan`` — one
    compiled program, peak activation memory divided by ``accum_steps``.
    """
    shardings = state_shardings(model_def, mesh, rules)
    uniform_keys = set(model_def.uniform_metrics) | {"loss_unweighted"}

    def grads_of(params, mutable, batch, rng, scales=None):
        """``scales=(masked_scale, unmasked_scale)`` rescales the loss
        BEFORE differentiation — grad is linear, so scaling the per-
        microbatch loss components here makes the accumulated gradient
        exactly the full-batch one. Models with a mask-independent loss
        component (MoE router aux) expose it as the differentiable
        ``loss_unweighted`` metric; everything else in the loss is
        treated as a per-valid-token mean."""

        def loss_fn(p):
            loss, metrics, new_mutable = model_def.apply(
                {"params": p, "state": mutable}, batch, True, rng
            )
            if scales is not None:
                masked_scale, unmasked_scale = scales
                unweighted = metrics.get("loss_unweighted")
                if unweighted is None:
                    if model_def.uniform_metrics:
                        # Trace-time contract check: declaring uniform
                        # metrics without exposing the decomposition
                        # would silently mis-scale the aux loss term.
                        raise ValueError(
                            f"model `{model_def.name}` declares "
                            f"uniform_metrics={model_def.uniform_metrics} "
                            "but its apply() does not return the "
                            "differentiable `loss_unweighted` metric "
                            "required for exact gradient accumulation")
                    loss_out = masked_scale * loss
                else:
                    loss_out = (masked_scale * (loss - unweighted)
                                + unmasked_scale * unweighted)
            else:
                loss_out = loss
            return loss_out, (metrics, new_mutable)

        (_, (metrics, new_mutable)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        return grads, metrics, new_mutable

    def train_step(state: TrainState, batch: dict, rng: jax.Array):
        if accum_steps == 1:
            grads, metrics, new_mutable = grads_of(
                state["params"], state["state"], batch, rng)
        else:
            # [G, ...] → [k, G/k, ...] microbatches, re-constrained to
            # the batch layout so dp/fsdp sharding survives the reshape.
            from polyaxon_tpu.parallel.sharding import batch_spec

            micro = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]),
                batch)
            rngs = jax.random.split(rng, accum_steps)

            def constrain(mb):
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, NamedSharding(
                            mesh, batch_spec(mesh, rules, ndim=x.ndim))),
                    mb)

            # Masked losses are per-valid-token means, so each
            # microbatch's masked component is weighted by its valid-
            # token share w_i/W; mask-independent components (MoE
            # router aux, surfaced as the ``loss_unweighted`` metric)
            # are uniform per-microbatch means and get 1/k each. The
            # mask is an input, so W is known before the scan and the
            # scaling happens inside each grad — exact, not approximate.
            if isinstance(batch, dict) and batch.get("mask") is not None:
                w_micro = micro["mask"].astype(jnp.float32).sum(
                    axis=tuple(range(1, micro["mask"].ndim)))
            else:
                w_micro = jnp.ones((accum_steps,), jnp.float32)
            # Clamp: a fully-masked batch (W == 0) must yield zero
            # masked grads like the accum=1 path, not 0/0 = NaN params.
            w_total = jnp.maximum(w_micro.sum(), 1.0)
            uniform_scale = jnp.float32(1.0 / accum_steps)

            def body(carry, xs):
                grads_acc, mutable = carry
                mb, r, w = xs
                mb = constrain(mb)
                g, m, new_mutable = grads_of(
                    state["params"], mutable, mb, r,
                    scales=(w / w_total, uniform_scale))
                grads_acc = jax.tree.map(
                    lambda acc, gi: acc + gi.astype(jnp.float32),
                    grads_acc, g)
                return (grads_acc, new_mutable), dict(m)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (grads, new_mutable), metrics_seq = jax.lax.scan(
                body, (zeros, state["state"]), (micro, rngs, w_micro))
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, state["params"])

            # Reporting mirrors the grad weighting: mask-weighted means
            # for masked metrics, uniform means for mask-independent
            # ones, and ``loss`` recombined from its two components.
            def agg_masked(v):
                return (w_micro * v).sum() / w_total

            metrics = {k: agg_masked(v) for k, v in metrics_seq.items()}
            unweighted = metrics_seq.get("loss_unweighted")
            if unweighted is not None:
                for key in uniform_keys:
                    if key in metrics_seq:
                        metrics[key] = metrics_seq[key].mean()
                metrics["loss"] = (
                    agg_masked(metrics_seq["loss"] - unweighted)
                    + unweighted.mean())

        updates, new_opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        new_params = optax.apply_updates(state["params"], updates)
        new_params = jax.lax.with_sharding_constraint(new_params, shardings["params"])
        new_opt_state = _place_opt_state(optimizer, new_opt_state, new_params,
                                         shardings["params"])
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        new_state = {
            "params": new_params,
            "state": new_mutable,
            "opt_state": new_opt_state,
            "step": state["step"] + 1,
        }
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=(0,))


def build_eval_step(model_def: ModelDef) -> Callable[[TrainState, dict], dict]:
    def eval_step(state: TrainState, batch: dict) -> dict:
        _, metrics, _ = model_def.apply(
            {"params": state["params"], "state": state["state"]}, batch, False, None
        )
        return metrics

    return jax.jit(eval_step)
