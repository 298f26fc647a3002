#!/usr/bin/env python3
"""One training cell, once: the process that holds the chips.

Registers the configuration and submits the cell's job to the program's
own loop (``runtime/loop.py run_jaxjob``: mesh, ``lm_synthetic`` with
prefetch, the step compiled once ahead of time, adamw). The loop calls
``on_metrics`` after every step, once the step's loss is ready
(``log_every: 1``), and that callback is the benchmark's clock: it
stamps each step's end, keeps the first steps' loss and gradient norm
for the comparison with the reference, opens the window after the
settle steps and asks the loop to stop (``should_stop``) once the
window has lasted ``--seconds``. One object, the loop's compiled step
with its state, runs the first steps and the window alike.

The loop hands out scalars only, and rounding moves a loss or a global
norm at second order with either sign, so a step one precision down
would pass on them. ``StateWatch`` therefore stands around that one
compiled step for its first calls and reads the state it returns, leaf
by leaf: the first gradient as Adam got it (its first moment after one
step) and the parameters' change after the check steps. It reads; the
state and the executable that go on into the window are the loop's own.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import common, program  # noqa: E402

TRACE_STEPS = 6
ADAM_B1 = 0.9          # runtime/optim.py: optax.adamw(b1=0.9)


BLOCK = 4 << 20        # elements a block of a leaf holds at most (16 MB)


def _leaves(tree) -> list:
    """[(name, leaf)] with the names the reference's tree has."""
    import jax

    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def blocks(shape: tuple, limit: int) -> tuple[list, tuple]:
    """(starts, sizes): equal blocks of at most `limit` elements that
    tile `shape`, the leading axes cut first."""
    import itertools
    import math

    sizes = list(shape)
    for axis, n in enumerate(shape):
        rest = math.prod(sizes) // n
        if rest <= limit:
            sizes[axis] = max(d for d in range(1, n + 1)
                              if n % d == 0 and d * rest <= limit)
            break
        sizes[axis] = 1
    starts = itertools.product(*(range(0, n, d)
                                 for n, d in zip(shape, sizes)))
    return list(starts), tuple(sizes)


class StateWatch:
    """Per-leaf norms of the job's own state around its first steps.

    The job's state is nearly all the chip holds (8.46 of its 8.49 GB
    peak in the dense cell), so a whole leaf's copy beside it (0.54 GB)
    raised the peak the run reports. Everything here therefore goes
    block by block: a block is cut from a leaf, read to the host or
    compared with the block read at the start, and dropped."""

    def __init__(self, check_steps: int, limit: int = BLOCK):
        import functools

        import jax
        import jax.numpy as jnp

        self.check_steps, self.calls, self.limit = check_steps, 0, limit
        self.start: dict = {}               # host copy of the parameters
        self.grad0: dict = {}
        self.update: dict = {}
        self.cut = jax.jit(jax.lax.dynamic_slice, static_argnums=2)
        self.gap2 = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(jnp.float32) - b)))

    def _blocks_of(self, leaf):
        starts, sizes = blocks(leaf.shape, self.limit)
        return (self.cut(leaf, start, sizes) for start in starts)

    def wrap(self, compiled):
        """What stands where the loop holds its compiled step."""
        import numpy as np

        def step(state, batch, rng):
            if self.calls >= self.check_steps:
                return compiled(state, batch, rng)
            if self.calls == 0:
                self.start = {name: [np.asarray(b) for b in
                                     self._blocks_of(leaf)]
                              for name, leaf in _leaves(state["params"])}
            out = compiled(state, batch, rng)
            self.after(out[0])
            return out

        step.as_text = compiled.as_text
        return step

    def after(self, state) -> None:
        import math

        import jax

        self.calls += 1
        if self.calls in (1, self.check_steps):
            # Read once the step has ended, not on top of its activations.
            jax.block_until_ready(state["params"])
        if self.calls == 1:
            adam = [s for s in jax.tree_util.tree_leaves(
                state["opt_state"], is_leaf=lambda s: hasattr(s, "mu"))
                if hasattr(s, "mu")]
            for name, mu in _leaves(adam[0].mu) if adam else []:
                self.grad0[name] = math.sqrt(sum(
                    float(self.gap2(b, 0.0)) for b in self._blocks_of(mu))
                ) / (1 - ADAM_B1)
        if self.calls == self.check_steps:
            for name, leaf in _leaves(state["params"]):
                self.update[name] = math.sqrt(sum(
                    float(self.gap2(now, before)) for now, before
                    in zip(self._blocks_of(leaf), self.start.pop(name))))


def watch_step(watch: StateWatch):
    """Put `watch` around the step the loop compiles (it lowers and
    compiles what ``build_train_step`` returns, then calls that).
    Returns the builder that was there, to be put back."""
    from types import SimpleNamespace as Stand

    from polyaxon_tpu.runtime import loop

    build = loop.build_train_step

    def stand_in(*args, **kwargs):
        fn = build(*args, **kwargs)
        return Stand(lower=lambda *a: Stand(
            compile=lambda: watch.wrap(fn.lower(*a).compile())))

    loop.build_train_step = stand_in
    return build


def break_step(kind: str) -> None:
    """The test of the check itself: break the timed path underneath.
    `frozen` makes the step return its state unchanged; `half_batch`
    leaves the second half of every batch out of the loss."""
    from polyaxon_tpu.runtime import loop, step

    real = step.build_train_step

    def build(model_def, optimizer, mesh, rules, accum_steps=1):
        import dataclasses

        import jax

        if kind == "half_batch":
            apply = model_def.apply

            def half(variables, batch, train=True, rng=None):
                cut = {k: v[: max(v.shape[0] // 2, 1)]
                       for k, v in batch.items()}
                return apply(variables, cut, train, rng)

            model_def = dataclasses.replace(model_def, apply=half)
        fn = real(model_def, optimizer, mesh, rules, accum_steps)
        if kind != "frozen":
            return fn

        def frozen(state, batch, rng):
            new_state, metrics = fn.__wrapped__(state, batch, rng)
            return {**state, "step": new_state["step"]}, metrics

        return jax.jit(frozen, donate_argnums=(0,))

    loop.build_train_step = build


def run(plan: dict) -> dict:
    import jax

    device = common.device_info(plan["chips"], plan["require_chip"])
    compiles = common.CompileCounter()
    common.say({"phase": "train", "note": "loading"}, device)
    if plan.get("break_path"):
        break_step(plan["break_path"])

    from polyaxon_tpu.polyflow import V1JAXJob
    from polyaxon_tpu.runtime import run_jaxjob

    config, traffic = plan["config"], plan["traffic"]
    name, family, cfg = program.register(config, "train")
    train = config["train"]
    seq_len = int(traffic["seq_len"])
    runtime = program.runtime_section(config, name, plan["seed"], seq_len)
    if "capacity_factor" in train:       # a model-config override
        runtime["capacity_factor"] = train["capacity_factor"]
    job = V1JAXJob.from_dict({
        "kind": "jaxjob", "mesh": {"axes": dict(train["mesh"])},
        "runtime": runtime})
    check_steps = int(traffic["check_steps"])
    watch = StateWatch(check_steps)
    unwatched = watch_step(watch)
    open_step = check_steps + int(traffic.get("settle_steps", 1)) - 1
    seconds = float(plan["seconds"])
    steps: list[dict] = []
    state = {"t_open": None, "t_close": None, "tracing": False,
             "trace_from": None, "trace_t0": None, "trace_t1": None}
    trace_dir = os.path.join(plan["out_dir"], "trace")

    def on_metrics(step: int, vals: dict) -> None:
        now = time.time()
        steps.append({"step": step, "t": now, "vals": dict(vals)})
        if step == open_step:
            state["t_open"] = now
            if plan["trace"]:
                jax.profiler.start_trace(
                    trace_dir, profiler_options=common.trace_options())
                state.update(tracing=True, trace_from=step,
                             trace_t0=time.time())
        elif state["tracing"] and step >= state["trace_from"] + TRACE_STEPS:
            jax.profiler.stop_trace()
            state.update(tracing=False, trace_t1=time.time())
        if (state["t_open"] is not None and state["t_close"] is None
                and now - state["t_open"] >= seconds):
            state["t_close"] = now

    # As many devices as the cell's mesh names (the CPU rehearsal gives
    # itself that many virtual ones).
    size = 1
    for axis in train["mesh"].values():
        size *= axis
    devices = jax.devices()[:size]
    try:
        result = run_jaxjob(
            job, on_metrics=on_metrics, devices=devices,
            should_stop=lambda: state["t_close"] is not None)
    finally:
        from polyaxon_tpu.runtime import loop

        loop.build_train_step = unwatched
        if state["tracing"]:
            jax.profiler.stop_trace()
            state.update(tracing=False, trace_t1=time.time())
    peak = common.memory_peak(plan["chips"])
    window_steps = [s for s in steps if state["t_open"] is not None
                    and state["t_open"] < s["t"] <= state["t_close"]]
    return {
        "kind": "train", "device": {**device, "memory_peak_bytes": peak},
        "t_open": state["t_open"], "t_close": state["t_close"],
        "steps": steps, "window_steps": window_steps,
        "leaf_norms": {"grad0": watch.grad0, "update": watch.update},
        "tokens_per_step": int(train["global_batch_size"]) * seq_len,
        "seq_len": seq_len, "layers": cfg.n_layers,
        "chips": plan["chips"],
        "batch_per_chip": max(int(train["global_batch_size"])
                              // max(plan["chips"], 1), 1),
        "compiles_in_window": compiles.between(
            state["t_open"] or 0, state["t_close"] or 0),
        "compiles_total": len(compiles.events),
        "loop": {"compile_time_s": result.compile_time_s,
                 "compile_cache": result.compile_cache,
                 "step_kernels": result.step_kernels,
                 "param_count": result.param_count,
                 "param_bytes_per_device": {
                     str(k): v for k, v in
                     result.param_bytes_per_device.items()},
                 "input_wait_ms": result.input_wait_ms,
                 "peak_hbm_bytes": result.peak_hbm_bytes},
        "trace_dir": trace_dir if plan["trace"] else None,
        "trace_span": [state["trace_t0"], state["trace_t1"]],
    }


def main() -> None:
    from harness import phase_end

    with open(sys.argv[1]) as fh:
        plan = json.load(fh)
    try:
        result = run(plan)
    except common.NoChip as exc:
        common.fail(str(exc), code=3)
    phase_end.finish(plan, result)


if __name__ == "__main__":
    main()
