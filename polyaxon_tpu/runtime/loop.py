"""The JAXJob training loop: mesh → data → compiled step → metrics/
checkpoints. Single code path from one chip to multi-host slices (only
the mesh and the env contract change — SURVEY.md §7 step 2).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Optional

import contextlib

import jax
import numpy as np

from polyaxon_tpu.models import config_of, get_model
from polyaxon_tpu.obs import flight as obs_flight
from polyaxon_tpu.obs import metrics as obs_metrics
from polyaxon_tpu.obs import trace as obs_trace
from polyaxon_tpu.parallel import build_mesh, rules_for_mesh
from polyaxon_tpu.parallel.sharding import bytes_per_device, param_bytes
from polyaxon_tpu.polyflow.runs import V1JAXJob, V1JaxCheckpointing
from polyaxon_tpu.runtime import compile_cache
from polyaxon_tpu.runtime import data as data_lib
from polyaxon_tpu.runtime.checkpoint import (CheckpointManager,
                                             TieredCheckpointManager)
from polyaxon_tpu.runtime.config import RuntimeConfig
from polyaxon_tpu.runtime.optim import build_optimizer
from polyaxon_tpu.runtime.step import build_eval_step, build_init, build_train_step

logger = logging.getLogger(__name__)

MetricsCallback = Callable[[int, dict[str, float]], None]


@dataclasses.dataclass
class TrainResult:
    steps: int
    final_metrics: dict[str, float]
    throughput: float  # units/sec (tokens or examples)
    unit: str
    units_per_step: int
    wall_time: float
    param_count: int
    restored_from_step: Optional[int] = None
    # Checkpoint steps whose bytes were skipped as corrupt during the
    # restore that produced restored_from_step (newest first; empty on
    # a clean restore or cold start).
    restore_skipped_steps: list[int] = dataclasses.field(default_factory=list)
    # Tier that satisfied the restore ("0" in-memory replica, "1" local
    # spill, "2" store); None on a cold start.
    restore_tier: Optional[str] = None
    # Host time blocked on `next(batches)`, averaged per timed step —
    # ~0 when the prefetcher keeps up, ≈ generation+transfer time when
    # the input pipeline is the bottleneck.
    input_wait_ms: float = 0.0
    # Wall time of lowering + compiling the train step (the one
    # compilation of the run); drops to executable-load time on a
    # persistent-compile-cache hit.
    compile_time_s: float = 0.0
    # The persistent compile cache as this run used it
    # (runtime.compile_cache.stats): directory, requests, hits.
    compile_cache: dict = dataclasses.field(default_factory=dict)
    # Mosaic kernels in the compiled step, by pallas_call name → call
    # sites (perf.hlo.pallas_kernels). Empty means every attention ran
    # a reference path: on the CPU mesh (interpret mode), or on a chip
    # because a shape gave way.
    step_kernels: dict[str, int] = dataclasses.field(default_factory=dict)
    # Parameter bytes resident on each addressable device, by device id
    # (parallel.sharding.bytes_per_device): sharded, replicated, or all
    # on the first.
    param_bytes_per_device: dict[int, int] = dataclasses.field(
        default_factory=dict)
    # Peak device memory of this process's first device, where the
    # backend reports it (None on the CPU mesh).
    peak_hbm_bytes: Optional[int] = None

    def program_outputs(self) -> dict:
        """What the run says about its compiled program and where it
        lived — the part of a run's outputs both entry points (the
        in-process executor and runtime/launch.py) log alike."""
        return {"compile_time_s": self.compile_time_s,
                "compile_cache": self.compile_cache,
                "step_kernels": self.step_kernels,
                "param_bytes_per_device": self.param_bytes_per_device,
                "peak_hbm_bytes": self.peak_hbm_bytes}


def _dataset_kwargs(cfg: RuntimeConfig, model_cfg, per_host_batch: int) -> dict:
    kwargs: dict[str, Any] = {"batch_size": per_host_batch, "seed": cfg.seed}
    extras = dict(cfg.__pydantic_extra__ or {})
    for key in ("path", "tokenizer", "image_size", "num_classes",
                "mask_rate"):
        if key in extras:
            kwargs[key] = extras[key]
    if cfg.seq_len:
        kwargs["seq_len"] = cfg.seq_len
    elif hasattr(model_cfg, "max_seq_len"):
        kwargs["seq_len"] = min(model_cfg.max_seq_len, 2048)
    if hasattr(model_cfg, "vocab_size"):
        kwargs["vocab_size"] = model_cfg.vocab_size
    if hasattr(model_cfg, "image_size") and "image_size" not in kwargs:
        kwargs["image_size"] = model_cfg.image_size
    if hasattr(model_cfg, "num_classes") and "num_classes" not in kwargs:
        kwargs["num_classes"] = model_cfg.num_classes
    return kwargs


def _span(tracer: Optional["obs_trace.RunTracer"], name: str, **attrs):
    """Span when tracing is on, nullcontext (yielding None) when off —
    keeps every instrumentation site a one-line `with`."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, attributes=attrs or None)


def run_jaxjob(
    job: V1JAXJob,
    *,
    artifacts_dir: Optional[str] = None,
    on_metrics: Optional[MetricsCallback] = None,
    devices: Optional[list] = None,
    mesh_axes: Optional[dict[str, int]] = None,
    should_stop: Optional[Callable[[], bool]] = None,
    tracer: Optional[obs_trace.RunTracer] = None,
) -> TrainResult:
    """Execute a builtin-runtime JAXJob in-process.

    Lifecycle tracing: with an ``artifacts_dir`` the loop emits
    runtime/jit_compile/restore/step/checkpoint/eval spans. An explicit
    ``tracer`` (the in-process executor passes one parented under its
    `execute` span) is used as-is and left open for its owner; without
    one a tracer is built from the env contract (the subprocess path —
    the executor stamps ``POLYAXON_TRACE_PARENT``) and closed by the
    loop's ExitStack.
    """
    if not job.runtime:
        raise ValueError("run_jaxjob requires a jaxjob with a `runtime` section")
    cfg = RuntimeConfig.model_validate(job.runtime)

    close_tracer = False
    if tracer is None and artifacts_dir:
        tracer = obs_trace.RunTracer.from_env(artifacts_dir,
                                              component="runtime")
        close_tracer = True

    compile_cache.enable()
    return _run_jaxjob(job, cfg, artifacts_dir=artifacts_dir,
                       on_metrics=on_metrics, devices=devices,
                       mesh_axes=mesh_axes,
                       should_stop=should_stop, tracer=tracer,
                       close_tracer=close_tracer)


def _run_jaxjob(
    job: V1JAXJob,
    cfg: RuntimeConfig,
    *,
    artifacts_dir: Optional[str],
    on_metrics: Optional[MetricsCallback],
    devices: Optional[list],
    should_stop: Optional[Callable[[], bool]],
    mesh_axes: Optional[dict[str, int]] = None,
    tracer: Optional[obs_trace.RunTracer] = None,
    close_tracer: bool = False,
) -> TrainResult:
    # An explicit `mesh_axes` overrides the spec's resolved axes — the
    # elastic resize path compiles the SAME job for a shrunk/regrown
    # device subset whose axis product no longer matches the spec.
    mesh = build_mesh(job.mesh, job.get_topology(), devices=devices,
                      axes=mesh_axes)
    rules = rules_for_mesh(mesh)
    logger.info("mesh axes=%s devices=%d", dict(zip(mesh.axis_names, mesh.devices.shape)),
                mesh.devices.size)

    base_cfg = config_of(cfg.model)
    overrides = cfg.model_overrides(type(base_cfg))
    model_def = get_model(cfg.model, **overrides)
    model_cfg = dataclasses.replace(base_cfg, **overrides)

    n_devices = mesh.devices.size
    if cfg.global_batch_size:
        global_batch = cfg.global_batch_size
    else:
        global_batch = (cfg.batch_size or 8) * n_devices
    if global_batch % jax.process_count():
        raise ValueError(
            f"global batch {global_batch} must divide process count {jax.process_count()}"
        )
    per_host_batch = global_batch // jax.process_count()

    dataset_name = cfg.dataset or data_lib.dataset_for_model(cfg.model)
    ds_kwargs = _dataset_kwargs(cfg, model_cfg, per_host_batch)

    optimizer = build_optimizer(cfg)
    if cfg.lora_rank:
        from polyaxon_tpu.models.lora import lora_model_def, wrap_optimizer

        model_def = lora_model_def(model_def, cfg.lora_rank,
                                   cfg.lora_alpha,
                                   cfg.lora_targets)
        optimizer = wrap_optimizer(optimizer)
        logger.info("lora: rank=%d alpha=%s targets=%s", cfg.lora_rank,
                    cfg.lora_alpha, cfg.lora_targets or "default")

    # The prefetch producer registers its close() here: stop, drain,
    # join on EVERY exit — normal completion, should_stop, or a raise
    # anywhere in the loop — so no thread outlives its run. The tracer's
    # EventWriter rides the same stack when this loop owns it.
    with mesh, contextlib.ExitStack() as cleanup:
        run_span = None
        if tracer is not None:
            if close_tracer:
                cleanup.callback(tracer.close)
            run_span = cleanup.enter_context(tracer.span(
                "runtime", attributes={"model": cfg.model,
                                       "steps": cfg.steps,
                                       "devices": mesh.devices.size}))
        init_fn = build_init(model_def, optimizer, mesh, rules)
        # polycheck: ignore[hotpath-host-sync] -- config scalar from the job spec, not a device value; one-shot setup before the loop
        accum = max(int(cfg.grad_accum_steps or 1), 1)
        if accum > 1:
            if global_batch % accum:
                raise ValueError(
                    f"grad_accum_steps {accum} must divide the global "
                    f"batch {global_batch}")
            from polyaxon_tpu.parallel.sharding import batch_spec

            spec = batch_spec(mesh, rules)
            batch_axes = spec[0] if len(spec) else None
            if isinstance(batch_axes, str):
                batch_axes = (batch_axes,)
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            shards = 1
            for axis in batch_axes or ():
                shards *= sizes[axis]
            if (global_batch // accum) % max(shards, 1):
                raise ValueError(
                    f"microbatch {global_batch // accum} (global batch "
                    f"{global_batch} / grad_accum_steps {accum}) must stay "
                    f"divisible by the {shards}-way batch sharding")
        train_step = build_train_step(model_def, optimizer, mesh, rules,
                                      accum_steps=accum)

        rng = jax.random.key(cfg.seed)
        state = init_fn(rng)
        n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
        logger.info("model=%s params=%.2fM bytes=%.1fMB", cfg.model, n_params / 1e6,
                    param_bytes(state["params"]) / 1e6)
        params_per_device = bytes_per_device(state["params"])

        ckpt: Optional[CheckpointManager] = None
        restored_from = None
        restore_skipped: list[int] = []
        restore_tier: Optional[str] = None
        ckpt_spec = job.checkpointing or V1JaxCheckpointing(enabled=False)
        if artifacts_dir and ckpt_spec.enabled:
            ckpt = TieredCheckpointManager(f"{artifacts_dir}/checkpoints",
                                           ckpt_spec)
            if ckpt_spec.restore_on_start and ckpt.latest_step() is not None:
                with _span(tracer, "restore") as sp:
                    state = ckpt.restore(state)
                    restored_from = int(state["step"])
                    restore_skipped = list(ckpt.last_restore_skipped)
                    restore_tier = ckpt.last_restore_tier
                    if sp is not None:
                        sp.set(restored_from_step=restored_from,
                               skipped_steps=restore_skipped,
                               restore_tier=restore_tier)

        seq = ds_kwargs.get("seq_len", 1)
        units_per_step = global_batch * (seq if model_def.unit == "tokens" else 1)

        start_step = int(state["step"])
        if start_step >= cfg.steps:
            if ckpt:
                ckpt.close()
            return TrainResult(
                steps=start_step,
                final_metrics={},
                throughput=0.0,
                unit=model_def.unit,
                units_per_step=0,
                wall_time=0.0,
                # polycheck: ignore[hotpath-host-sync] -- n_params is a host-side sum of static leaf sizes; no device sync
                param_count=int(n_params),
                restored_from_step=restored_from,
                restore_skipped_steps=restore_skipped,
                restore_tier=restore_tier,
            )
        # Data streams are index-addressable (batch i = f(seed, i)), so a
        # restored run resumes the stream at its step instead of replaying
        # from batch 0 — the iterator is built only after restore (which
        # also makes prefetch resume-exact for free: batches that were
        # prefetched but unconsumed at interrupt are simply regenerated).
        host_iter = data_lib.get_dataset(dataset_name, start_batch=start_step,
                                         **ds_kwargs)
        batches = data_lib.shard_batches(host_iter, mesh, rules)
        prefetcher: Optional[data_lib.PrefetchIterator] = None
        if cfg.prefetch > 0:
            # Overlap the host with the device: batch i+k generates and
            # commits to its NamedSharding on a background thread while
            # the device runs step i.
            batches = prefetcher = data_lib.PrefetchIterator(
                batches, depth=cfg.prefetch)
            cleanup.callback(prefetcher.close)
        # Periodic held-out evaluation: a FIXED batch set drawn from the
        # same dataset family at a disjoint seed (or from `eval_path`
        # when given — e.g. a separate validation corpus for lm_text),
        # so every eval scores the same data and curves are comparable.
        eval_step = run_eval = None
        if cfg.eval_every:
            eval_step = build_eval_step(model_def)
            eval_kwargs = dict(ds_kwargs)
            extras = dict(cfg.__pydantic_extra__ or {})
            if extras.get("eval_path"):
                eval_kwargs["path"] = extras["eval_path"]
            eval_kwargs["seed"] = cfg.seed + 104_729  # disjoint stream
            eval_kwargs["start_batch"] = 0
            n_eval = max(cfg.eval_steps, 1)
            # Materialize the fixed batch set ONCE: rebuilding the
            # dataset pipeline per eval would re-pay its construction
            # cost (e.g. lm_text's corpus mmap + vocab scan) at every
            # cadence point.
            _eval_iter = data_lib.shard_batches(
                data_lib.get_dataset(dataset_name, **eval_kwargs),
                mesh, rules)
            eval_batches = [next(_eval_iter) for _ in range(n_eval)]
            del _eval_iter

            def run_eval(state) -> dict[str, float]:
                sums: dict[str, float] = {}
                for batch in eval_batches:
                    for k, v in eval_step(state, batch).items():
                        sums[k] = sums.get(k, 0.0) + float(v)
                return {f"eval_{k}": v / n_eval for k, v in sums.items()}

        final_metrics: dict[str, float] = {}
        last_eval: dict[str, float] = {}
        evaled_at = -1  # state["step"] value the last eval scored
        step_rng = jax.random.key(cfg.seed + 17)
        # Compile the step ONCE, ahead of time, outside the timed
        # window: the loop then holds the executable it runs, so it can
        # say which kernels are in it, a later shape or layout drift
        # raises instead of recompiling behind the run's back, and
        # compile_time_s is the compile alone, so cache-hit restarts
        # are attributable.
        from polyaxon_tpu.perf.hlo import pallas_kernels

        first_batch = next(batches)
        with _span(tracer, "jit_compile") as sp:
            t_compile = time.perf_counter()
            train_step = train_step.lower(
                state, first_batch, step_rng).compile()
            compile_time_s = time.perf_counter() - t_compile
            step_kernels = pallas_kernels(train_step.as_text())
            if sp is not None:
                sp.set(compile_time_s=round(compile_time_s, 3),
                       step_kernels=step_kernels)
        logger.info("train step compiled in %.1fs, kernels=%s",
                    compile_time_s, step_kernels or "none")
        # Per-step MFU self-reporting (SURVEY §5.1): every emission
        # carries tokens/sec + achieved TFLOPs/chip, and MFU when both
        # the analytic FLOPs/token and the chip's peak are known
        # (CPU mesh → flops known, no peak → mfu omitted; a TPU kind
        # missing from the peaks table raises).
        from polyaxon_tpu.runtime.flops import peak_flops, train_flops_per_token

        n_chips = int(mesh.devices.size)
        # polycheck: ignore[hotpath-host-sync] -- n_params is a host-side sum of static leaf sizes; one-shot setup before the loop
        flops_unit = (train_flops_per_token(cfg.model, seq, int(n_params))
                      if model_def.unit == "tokens" else None)
        peak = peak_flops(mesh.devices.flat[0])
        t_emit = time.perf_counter()
        # polycheck: ignore[hotpath-wallclock] -- observability timestamp: span wall-clock twin of t_emit; never feeds training state or replay
        t_emit_wall = time.time()  # wall twin of t_emit for step spans
        steps_since_emit = 0
        emitted_compile = False
        wait_window = 0.0  # host seconds blocked on data, per emission
        wait_total = 0.0   # ... over all timed steps

        def emit(step: int, metrics: dict) -> None:
            """One emission window: the steps since the last emission,
            as metrics (on_metrics), one `step` span and one histogram
            sample."""
            nonlocal t_emit, t_emit_wall, steps_since_emit, wait_window
            nonlocal emitted_compile
            # polycheck: ignore[hotpath-host-sync] -- deliberate emission-window materialization at log_every cadence, off the per-step path
            vals = {k: float(v) for k, v in metrics.items()}
            # Rolling window since the last emission; block so the
            # window covers completed device work, not dispatch.
            # polycheck: ignore[hotpath-host-sync] -- deliberate emission-window sync (see comment above): throughput must cover completed device work
            jax.block_until_ready(metrics["loss"])
            window = time.perf_counter() - t_emit
            if window > 0 and steps_since_emit:
                ups = units_per_step * steps_since_emit / window
                vals[f"{model_def.unit}_per_sec"] = ups
                vals["step_time_ms"] = 1e3 * window / steps_since_emit
                # Host time blocked on next(batches), per step:
                # ~0 when prefetch keeps up; ≈ generation+transfer
                # when the input pipeline is the bottleneck.
                vals["input_wait_ms"] = (1e3 * wait_window
                                         / steps_since_emit)
                if flops_unit:
                    achieved = ups * flops_unit / n_chips
                    vals["tflops_per_sec_per_chip"] = achieved / 1e12
                    if peak:
                        vals["mfu"] = achieved / peak
            if not emitted_compile:
                # One-shot: the compile wall, so a metric stream can
                # attribute a cheap restart to the persistent compile
                # cache.
                vals["compile_time_s"] = compile_time_s
                emitted_compile = True
            # The emission window is one `step` span on the
            # timeline (reusing the already-derived step_time_ms /
            # input_wait_ms) and one histogram sample — per-window,
            # not per-step, so tracing cost stays off the hot path.
            if steps_since_emit and window > 0:
                obs_metrics.training_step_hist().observe(
                    window / steps_since_emit)
            if tracer is not None and steps_since_emit:
                tracer.record_completed(
                    # polycheck: ignore[hotpath-wallclock] -- observability timestamp: span end on the wall-clock timeline, per-window not per-step
                    "step", start=t_emit_wall, end=time.time(),
                    parent_id=(run_span.span_id if run_span is not None
                               else None),
                    attributes={
                        "from_step": step - steps_since_emit + 1,
                        "to_step": step,
                        "steps": steps_since_emit,
                        **{k: round(vals[k], 3) for k in
                           ("step_time_ms", "input_wait_ms", "loss")
                           if k in vals},
                    })
            steps_since_emit = 0
            wait_window = 0.0
            if tracer is not None:
                # The flight ring keeps the last emissions a dying
                # run saw — the postmortem's "final instruments".
                obs_flight.RECORDER.note(
                    tracer.trace_id, "metrics", step=step,
                    # polycheck: ignore[hotpath-host-sync] -- vals already holds host floats (materialized at the emission sync above); no new device sync
                    **{k: round(float(v), 5) for k, v in vals.items()})
            on_metrics(step, vals)
            # Stamp AFTER the callback: tracking I/O must not
            # deflate the next window's reported throughput.
            t_emit = time.perf_counter()
            # polycheck: ignore[hotpath-wallclock] -- observability timestamp: re-stamp the span wall twin after tracking I/O
            t_emit_wall = time.time()

        # The first execution runs outside the run-level timed window
        # (it pays one-time program load), but it consumed batch
        # `start_step` and advanced the state — a REAL training step, so
        # it opens the first emission window: step windows stay
        # contiguous from `start_step` across restore/resize segment
        # boundaries (the oracle's loss_continuity invariant reads
        # them), and a fresh run reports its first loss, which should
        # sit at ln(vocab).
        state, metrics = train_step(state, first_batch, step_rng)
        steps_since_emit = 1
        # polycheck: ignore[hotpath-host-sync] -- deliberate: the first execution must finish before the timed window opens
        jax.block_until_ready(metrics["loss"])
        if on_metrics and start_step % cfg.log_every == 0:
            emit(start_step, metrics)

        t0 = time.perf_counter()
        timed_steps = 0
        off_clock = 0.0  # eval + sync-checkpoint seconds, excluded
        for step in range(start_step + 1, cfg.steps):
            if should_stop is not None and should_stop():
                logger.info("stop requested at step %d", step)
                break
            profiling = cfg.profile_steps and step in cfg.profile_steps and artifacts_dir
            if profiling:
                jax.profiler.start_trace(f"{artifacts_dir}/profile")
            t_wait = time.perf_counter()
            batch = next(batches)
            dt_wait = time.perf_counter() - t_wait
            wait_window += dt_wait
            wait_total += dt_wait
            state, metrics = train_step(state, batch, step_rng)
            timed_steps += 1
            steps_since_emit += 1
            if profiling:
                # polycheck: ignore[hotpath-host-sync] -- deliberate: bound the profiler trace at a completed step; profiled steps are off the timed window
                jax.block_until_ready(metrics["loss"])
                jax.profiler.stop_trace()
            if on_metrics and (step % cfg.log_every == 0 or step == cfg.steps - 1):
                emit(step, metrics)
            if eval_step is not None and step % cfg.eval_every == 0:
                # Drain queued train dispatches BEFORE stamping the
                # exclusion window, or their device time would be
                # charged to eval and inflate reported throughput/MFU.
                # polycheck: ignore[hotpath-host-sync] -- deliberate: drain queued train dispatches so their device time is not charged to eval (see comment above)
                jax.block_until_ready(metrics["loss"])
                t_eval = time.perf_counter()
                with _span(tracer, "eval", step=step):
                    last_eval = run_eval(state)
                evaled_at = int(state["step"])
                if on_metrics:
                    on_metrics(step, last_eval)
                # Off the training clock, like checkpoint saves — for
                # both the per-emission window AND the run-level wall.
                dt_eval = time.perf_counter() - t_eval
                t_emit += dt_eval
                # polycheck: ignore[hotpath-wallclock] -- observability timestamp: restart the span wall twin after the eval exclusion window
                t_emit_wall = time.time()
                off_clock += dt_eval
            if ckpt and ckpt.should_save(step):
                t_save = time.perf_counter()
                with _span(tracer, "checkpoint", step=step):
                    ckpt.save(step, state)
                # Exclude (synchronous) checkpoint time too — an MFU
                # dip every save interval would make real regressions
                # indistinguishable from checkpoint cadence.
                dt_save = time.perf_counter() - t_save
                t_emit += dt_save
                # polycheck: ignore[hotpath-wallclock] -- observability timestamp: restart the span wall twin after the checkpoint exclusion window
                t_emit_wall = time.time()
                off_clock += dt_save
        # polycheck: ignore[hotpath-host-sync] -- deliberate end-of-run drain: the wall stamp below must cover all device work
        jax.block_until_ready(state["params"])
        # Run-level throughput matches the emitted stream: eval and
        # sync-save time are off the training clock in both.
        wall = time.perf_counter() - t0 - off_clock
        # polycheck: ignore[hotpath-host-sync] -- post-loop materialization of the final metrics; the loop is over
        final_metrics = {k: float(v) for k, v in metrics.items()}
        if eval_step is not None:
            # Outputs always carry an eval of the FINISHED params; skip
            # the extra pass (and the duplicate metric point) when the
            # cadence already scored them.
            if evaled_at != int(state["step"]):
                last_eval = run_eval(state)
                if on_metrics:
                    on_metrics(max(int(state["step"]) - 1, 0), last_eval)
            final_metrics.update(last_eval)
        final_step = int(state["step"])
        peak_hbm = (mesh.local_devices[0].memory_stats() or {}).get(
            "peak_bytes_in_use")

        # Flush the partial un-emitted window (an early stop — resize,
        # preemption, stop request — lands between emissions): without
        # this span the last `steps_since_emit` trained steps would be
        # a gap in the step-window stream and loss_continuity could not
        # hold across a resize boundary.
        if tracer is not None and steps_since_emit:
            window = time.perf_counter() - t_emit
            flush_to = final_step - 1
            attrs = {
                "from_step": flush_to - steps_since_emit + 1,
                "to_step": flush_to,
                "steps": steps_since_emit,
            }
            if window > 0:
                attrs["step_time_ms"] = round(
                    1e3 * window / steps_since_emit, 3)
                attrs["input_wait_ms"] = round(
                    1e3 * wait_window / steps_since_emit, 3)
                obs_metrics.training_step_hist().observe(
                    window / steps_since_emit)
            if "loss" in final_metrics:
                attrs["loss"] = round(final_metrics["loss"], 3)
            tracer.record_completed(
                # polycheck: ignore[hotpath-wallclock] -- observability timestamp: one span end after the loop has exited
                "step", start=t_emit_wall, end=time.time(),
                parent_id=(run_span.span_id if run_span is not None
                           else None),
                attributes=attrs)

        if ckpt:
            with _span(tracer, "checkpoint", step=final_step, final=True):
                ckpt.save(final_step, state, force=True)
            ckpt.close()

    throughput = units_per_step * timed_steps / wall if wall > 0 and timed_steps else 0.0
    return TrainResult(
        steps=final_step,
        final_metrics=final_metrics,
        throughput=throughput,
        unit=model_def.unit,
        units_per_step=units_per_step,
        wall_time=wall,
        # polycheck: ignore[hotpath-host-sync] -- n_params is a host-side sum of static leaf sizes; no device sync
        param_count=int(n_params),
        restored_from_step=restored_from,
        restore_skipped_steps=restore_skipped,
        restore_tier=restore_tier,
        input_wait_ms=1e3 * wait_total / timed_steps if timed_steps else 0.0,
        compile_time_s=compile_time_s,
        compile_cache=compile_cache.stats(),
        step_kernels=step_kernels,
        param_bytes_per_device=params_per_device,
        peak_hbm_bytes=peak_hbm,
    )
