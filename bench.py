#!/usr/bin/env python
"""Headline benchmark: JAXJob LM training throughput, tokens/sec/chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "platform": ..., "device_kind":
   ..., "n_chips": N, ...}

A measurement needs the chip: without ``--smoke`` the run fails (exit 1,
an ``error`` in the JSON line) unless JAX's default backend is a TPU, and
a kernel that fails to compile fails the run — nothing is retried on a
reference path and no earlier number is printed in its place. ``--smoke``
is a correctness gate of the control flow at a toy size; it runs on
whatever backend JAX has and says which in the same fields, so its
numbers cannot pass for a measurement. One process does everything: the
one that initializes the backend is the one that trains.

Model is a ~200M-param Llama proxy (8B does not fit one v5e chip with
optimizer state); metric is normalized per chip.

Usage: python bench.py [--smoke] [--model llama_200m] [--steps N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# FLOPs accounting + peak tables live in the package so the runtime
# loop self-reports the same MFU numbers (runtime/flops.py).

# (metric, unit) of the mode actually running — set once args are
# parsed; the top-level catch-all uses it so --tuner failures land on
# the polytune series, not the jaxjob one.
_ACTIVE = ["jaxjob_train_tokens_per_sec_per_chip", "tokens/sec/chip"]

def _flops_per_token(model: str, seq: int, param_count: int):
    from polyaxon_tpu.runtime.flops import train_flops_per_token

    return train_flops_per_token(model, seq, param_count)


def _emit_error(error: str) -> int:
    """One parseable JSON line, never a bare traceback, and always a
    failing exit code: a run that measured nothing is a failed run,
    whatever stopped it. Metric/unit come from ``_ACTIVE`` so failures
    land on the series that was running."""
    print(json.dumps({
        "metric": _ACTIVE[0],
        "value": None,
        "unit": _ACTIVE[1],
        "error": error,
    }))
    return 1


def estimate_bench(model: str, seq: int, per_chip_batch: int,
                   target_chips: int) -> int:
    """Roofline projection for models too big to measure on one chip
    (VERDICT r2 item 8 / SURVEY §6 north star: llama3_8b FSDP on
    v5e-64). Compiles the REAL sharded train step (8-device virtual
    CPU mesh, FSDP rules, abstract inputs — no weights materialized)
    as a does-it-compile + memory check, and reports the MFU = 1 bound
    ``bf16 peak / analytic flops_per_token``. It reports only the
    bound: a realistic line needs a measured MFU of today's code on
    the chip, and until a benchmark supplies one there is none to
    transfer.

    Why the bound is ANALYTIC flops rather than raw cost-analysis
    output: XLA's HLO cost analysis counts a ``lax.scan`` body ONCE
    regardless of trip count (the layer stack), undercounting flops
    ~n_layers-fold, and its bytes-accessed ignores fusion. The compile
    is still load-bearing: it validates that the sharded step program for the
    target model actually compiles on the FSDP mesh, and its XLA
    memory analysis is reported as an HBM-fit diagnostic.

    Labeled assumptions (also emitted in the JSON):
    - per-device program ≈ the v5e-64 one at equal per-chip batch
      (FSDP all-gather/reduce-scatter volumes are shard-count-
      invariant; ICI latency differences ignored);
    - v5e peak 197 bf16 TFLOP/s; roofline = peak / flops_per_token is
      the MFU=1 UPPER BOUND;
    - CPU-backend compile: einsum attention stands in for the Pallas
      kernel, so the memory diagnostic OVERSTATES activation temps at
      long seq (the S^2 score tensor never exists on the TPU path).
    """
    from polyaxon_tpu.utils import cpu_mesh_xla_flags

    cpu_mesh_xla_flags(8)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import numpy as np

    from polyaxon_tpu.models import get_model
    from polyaxon_tpu.parallel.sharding import rules_for_mesh
    from polyaxon_tpu.runtime.config import RuntimeConfig
    from polyaxon_tpu.runtime.flops import PEAK_FLOPS, train_flops_per_token
    from polyaxon_tpu.runtime.optim import build_optimizer
    from polyaxon_tpu.runtime.step import build_init, build_train_step

    V5E_PEAK = PEAK_FLOPS["v5e"]
    V5E_HBM_GB = 16.0  # per chip

    def compile_check(model_name: str, seq_len: int, batch_per_chip: int):
        """Compile the real sharded step with abstract inputs (no
        weights materialized) → (param_count, memory diagnostic)."""
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:8]).reshape(1, 8), ("dp", "fsdp"))
        cfg = RuntimeConfig(model=model_name, steps=1, seq_len=seq_len)
        # remat must reach the MODEL config (the measured baseline runs
        # with dots remat; the memory diagnostic should describe the
        # same program).
        model_def = get_model(model_name, max_seq_len=seq_len,
                              remat="dots")
        rules = rules_for_mesh(mesh)
        optimizer = build_optimizer(cfg)
        with mesh:
            init_fn = build_init(model_def, optimizer, mesh, rules)
            train_step = build_train_step(model_def, optimizer, mesh, rules)
            rng_aval = jax.eval_shape(lambda: jax.random.key(0))
            state_aval = jax.eval_shape(init_fn, rng_aval)
            batch_aval = {"tokens": jax.ShapeDtypeStruct(
                (batch_per_chip * 8, seq_len), jnp.int32)}
            compiled = jax.jit(train_step).lower(
                state_aval, batch_aval, rng_aval).compile()
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(state_aval["params"]))
        mem = {}
        try:
            ma = compiled.memory_analysis()
            if isinstance(ma, (list, tuple)):
                ma = ma[0]
            # memory_analysis describes the per-device SPMD executable.
            mem = {
                "state_gb_per_chip": round(
                    ma.argument_size_in_bytes / 2**30, 2),
                "temp_gb_per_chip": round(
                    ma.temp_size_in_bytes / 2**30, 2),
            }
        except Exception:
            pass
        return n_params, mem

    n_params, mem = compile_check(model, seq, per_chip_batch)
    flops_tok = train_flops_per_token(model, seq, n_params)
    if not flops_tok:
        return _emit_error(f"no flops derivation for {model}")
    roof = V5E_PEAK / flops_tok  # tokens/sec/chip at MFU=1
    print(json.dumps({
        "metric": f"estimate_tokens_per_sec_per_chip[{model},seq{seq},"
                  f"v5e-{target_chips},fsdp]",
        "value": round(roof, 2),
        "unit": "tokens/sec/chip",
        "kind": "roofline_upper_bound_mfu1",
        "roofline_upper_bound_mfu1": round(roof, 2),
        "measured_mfu": "not measured",
        "params": n_params,
        "flops_per_token": flops_tok,
        "sharded_step_compiles": True,
        "memory_diagnostic": {
            **mem,
            "hbm_gb_per_chip": V5E_HBM_GB,
            "caveat": "cpu compile; einsum attention inflates temps "
                      "(the TPU flash path never builds S^2 scores)",
        },
        "assumptions": {
            "per_chip_batch": per_chip_batch,
            "target": f"v5e-{target_chips} fsdp",
            "peak_bf16_tflops": V5E_PEAK / 1e12,
            "flops_model": "6N(active) + causal attention term "
                           "(runtime/flops.py)",
            "cost_analysis_not_used": "XLA HLO cost analysis counts "
                                      "lax.scan bodies once and "
                                      "ignores fusion for bytes",
        },
    }))
    return 0


def tuner_bench(smoke: bool = False) -> int:
    """Polytune trials/hour: a Hyperband LR sweep whose trials are real
    JAXJobs driven by the embedded plane + agent (the BASELINE "trials/
    hour on preemptible slices" metric, measured on this host's chip)."""
    import tempfile
    import time

    _require_tpu_unless(smoke)
    from polyaxon_tpu.agent import Agent
    from polyaxon_tpu.controlplane import ControlPlane
    from polyaxon_tpu.lifecycle import V1Statuses

    steps_base = 2 if smoke else 10
    sweep = {
        "kind": "operation",
        "name": "bench-sweep",
        "matrix": {
            "kind": "hyperband",
            "maxIterations": 4,
            "eta": 2,
            "resource": {"name": "steps", "type": "int"},
            "metric": {"name": "loss", "optimization": "minimize"},
            "resume": False,
            "seed": 11,
            "params": {"lr": {"kind": "loguniform",
                               "value": {"low": -9.2, "high": -2.3}}},
        },
        "component": {
            "inputs": [
                {"name": "lr", "type": "float"},
                {"name": "steps", "type": "int", "value": steps_base,
                 "isOptional": True},
            ],
            "run": {
                "kind": "jaxjob",
                "runtime": {
                    "model": "llama_tiny", "dataset": "lm_synthetic",
                    "steps": "{{ params.steps }}",
                    "seq_len": 64 if smoke else 512,
                    "global_batch_size": 8,
                    "learning_rate": "{{ params.lr }}",
                    "log_every": 10**9,
                },
            },
        },
    }
    with tempfile.TemporaryDirectory() as home:
        plane = ControlPlane(home)
        agent = Agent(plane, max_concurrent=1, in_process=True)
        record = plane.submit(sweep)
        t0 = time.perf_counter()
        status = agent.run_until_done(record.uuid, timeout=3600)
        wall = time.perf_counter() - t0
        trials = plane.list_runs(pipeline_uuid=record.uuid)
        done = [t for t in trials if t.status == V1Statuses.SUCCEEDED]
    trials_per_hour = len(done) / wall * 3600 if wall > 0 else 0.0

    print(json.dumps({
        "metric": "polytune_hyperband_trials_per_hour[llama_tiny]",
        "value": round(trials_per_hour, 1),
        "unit": "trials/hour",
        **_device_fields(),
    }))
    return 0 if status == V1Statuses.SUCCEEDED else 1


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true", help="tiny fast run (CI)")
    parser.add_argument("--model", default="llama_200m")
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--seq", type=int, default=None)
    parser.add_argument("--attention", default="auto",
                        choices=["auto", "xla", "flash"],
                        help="attention impl; auto = Pallas flash on real "
                             "TPU (self-falls-back), einsum elsewhere")
    parser.add_argument("--remat", default=None,
                        choices=["none", "dots", "full"],
                        help="checkpoint policy (default: dots, none on --smoke)")
    parser.add_argument("--block-q", default=None,
                        help="flash fwd q-tile size, or 'auto' "
                             "(VMEM-budget auto-pick; sweepable)")
    parser.add_argument("--block-k", default=None,
                        help="flash fwd k-tile size, or 'auto' (sweepable)")
    parser.add_argument("--bwd", default=None, choices=["pallas", "xla"],
                        help="flash backward impl (default: pallas on TPU)")
    parser.add_argument("--loss-chunk", type=int, default=None,
                        help="chunked lm-head loss slab length (sweepable)")
    parser.add_argument("--profile", action="store_true",
                        help="capture a jax.profiler trace of one "
                             "mid-run step into profiles/<config>/ "
                             "(the per-point trace VERDICT r3 #2 asks "
                             "for; adds one traced step of overhead)")
    parser.add_argument("--tuner", action="store_true",
                        help="measure Polytune throughput instead: a "
                             "Hyperband LR sweep of JAXJob trials, "
                             "reported as trials/hour (BASELINE metric 2)")
    parser.add_argument("--estimate", metavar="MODEL", default=None,
                        help="no measurement: compiled-HLO roofline "
                             "projection of tokens/sec/chip for MODEL "
                             "(e.g. llama3_8b) on a v5e-64 FSDP mesh, "
                             "calibrated by the measured baseline when "
                             "one exists")
    parser.add_argument("--estimate-chips", type=int, default=64,
                        help="target slice size for --estimate")
    args = parser.parse_args()

    if args.estimate:
        _ACTIVE[:] = [f"estimate_tokens_per_sec_per_chip[{args.estimate}]",
                      "tokens/sec/chip"]
        return estimate_bench(args.estimate, args.seq or 8192,
                              args.batch or 8, args.estimate_chips)

    if args.tuner:
        _ACTIVE[:] = ["polytune_hyperband_trials_per_hour", "trials/hour"]

    flash_flags = [f for f, v in (("--block-q", args.block_q),
                                  ("--block-k", args.block_k),
                                  ("--bwd", args.bwd)) if v is not None]
    sweep_flags = flash_flags + (["--loss-chunk"]
                                 if args.loss_chunk is not None else [])
    if sweep_flags and args.tuner:
        parser.error(f"{'/'.join(sweep_flags)} have no effect in --tuner "
                     "mode")
    if flash_flags and args.attention != "flash":
        # 'auto' resolves to einsum off-TPU and would silently drop the
        # knobs — a sweep must pin the impl it is sweeping.
        parser.error(f"{'/'.join(flash_flags)} require --attention flash "
                     f"(got {args.attention!r})")

    # Resolve the workload shape and validate sweep points BEFORE the
    # backend initializes: a bad flag should fail instantly.
    if args.smoke:
        model, steps, batch, seq = "llama_tiny", 8, 2, 64
    else:
        model = args.model
        steps = args.steps or 30
        batch = args.batch or 8
        seq = args.seq or 2048

    # A sweep point whose tiles can't actually run in the flash kernel
    # (pick_block reduces them, or <128 triggers the einsum fallback)
    # would silently measure something else — refuse it instead.
    from polyaxon_tpu.ops.flash import pick_block

    # Validate AND normalize in one pass: ints land back on args as
    # ints (they flow into the runtime spec), "auto" rides through to
    # the kernel's trace-time auto-pick.
    for attr, flag in (("block_q", "--block-q"), ("block_k", "--block-k")):
        value = getattr(args, attr)
        if value is None or value == "auto":
            continue
        try:
            value = int(value)
        except ValueError:
            parser.error(f"{flag} must be an integer or 'auto', "
                         f"got {value!r}")
        effective = pick_block(seq, value)
        if value < 128 or effective != value:
            parser.error(
                f"{flag} {value} cannot tile seq {seq} in the flash "
                f"kernel (effective block {effective}, minimum 128): "
                "this sweep point would fall back to einsum attention")
        setattr(args, attr, value)
    if args.loss_chunk is not None:
        effective = pick_block(seq, args.loss_chunk)
        if args.loss_chunk < 1 or effective != args.loss_chunk:
            parser.error(
                f"--loss-chunk {args.loss_chunk} does not divide seq "
                f"{seq} (the loss would silently run chunk "
                f"{max(effective, 1)}): pick a power-of-two divisor")

    if args.tuner:
        return tuner_bench(smoke=args.smoke)

    import jax

    from polyaxon_tpu.polyflow import V1JAXJob
    from polyaxon_tpu.runtime import run_jaxjob

    def _noop_metrics(step, vals):
        # A callback (even discarded) engages the loop's emission path;
        # with log_every=1e9 that is the first step's window and ONE
        # more at the final step, so the registry's training-step
        # histogram gets the run-mean sample without mid-run sync
        # points perturbing the measurement. The snapshot rides out in
        # metrics_registry.
        pass

    _require_tpu_unless(args.smoke)
    n_chips = jax.device_count()
    spec = {
        "kind": "jaxjob",
        "mesh": {"axes": {"dp": 1, "fsdp": -1}} if n_chips > 1 else {"axes": {"dp": 1}},
        "runtime": {
            "model": model,
            "dataset": "lm_synthetic",
            "steps": steps,
            "optimizer": "adamw",
            "learning_rate": 3e-4,
            "global_batch_size": batch * n_chips,
            "seq_len": seq,
            "log_every": 10**9,
            "remat": args.remat or ("none" if args.smoke else "dots"),
            "attention_impl": args.attention,
            **({"flash_block_q": args.block_q}
               if args.block_q is not None else {}),
            **({"flash_block_k": args.block_k}
               if args.block_k is not None else {}),
            **({"flash_bwd_impl": args.bwd} if args.bwd else {}),
            **({"loss_chunk": args.loss_chunk}
               if args.loss_chunk is not None else {}),
        },
    }
    profile_dir = None
    if args.profile:
        # Trace one late step (warmed-up, compiled); the trace lands in
        # <profile_dir>/profile as a perfetto/tensorboard-loadable dump.
        # Tag carries EVERY lever that distinguishes sweep points —
        # the tile/chunk/remat variants are exactly the points the
        # per-point traces exist to compare.
        tag = f"{model}-seq{seq}-b{batch}" + "".join(
            f"-{part}" for part in (
                args.attention if args.attention != "auto" else None,
                spec["runtime"]["remat"],
                f"q{args.block_q}" if args.block_q else None,
                f"k{args.block_k}" if args.block_k else None,
                f"bwd{args.bwd}" if args.bwd else None,
                f"chunk{args.loss_chunk}" if args.loss_chunk else None,
            ) if part)
        profile_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "profiles", tag)
        os.makedirs(profile_dir, exist_ok=True)
        spec["runtime"]["profile_steps"] = [max(steps - 2, 1)]
        print(f"# profiler trace -> {profile_dir}/profile", file=sys.stderr)
    # The run always gets an artifacts dir (a throwaway when not
    # profiling) so the runtime loop emits lifecycle spans; obs.analyze
    # folds them into the per-record perf report below — a sweep
    # regression arrives pre-attributed (compile vs input-wait vs step)
    # instead of as a bare tokens/sec delta.
    trace_dir = profile_dir
    trace_dir_tmp = False
    if trace_dir is None:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="plx-bench-trace-")
        trace_dir_tmp = True
    # A kernel that fails to compile fails the run: no retry on the XLA
    # backward, no degraded number under the headline metric's name.
    result = run_jaxjob(V1JAXJob.from_dict(spec), artifacts_dir=trace_dir,
                        on_metrics=_noop_metrics)
    tokens_per_sec_per_chip = result.throughput / max(n_chips, 1)

    flops_tok = _flops_per_token(model, seq, result.param_count)
    achieved = tokens_per_sec_per_chip * flops_tok if flops_tok else None
    from polyaxon_tpu.runtime.flops import peak_flops

    peak = peak_flops(jax.devices()[0])  # None off-TPU; unknown TPU raises
    print(json.dumps({
        "metric": f"jaxjob_train_tokens_per_sec_per_chip[{model},seq{seq}]",
        "value": round(tokens_per_sec_per_chip, 2),
        "unit": "tokens/sec/chip",
        **_device_fields(),
        "flops_per_token": flops_tok,
        "tflops_per_sec_per_chip": round(achieved / 1e12, 2) if achieved else None,
        "mfu": round(achieved / peak, 4) if achieved and peak else None,
        # Input-pipeline attribution: host ms/step blocked on data and
        # the compile wall, so a reader can tell an input-bound
        # regression from a device one and see persistent-compile-cache
        # hits.
        "input_wait_ms": round(result.input_wait_ms, 3),
        "compile_time_s": round(result.compile_time_s, 3),
        # Which Mosaic kernels the compiled step holds (empty = every
        # attention ran a reference path).
        "step_kernels": result.step_kernels,
        # Collective-overlap measurement of this config's train step
        # (ISSUE 12): every non-smoke multi-chip record carries the
        # hidden fraction of its collective time, so a sweep point's
        # tokens/sec regression can be attributed to de-overlapped
        # collectives without a separate audit run.
        "overlap_snapshot": _overlap_snapshot(
            model, seq, batch, n_chips, args.smoke),
        # Unified-registry snapshot (obs.metrics): the run's training-
        # step histogram and any store/retry counters ride into every
        # bench record, so perf_sweep points carry their own latency
        # distributions instead of a single mean.
        "metrics_registry": _registry_snapshot(),
        # Phase attribution from the run's own lifecycle spans
        # (obs.analyze): where the wall went + step-trend verdict.
        "perf_report": _perf_report(trace_dir, cleanup=trace_dir_tmp),
    }))
    return 0


def _device_fields() -> dict:
    """The device every result line names (never a CPU number under a
    device metric's name without saying so)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_chips": len(devices)}


def _require_tpu_unless(smoke: bool) -> None:
    """A measurement path that finds no chip fails. This initializes
    the backend in THIS process — the one that then trains; nothing is
    probed from a child (a child that took the chip would leave the
    parent without it)."""
    import jax

    platform = jax.devices()[0].platform
    if not smoke and platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax's default backend is `{platform}` — bench.py "
            "measures on the chip only (use --smoke for the CPU "
            "control-flow gate)")


def _overlap_snapshot(model, seq, batch, n_chips, smoke):
    """Overlap measurement of THIS bench config's train-step program:
    a compile-only re-lower through perf.audit on the live devices,
    censused and window-measured from the compiled HLO. Skipped where
    it can't mean anything (smoke's correctness-gate config; a single
    chip has no collectives to hide); any failure degrades to an error
    dict — the bench JSON contract outranks the snapshot."""
    if smoke:
        return {"skipped": "smoke run"}
    if n_chips < 2:
        return {"skipped": "single chip: no collectives"}
    try:
        from polyaxon_tpu.perf import audit as perf_audit

        point = perf_audit.AuditPoint(
            "bench-fsdp", {"dp": 1, "fsdp": n_chips}, model=model,
            seq_len=seq, global_batch=batch * n_chips)
        rep = perf_audit.audit_point(point)
        return {"axes": rep["axes"],
                "overlap_ratio": rep["overlap_ratio"],
                "overlap": rep["overlap"],
                "counts": rep["counts"],
                "backend": rep["backend"],
                "compile_s": rep["compile_s"]}
    except Exception as exc:  # noqa: BLE001 — degrade, don't erase
        return {"error": f"{type(exc).__name__}: {exc}"[:300]}


def _registry_snapshot():
    try:
        from polyaxon_tpu.obs import metrics as obs_metrics

        return obs_metrics.REGISTRY.snapshot()
    except Exception:  # noqa: BLE001 — the JSON contract outranks obs
        return None


def _perf_report(trace_dir, cleanup=False):
    try:
        from polyaxon_tpu.obs import analyze as obs_analyze

        report = obs_analyze.compact_report(
            obs_analyze.analyze_run_dir(trace_dir))
    except Exception:  # noqa: BLE001 — the JSON contract outranks obs
        report = None
    if cleanup:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    return report


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as exc:  # noqa: BLE001 — the contract is one JSON line
        import traceback

        traceback.print_exc()  # full detail to stderr; stdout stays parseable
        sys.exit(_emit_error(f"{type(exc).__name__}: {exc}"[:300]))
