#!/usr/bin/env python
"""Real-chip perf sweep: runs the VERDICT-r1 item-3 lever matrix through
bench.py and reports a ranked table (tokens/sec/chip + MFU).

Levers: per-device batch (8 vs 16), remat policy (dots vs none),
attention (flash vs xla), flash fwd tile sizes, and backward impl
(pallas kernels vs chunked-XLA recompute). Each point is an isolated
bench.py subprocess so an OOM or compile failure poisons nothing.

Usage: python scripts/perf_sweep.py [--steps N] [--quick]
Writes perf_sweep_results.json at the repo root (an output: not tracked).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_args(**kw) -> list[str]:
    args = []
    for flag, key in (("--batch", "batch"), ("--seq", "seq"),
                      ("--steps", "steps"), ("--remat", "remat"),
                      ("--attention", "attention"), ("--block-q", "block_q"),
                      ("--block-k", "block_k"), ("--bwd", "bwd"),
                      ("--loss-chunk", "loss_chunk"), ("--model", "model")):
        if kw.get(key) is not None:
            args += [flag, str(kw[key])]
    if kw.get("profile"):
        # One jax.profiler trace of a late step per point
        # (VERDICT r3 #2); dumps land under profiles/<config>/.
        args += ["--profile"]
    return args


def run_point(name: str, timeout_s: float = 1200, **kw):
    cmd = [sys.executable, os.path.join(REPO, "bench.py")] + bench_args(**kw)
    t0 = time.time()
    # This parent never imports jax in sweep mode: each point's bench.py
    # child is the one process that holds the chip while it runs.
    # Popen + SIGTERM-then-SIGKILL, not subprocess.run(timeout=...):
    # run() SIGKILLs on timeout; SIGTERM lets the PJRT client release
    # the chip before the next point asks for it.
    with subprocess.Popen(cmd, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True,
                          cwd=REPO) as popen:
        try:
            stdout, stderr = popen.communicate(timeout=timeout_s)
            proc = subprocess.CompletedProcess(cmd, popen.returncode,
                                               stdout, stderr)
        except subprocess.TimeoutExpired:
            popen.terminate()
            try:
                popen.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                popen.kill()
                popen.communicate()
            return {"name": name, "error": f"timeout>{timeout_s:.0f}s", **kw}
    line = _last_json_line(proc.stdout)
    if line is None:
        tail = " | ".join(proc.stderr.strip().splitlines()[-3:])[-300:]
        return {"name": name, "error": f"rc={proc.returncode}: {tail}", **kw}
    out = {"name": name, "wall_s": round(time.time() - t0, 1), **kw, **line}
    # Per-point metrics-registry snapshot (ISSUE 5): bench.py emits the
    # unified registry (training-step histogram, store-op latency,
    # retry counters) in its JSON line; normalize the key so every
    # sweep point in perf_sweep_results.json carries one — None for
    # error points and pre-registry bench binaries.
    out.setdefault("metrics_registry", None)
    # Per-point phase attribution (ISSUE 6): bench.py analyzes its own
    # run's lifecycle spans (obs.analyze) into a compact perf report —
    # normalize the key so every sweep point carries one (None for
    # error points and pre-report bench binaries), and a regression
    # between rounds names the phase that moved, not just the number.
    out.setdefault("perf_report", None)
    # OOM shows up as an error field from bench's catch-all.
    if kw.get("profile") and "error" not in out:
        out.update(_analyze_profile(proc.stderr))
    return out


def _last_json_line(stdout: str):
    """Last parseable JSON object on stdout, or None — the one-JSON-line
    output contract shared by bench.py and analyze_trace.py."""
    for ln in reversed(stdout.strip().splitlines()):
        try:
            parsed = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            return parsed
    return None


def _analyze_profile(bench_stderr: str) -> dict:
    """Run scripts/analyze_trace.py on the trace the bench just wrote
    (it announces '# profiler trace -> <dir>/profile' on stderr) and
    attach the summary — so every profiled chip point carries its own
    matmul-ceiling/top-sink analysis in perf_sweep_results.json instead
    of needing a manual per-point analyzer pass.
    Analysis failure never fails the measurement (the number stands on
    its own; the note says what went wrong)."""
    marker = "# profiler trace -> "
    trace_dir = None
    for ln in bench_stderr.splitlines():
        if ln.startswith(marker):
            trace_dir = ln[len(marker):].strip()
    if not trace_dir:
        return {"profile_analysis": {"error": "no trace dir announced"}}
    try:
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "analyze_trace.py"), trace_dir],
            capture_output=True, text=True, timeout=600, cwd=REPO)
    except (subprocess.TimeoutExpired, OSError) as exc:
        # The measurement stands on its own — a slow/broken analyzer
        # must never cost a completed chip number or the rest of the
        # sweep (the docstring's promise, enforced).
        return {"profile_analysis": {
            "error": f"analyzer failed: {type(exc).__name__}"}}
    summary = _last_json_line(proc.stdout)
    if summary is not None:
        summary.pop("categories", None)  # keep the record compact
        return {"profile_analysis": summary}
    tail = " | ".join(proc.stderr.strip().splitlines()[-2:])[-200:]
    return {"profile_analysis": {
        "error": f"analyzer rc={proc.returncode}: {tail}"}}


def moe_dispatch_sweep(platform: str, steps: int) -> int:
    """Dense one-hot vs ragged all_to_all MoE dispatch, measured
    (VERDICT r2 item 3): train-step wall time at E ∈ {8,16,32} on a
    dp2×ep4 mesh (8-device virtual CPU mesh by default; single-chip
    ep=1 on TPU still measures the einsum-elimination term, which
    dominates as E grows). Writes moe_dispatch_results.json."""
    sys.path.insert(0, REPO)
    if platform == "cpu":
        from polyaxon_tpu.utils import cpu_mesh_xla_flags

        cpu_mesh_xla_flags(8)
    import dataclasses

    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.models import moe
    from polyaxon_tpu.parallel.sharding import rules_for_mesh, tree_shardings

    devices = jax.devices()
    if len(devices) >= 8:
        mesh = jax.sharding.Mesh(np.array(devices[:8]).reshape(2, 4),
                                 ("dp", "ep"))
    else:
        mesh = jax.sharding.Mesh(np.array(devices[:1]).reshape(1, 1),
                                 ("dp", "ep"))
    results = []
    for n_experts in (8, 16, 32):
        cfg0 = dataclasses.replace(
            moe.CONFIGS["moe_tiny"], dim=256, ffn_dim=512, n_layers=2,
            n_heads=8, n_kv_heads=4, n_experts=n_experts,
            experts_per_token=2, capacity_factor=1.25, vocab_size=1024,
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16)
        variables = moe.init(cfg0, jax.random.key(0))
        shardings = tree_shardings(moe.logical_axes(cfg0)["params"], mesh,
                                   rules_for_mesh(mesh))
        params = jax.device_put(variables["params"], shardings)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 128),
                                              0, cfg0.vocab_size)}
        row = {"n_experts": n_experts}
        for dispatch in ("dense", "ragged"):
            cfg = dataclasses.replace(cfg0, dispatch=dispatch)

            def loss_fn(p, b, cfg=cfg):
                return moe.apply(cfg, {"params": p, "state": {}}, b)[0]

            with mesh:
                step = jax.jit(jax.grad(loss_fn))
                g = step(params, batch)  # compile + warm
                jax.block_until_ready(g)
                times = []
                for _ in range(steps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(step(params, batch))
                    times.append(time.perf_counter() - t0)
            row[dispatch + "_ms"] = round(
                sorted(times)[len(times) // 2] * 1e3, 2)
        row["ragged_speedup"] = round(row["dense_ms"] / row["ragged_ms"], 3)
        results.append(row)
        print(f"E={n_experts}: dense {row['dense_ms']}ms, "
              f"ragged {row['ragged_ms']}ms, "
              f"speedup {row['ragged_speedup']}x", flush=True)

    out_path = os.path.join(REPO, "moe_dispatch_results.json")
    with open(out_path, "w") as fh:
        json.dump({"platform": jax.devices()[0].platform,
                   "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
                   "grid": "dim256 ffn512 L2 seq128 batch8 K2 cf1.25",
                   "results": results}, fh, indent=2)
    print(f"wrote {out_path}")
    return 0


def run_audit_artifacts():
    """The communication-audit companion artifacts for a sweep round
    (ISSUE 4): the CPU-mesh collective census per schedule, the AOT
    topology-only TPU evidence, and the overlap audit (ISSUE 12). Each
    runs as its own subprocess with a bounded budget — a hung audit
    costs its timeout, not the sweep. Returns the ingested overlap
    summary (or None) so the sweep record carries per-schedule
    overlap_ratio alongside the throughput points."""
    for name, cmd, budget_s in (
        ("collective audit (CPU mesh)",
         [sys.executable, "-m", "polyaxon_tpu.perf",
          "--json", os.path.join(REPO, "collective_audit.json")], 900),
        ("AOT topology audit (TPU, no device)",
         [sys.executable, "-m", "polyaxon_tpu.perf", "--aot-probe",
          "--aot-train-step", "ulysses-cp,ring-cp"], 1500),
        ("overlap audit (latency-hiding scheduler)",
         [sys.executable, "-m", "polyaxon_tpu.perf", "--audit",
          "--json", os.path.join(REPO, "overlap_audit.json")], 900),
    ):
        print(f"→ {name} ...", flush=True)
        try:
            proc = subprocess.run(cmd, cwd=REPO, timeout=budget_s,
                                  capture_output=True, text=True)
            tail = (proc.stdout or proc.stderr).strip().splitlines()
            print("  " + (tail[-1][:200] if tail else f"rc={proc.returncode}"),
                  flush=True)
        except (subprocess.TimeoutExpired, OSError) as exc:
            print(f"  audit step failed: {type(exc).__name__} "
                  f"(sweep continues)", flush=True)
    return _load_overlap_summary()


def _load_overlap_summary():
    """Structured ingestion of the overlap artifact the audit step just
    wrote — the `{"overlap_audit": {ok, topology, reports}}` contract of
    `python -m polyaxon_tpu.perf --audit --json <path>` — so the sweep
    record carries per-schedule overlap numbers without re-parsing the
    human-facing table text."""
    path = os.path.join(REPO, "overlap_audit.json")
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, ValueError):
        return None
    audit = payload.get("overlap_audit")
    if not isinstance(audit, dict):
        return None
    if not audit.get("ok"):
        # The probe found no workable TPU topology on this host: record
        # the skip (with the per-topology errors) instead of nothing,
        # so a sweep round without overlap numbers is distinguishable
        # from one where the audit was never requested.
        return {"ok": False, "topologies": audit.get("topologies", {})}
    reports = audit.get("reports", [])
    summary = {
        "ok": True,
        "topology": audit.get("topology"),
        "overlap_ratio": {r["name"]: r["overlap_ratio"] for r in reports},
        "async_by_kind": {r["name"]: r["overlap"].get("async_by_kind", {})
                          for r in reports},
    }
    for name, ratio in sorted(summary["overlap_ratio"].items()):
        print(f"  overlap[{name}] = {ratio:.4f}", flush=True)
    return summary


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--model", default="llama_200m")
    parser.add_argument("--seq", type=int, default=2048,
                        help="sequence length (shrink for CPU smokes: the "
                             "8-thread CPU mesh trips XLA's 40s collective "
                             "watchdog on large shapes)")
    parser.add_argument("--quick", action="store_true",
                        help="baseline + the 3 highest-value levers only")
    parser.add_argument("--moe", action="store_true",
                        help="run the MoE dense-vs-ragged dispatch sweep "
                             "instead of the llama lever matrix")
    parser.add_argument("--moe-platform", default="cpu",
                        choices=("cpu", "tpu"),
                        help="--moe backend: cpu = 8-device virtual mesh "
                             "(dp2xep4), tpu = the real chip (ep=1)")
    parser.add_argument("--profile", action="store_true",
                        help="capture a jax.profiler trace of one late "
                             "step per point (profiles/<config>/; "
                             "VERDICT r3 #2's per-point trace)")
    parser.add_argument("--resume", action="store_true",
                        help="rerun only the points that errored in the "
                             "existing perf_sweep_results.json, keeping "
                             "prior successes")
    parser.add_argument("--audit", action="store_true",
                        help="also emit the per-point HLO/collective "
                             "report artifacts: the CPU-mesh schedule "
                             "census (collective_audit.json) and the AOT "
                             "topology-only TPU evidence incl. train-step "
                             "collective reports + flash VMEM fits "
                             "(aot_probe_results.json), plus the overlap "
                             "audit (overlap_audit.json, ingested into "
                             "this sweep's record as per-schedule "
                             "overlap_ratio) — all run in isolated "
                             "subprocesses and never block the sweep "
                             "points")
    args = parser.parse_args()

    overlap_summary = run_audit_artifacts() if args.audit else None

    if args.moe:
        return moe_dispatch_sweep(args.moe_platform,
                                  steps=min(args.steps, 15))

    base = dict(model=args.model, steps=args.steps, seq=args.seq,
                profile=args.profile or None)
    points = [
        ("baseline-b8-dots-flash", dict(base, batch=8, remat="dots",
                                        attention="flash")),
        ("b16-dots-flash", dict(base, batch=16, remat="dots",
                                attention="flash")),
        ("b8-dots-flash-bwd-xla", dict(base, batch=8, remat="dots",
                                       attention="flash", bwd="xla")),
        ("b8-none-flash", dict(base, batch=8, remat="none",
                               attention="flash")),
    ]
    if not args.quick:
        points += [
            ("b16-none-flash", dict(base, batch=16, remat="none",
                                    attention="flash")),
            ("b8-dots-xla", dict(base, batch=8, remat="dots",
                                 attention="xla")),
            ("b8-dots-flash-q256k512", dict(base, batch=8, remat="dots",
                                            attention="flash",
                                            block_q=256, block_k=512)),
            ("b8-dots-flash-q512k256", dict(base, batch=8, remat="dots",
                                            attention="flash",
                                            block_q=512, block_k=256)),
            ("b8-dots-flash-q256k256", dict(base, batch=8, remat="dots",
                                            attention="flash",
                                            block_q=256, block_k=256)),
            # VERDICT r4 item 3 staged levers: VMEM-budget auto-pick
            # (currently resolves to 1024-tiles at these shapes) vs the
            # fixed 512 default, plus the explicit 1024-tile point so
            # the auto pick's benefit is attributable.
            ("b8-dots-flash-qkauto", dict(base, batch=8, remat="dots",
                                          attention="flash",
                                          block_q="auto", block_k="auto")),
            ("b8-dots-flash-q1024k1024", dict(base, batch=8, remat="dots",
                                              attention="flash",
                                              block_q=1024, block_k=1024)),
            ("b16-dots-flash-bwd-xla", dict(base, batch=16, remat="dots",
                                            attention="flash", bwd="xla")),
            ("b8-dots-flash-chunk512", dict(base, batch=8, remat="dots",
                                            attention="flash",
                                            loss_chunk=512)),
            ("b8-dots-flash-chunk128", dict(base, batch=8, remat="dots",
                                            attention="flash",
                                            loss_chunk=128)),
            # Bigger proxy: dim-2048 matmuls fill the MXU better than
            # the 200M's dim-1024; reconciles the --estimate projection
            # against a measured point one step closer to the 8B star.
            ("1b-b4-dots-flash", dict(base, model="llama3_1b",
                                      batch=4, remat="dots",
                                      attention="flash")),
            ("1b-b8-dots-flash", dict(base, model="llama3_1b",
                                      batch=8, remat="dots",
                                      attention="flash")),
            ("1b-b4-seq4096-dots-flash", dict(base, model="llama3_1b",
                                              batch=4, seq=4096,
                                              remat="dots",
                                              attention="flash")),
        ]

    out_path = os.path.join(REPO, "perf_sweep_results.json")
    prior: dict[str, dict] = {}
    if args.resume and os.path.exists(out_path):
        with open(out_path) as fh:
            prior = {r["name"]: r for r in json.load(fh).get("results", [])}

    def dump(results):
        # After every point, not just at the end: a Ctrl-C (or a hang
        # killed from outside) must not lose completed measurements —
        # --resume exists for exactly that situation.
        ok = [r for r in results if r.get("value")]
        ok.sort(key=lambda r: -r["value"])
        payload = {"results": results, "best": ok[0] if ok else None}
        if overlap_summary is not None:
            payload["overlap_audit"] = overlap_summary
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        return ok

    results = []
    for name, kw in points:
        kept = prior.get(name) if args.resume else None
        # Reuse only if the prior point measured the SAME config —
        # name alone would merge e.g. a --seq 512 smoke into a
        # seq-2048 table with no warning.
        if kept and kept.get("value") and all(
                kept.get(k) == v for k, v in kw.items()):
            results.append(kept)
            print(f"→ {name}: kept prior "
                  f"{kept['value']} tok/s/chip", flush=True)
            continue
        print(f"→ {name} ...", flush=True)
        res = run_point(name, **kw)
        results.append(res)
        dump(results)
        val = res.get("value")
        print(f"  {name}: "
              + (f"{val} tok/s/chip, mfu={res.get('mfu')}"
                 if val else f"ERROR {res.get('error')}"),
              flush=True)

    ok = dump(results)
    print(f"\nwrote {out_path}\n")
    print(f"{'config':<28} {'tok/s/chip':>12} {'mfu':>8}")
    for r in ok:
        print(f"{r['name']:<28} {r['value']:>12} "
              f"{r.get('mfu') if r.get('mfu') is not None else '-':>8}")
    for r in results:
        if not r.get("value"):
            print(f"{r['name']:<28} ERROR: {r.get('error')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
