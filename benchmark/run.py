#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax: a chip belongs to one process at a time.
It finds the cell's files by the names in BENCHMARK.json, starts the
program's phase (serving or training: the process that holds the chips,
loads, warms up, measures and exits), then the reference's phase (the
plain float32 comparison, in a process of its own so that the program's
peak memory stays the program's), and prints one JSON object as its last
line. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

Everything a run writes goes under ``.benchmark_out/`` and
``.jax-compile-cache/`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import common, spec  # noqa: E402

TRACE_SECONDS = 6.0


def run_phase(script: str, plan_path: str, env: dict, log_name: str,
              out_dir: str, timeout: float) -> int:
    """A child to its end; its own lines are echoed (each names the
    device), its errors kept in a log."""
    log_path = os.path.join(out_dir, log_name)
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", script), plan_path],
            cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=log,
            start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, 9)
            proc.wait()
            return 124
    for line in out.decode(errors="replace").splitlines():
        if line.startswith("{"):
            print(line, flush=True)
    if proc.returncode != 0:
        with open(log_path, errors="replace") as fh:
            sys.stderr.write(fh.read()[-4000:])
    return proc.returncode


def decide(cell, failed: int, program: dict, reference: dict) -> tuple[bool, list]:
    """`correct`, and every number compared beside its limit."""
    lines, ok = [], True

    def check(name, value, limit, kind="at most"):
        nonlocal ok
        passed = value is not None and (
            value <= limit if kind == "at most" else value >= limit)
        ok = ok and passed
        lines.append({"compared": name, "value": value, "limit": limit,
                      "rule": kind, "ok": passed})

    check("compiles_in_window", program["compiles_in_window"], 0)
    limits = cell.config["check"][program["kind"]]
    if "error" in reference:
        ok = False
        lines.append({"compared": "reference", "error": reference["error"]})
    else:
        for name, limit in limits.items():
            check(name, reference["numbers"].get(name), limit)
    if program["kind"] == "serve":
        after = program["stats"]["after"]
        check("kv_invariant_violations", after["kv_invariant_violations"], 0)
        check("step_failures", after["step_failures"], 0)
        check("rejected", sum(after["rejected"].values()), 0)
        check("failed_requests", failed, 0)
    else:
        check("window_steps", len(program["window_steps"]), 1, "at least")
    return ok, lines


def make_plan(cell, *, seed: int, seconds: float, trace: bool,
              require_chip: bool = True, control: bool = False,
              keep_trace: bool = False, break_path: str | None = None,
              rate: float | None = None, root: str = ROOT) -> dict:
    """What a phase is handed: the cell's files, the run's arguments and
    an empty directory for what it writes. The configuration keeps its
    ``_path``: its family is looked for beside its own tree first."""
    out_dir = os.path.join(root, ".benchmark_out",
                           f"{cell.name}-{seed}-{int(trace)}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    return {
        "workload": cell.name, "chips": cell.chips, "seed": seed,
        "seconds": seconds, "trace": bool(trace),
        "trace_seconds": TRACE_SECONDS, "require_chip": require_chip,
        "config": cell.config,
        "traffic": ({**cell.traffic, "rate_per_s": rate} if rate
                    else cell.traffic),
        "out_dir": out_dir, "t_start": T_START,
        "per_layer": [m["name"] for m in cell.per_layer],
        "control": control, "keep_trace": keep_trace,
        "break_path": break_path,
    }


def run_cell(cell, *, trace: bool, require_chip: bool = True,
             root: str = ROOT, **plan_args) -> dict:
    """One run of one cell; the final object (and the lines before it).
    `plan_args`: the seed, the seconds and the rest of `make_plan`."""
    plan = make_plan(cell, trace=trace, require_chip=require_chip, root=root,
                     **plan_args)
    out_dir = plan["out_dir"]
    plan_path = os.path.join(out_dir, "plan.json")
    common.write_json(plan_path, plan)
    env = common.child_env(ROOT)

    script = "serve_phase.py" if cell.kind == "serve" else "train_phase.py"
    rc = run_phase(script, plan_path, env, "program.log", out_dir, 1100)
    if rc != 0:
        common.fail(f"the program's phase ended with code {rc}", code=rc or 1)
    rc = run_phase("reference_phase.py", plan_path, env, "reference.log",
                   out_dir, 1100)
    if rc != 0:
        common.fail(f"the reference's phase ended with code {rc}",
                    code=rc or 1)
    with open(os.path.join(out_dir, "program.json")) as fh:
        program = json.load(fh)
    with open(os.path.join(out_dir, "reference.json")) as fh:
        reference = json.load(fh)

    device = program["device"]
    if program["kind"] == "serve":
        counted = [r for r in program["records"] if r["phase"] == "window"
                   and r["due"] < program["t_close"]]
        attempted = len(counted)
        failed = sum(1 for r in counted if r["error"]
                     and r["error"] != "abandoned after the window")
    else:
        attempted = len(program["steps"])
        failed = 0
    correct, lines = decide(cell, failed, program, reference)
    for line in lines:
        common.say(line, device)
    common.say({"note": "details", "out_dir": os.path.relpath(out_dir, root),
                "compiles_total": program["compiles_total"],
                "reference_seconds": reference.get("seconds"),
                "reference": reference.get("numbers"),
                "control": reference.get("control"),
                "tokens_compared": reference.get("tokens_compared"),
                "window_work": program.get("window_work"),
                "end_to_end": program["end_to_end"],
                "per_layer": program["per_layer"]}, device)

    names = cell.per_layer if trace else cell.end_to_end
    values = program["per_layer"] if trace else program["end_to_end"]
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in names if m["name"] in values}
    final = {"correct": bool(correct), "attempted": attempted,
             "failed": failed, "metrics": metrics,
             "device": {"platform": device["platform"],
                        "kind": device["kind"], "count": device["count"],
                        "memory_peak_bytes": device["memory_peak_bytes"]}}
    if trace and "busy" in program:
        final["device"].update(program["busy"])
        final["breakdown"] = program["breakdown"]
    elif trace and require_chip:
        common.fail("the traced run holds no device operation")
    return final


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    # Not the driver's: the builder's own studies of the check.
    ap.add_argument("--control", action="store_true",
                    help="also compute the lower-precision control")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="open loops: offer this rate (the knee sweep)")
    args = ap.parse_args()
    try:
        cell = spec.Cell(args.workload)
    except (spec.SpecError, OSError, KeyError) as exc:
        common.fail(str(exc), code=2)
    final = run_cell(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), control=args.control,
                     keep_trace=args.keep_trace,
                     rate=args.rate)
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
