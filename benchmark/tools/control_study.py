#!/usr/bin/env python3
"""What a training cell's check reads, seed by seed, at the cell's own
size and in one process (one set-up for a dozen seeds): the job's first
steps through the harness's own path (harness/train_phase.py, a window
of a second), the float32 reference beside them, and for the first
``--control`` seeds the lower-precision control (the reference with int8
matmul inputs, reference/plain.py) held against the same reference. The
limits in a configuration's ``check`` were set from these lines
(PERF.md, section 2); the benchmark's runs never run this. Needs the
chips the cell needs.

    python3 benchmark/tools/control_study.py \
        --workload mistral7b_train_seq4k --seeds 11 12 13 14 --control 3
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--dry", default=None, metavar="DIR",
                    help="rehearse off the chip: a directory with a "
                         "BENCHMARK.json of dry cells (tests/fixtures/dry)")
    args = ap.parse_args()

    import run
    from harness import common, reference_phase, spec, train_phase

    os.environ.update({k: v for k, v in common.child_env(run.ROOT).items()
                       if k.startswith("JAX_")})
    cell = (spec.Cell(args.workload, spec.load_benchmark(args.dry), args.dry)
            if args.dry else spec.Cell(args.workload))
    ref = reference_phase.load_reference(cell.config)
    for i, seed in enumerate(args.seeds):
        plan = run.make_plan(cell, seed=seed, seconds=1.0, trace=False,
                             require_chip=not args.dry,
                             control=i < args.control)
        try:
            program = train_phase.run(plan)
            gc.collect()              # the job's state off the chip first
            out = reference_phase.train_numbers(plan, program, ref)
        except Exception as exc:  # noqa: BLE001 — a study: the next seed
            print(json.dumps({"seed": seed, "error": repr(exc)[:400]}),
                  flush=True)
            continue
        print(json.dumps({
            "seed": seed, "numbers": out["numbers"],
            "control": out.get("control"),
            "leaf_norms": out["leaf_norms"],
            "reference_leaf_norms": {
                "grad0": out["reference"]["grad0_leaf"],
                "update": out["reference"]["update_leaf"]},
            "control_leaf_norms": out.get("control_leaf_norms"),
            "program": out["program"],
            "reference": out["reference"]["steps"],
            "memory_peak_bytes": program["device"]["memory_peak_bytes"],
        }), flush=True)
        del program, out
        gc.collect()


if __name__ == "__main__":
    main()
