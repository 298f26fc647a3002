"""Drive a whole run of a dry cell on the CPU: everything `run.py` does
except the look for a chip."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRY = os.path.join(BENCH, "tests", "fixtures", "dry")
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import spec  # noqa: E402


def dry_cell(workload: str):
    return spec.Cell(workload, spec.load_benchmark(DRY), DRY)


def rehearse(workload: str, seed: int, seconds: float, trace: bool = False,
             break_path: str | None = None, control: bool = False) -> dict:
    cell = dry_cell(workload)
    final = run.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                         require_chip=False, break_path=break_path,
                         control=control)
    out_dir = os.path.join(run.ROOT, ".benchmark_out",
                           f"{cell.name}-{seed}-{int(trace)}")
    return {"final": final, "out_dir": out_dir}
