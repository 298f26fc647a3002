#!/usr/bin/env python
"""Profiler-trace analysis: name the top time sinks of a captured step.

The evidence step of a profiled run, scripted so chip minutes go to
measuring, not spelunking: given a trace directory (a
``--profile`` sweep point's ``profiles/<tag>/`` or any run's
``<artifacts>/profile``), this finds the newest ``*.xplane.pb``,
converts it with the in-env xprof tooling, and prints

- a category rollup (matmul/convolution self-time share = the ceiling
  on MFU this program can reach no matter how fast the MXU runs), and
- the top-N ops by self time with their measured GFLOP/s and memory
  bandwidth — the non-matmul sink VERDICT asks to be named is the
  first non-matmul row.

Ends with ONE JSON line (machine-readable, perf_sweep-attachable).

Caveat: XLA:CPU traces carry no per-op device stats (hlo_stats comes
back empty and framework_op_stats holds a lone host IDLE row — checked
2026-08-01), so off-chip runs only validate the plumbing; the analysis
itself is for real-TPU captures.

Usage: python scripts/analyze_trace.py <trace-dir> [--top 15]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def find_xplane(root: str) -> str:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    if not hits:
        raise SystemExit(f"no *.xplane.pb under {root!r} — pass a "
                         "profiles/<tag>/ dir or a run's artifacts/profile")
    return hits[-1]


def _gviz_rows(table: dict) -> list[dict]:
    cols = [c["id"] for c in table.get("cols", [])]
    rows = []
    for row in table.get("rows", []):
        vals = [cell.get("v") if isinstance(cell, dict) else cell
                for cell in row.get("c", [])]
        rows.append(dict(zip(cols, vals)))
    return rows


def load_op_stats(xplane: str) -> tuple[list[dict], str]:
    """(rows, tool) — hlo_stats (per-HLO, the TPU view) with a
    framework_op_stats fallback: CPU traces leave hlo_stats empty, and
    the framework table keeps the analyzer testable off-chip (same
    self-time/occurrence columns, coarser op identity)."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data([xplane], "hlo_stats", {})
    rows = _gviz_rows(json.loads(
        data if isinstance(data, str) else data.decode()))
    if rows:
        return rows, "hlo_stats"
    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xplane], "framework_op_stats", {})
    parsed = json.loads(data if isinstance(data, str) else data.decode())
    tables = parsed if isinstance(parsed, list) else [parsed]
    rows = [r for t in tables for r in _gviz_rows(t)]
    # A device table that is pure IDLE carries no information (the CPU
    # backend's device plane) — the host table holds the real ops then.
    informative = [r for r in rows
                   if str(r.get("operation", "")).upper() != "IDLE"]
    rows = informative or rows
    for r in rows:  # map the framework columns onto the hlo names
        r.setdefault("category", r.get("type"))
        r.setdefault("hlo_op_name", r.get("operation"))
        r.setdefault("total_self_time", r.get("total_self_time")
                     or r.get("total_time"))
    return rows, "framework_op_stats"


MATMUL_CATEGORIES = {"convolution", "convolution fusion", "matmul",
                     "dot", "output fusion"}
# TPU hlo_stats buckets MXU work mostly under "convolution"/"dot"/
# fused variants; everything else (loop fusion, copy, reduce,
# all-reduce, ...) is the non-matmul time MFU analysis hunts.


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("trace_dir")
    parser.add_argument("--top", type=int, default=15)
    args = parser.parse_args()

    xplane = find_xplane(args.trace_dir)
    rows, tool = load_op_stats(xplane)
    if not rows:
        print(json.dumps({"trace": xplane, "error": "no op stats"}))
        return 1

    def f(row, key):
        try:
            return float(row.get(key) or 0.0)
        except (TypeError, ValueError):
            return 0.0

    total_self = sum(f(r, "total_self_time") for r in rows) or 1.0
    by_cat: dict[str, float] = {}
    for r in rows:
        cat = (r.get("category") or "?").lower()
        by_cat[cat] = by_cat.get(cat, 0.0) + f(r, "total_self_time")
    cat_table = sorted(by_cat.items(), key=lambda kv: -kv[1])
    matmul_pct = 100.0 * sum(
        t for c, t in by_cat.items() if c in MATMUL_CATEGORIES) / total_self

    print(f"# trace: {xplane}")
    print(f"# total self time: {total_self / 1e3:.2f} ms across "
          f"{len(rows)} ops ({tool})")
    print(f"\n== category rollup (matmul-ish share = {matmul_pct:.1f}% — "
          "the MFU ceiling of this program)")
    for cat, t in cat_table:
        print(f"  {100.0 * t / total_self:5.1f}%  {t / 1e3:8.2f} ms  {cat}")

    ranked = sorted(rows, key=lambda r: -f(r, "total_self_time"))
    print(f"\n== top {args.top} ops by self time")
    print(f"  {'self%':>6} {'ms':>8} {'GFLOP/s':>9} {'GiB/s':>7} "
          f"{'category':<18} op")
    for r in ranked[: args.top]:
        cat = (r.get("category") or "?").lower()
        pct = 100.0 * f(r, "total_self_time") / total_self
        name = str(r.get("hlo_op_name") or "?")[:60]
        print(f"  {pct:6.1f} {f(r, 'total_self_time') / 1e3:8.2f} "
              f"{f(r, 'model_flop_rate'):9.1f} "
              f"{f(r, 'measured_memory_bw'):7.1f} {cat:<18} {name}")
    # The headline answer walks the FULL ranking, not the display
    # slice — a matmul-dominated top-N must not report null while a
    # real non-matmul sink sits just below the cutoff.
    top_non_matmul = None
    for r in ranked:
        cat = (r.get("category") or "?").lower()
        if cat not in MATMUL_CATEGORIES:
            top_non_matmul = {
                "op": str(r.get("hlo_op_name") or "?")[:60],
                "category": cat,
                "self_pct": round(
                    100.0 * f(r, "total_self_time") / total_self, 2),
            }
            break

    print()
    print(json.dumps({
        "trace": xplane,
        "tool": tool,
        "total_self_ms": round(total_self / 1e3, 2),
        "matmul_self_pct": round(matmul_pct, 2),
        "top_non_matmul": top_non_matmul,
        "categories": {c: round(100.0 * t / total_self, 2)
                       for c, t in cat_table},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
