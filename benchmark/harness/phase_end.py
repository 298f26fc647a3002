"""What both phases do once the window has closed: reduce the trace,
run the cell's per-layer readers, and hand the parent one file."""

from __future__ import annotations

import os

from harness import common, layers, spec, trace_reduce, window


def end_to_end(result: dict, plan: dict) -> dict:
    """The cell's end-to-end metrics, by the benchmark's own clock."""
    t0, t1 = result["t_open"], result["t_close"]
    out = {}
    if result["kind"] == "train":
        steps = result["window_steps"]
        if steps:
            span = steps[-1]["t"] - t0
            out["train_tok_s_chip"] = (len(steps) * result["tokens_per_step"]
                                       / span / result["chips"])
    else:
        records = result["records"]
        out["out_tok_s"] = window.out_tok_s(records, t0, t1)
        value, n = window.tpot_ms(records, t0, t1)
        if value is not None:
            out["tpot_p50_ms"] = value
        result["tpot_requests"] = n
        admitted = [r for r in records if r["token_times"]
                    and t0 <= r["token_times"][0] < t1]
        grid = [t0 + 0.5 * i for i in range(int((t1 - t0) / 0.5))]
        live = [sum(layers.live_lengths_at(records, t)) for t in grid]
        all_gaps = sorted(g for r in records
                          for g in window.request_gaps(r, t0, t1))
        med = all_gaps[len(all_gaps) // 2] if all_gaps else 0.0
        stats = result.get("stats") or {}
        steps = ((stats.get("close") or {}).get("decode_steps", 0)
                 - (stats.get("open") or {}).get("decode_steps", 0))
        result["window_work"] = {
            "decode_steps": steps,
            "gap_median_ms": 1e3 * med,
            "gap_max_ms": 1e3 * (all_gaps[-1] if all_gaps else 0.0),
            "gaps_over_2x_median": sum(1 for g in all_gaps if g > 2 * med),
            "excess_over_median_s": sum(g - med for g in all_gaps
                                        if g > 2 * med) / 16.0,
            "admissions": len(admitted),
            "admitted_prompt_tokens": sum(r["prompt_len"] for r in admitted),
            "mean_live_tokens": sum(live) / max(len(live), 1)}
    out["setup_s"] = t0 - plan["t_start"]
    return out


def finish(plan: dict, result: dict) -> None:
    device = result["device"]
    result["t_end"] = max([result["t_close"]] + [
        t for r in result.get("records", []) for t in r["token_times"][-1:]])
    result["end_to_end"] = end_to_end(result, plan)
    ctx = {**result, "config": plan["config"], "traffic": plan["traffic"],
           "peaks": None, "trace": None,
           "memory_peak_bytes": device["memory_peak_bytes"]}
    if result["kind"] == "train":
        ctx["tok_s_chip"] = result["end_to_end"].get("train_tok_s_chip", 0.0)
    if plan["require_chip"] or device["platform"] == "tpu":
        ctx["peaks"] = spec.load_peaks(device["kind"])
    if result.get("trace_dir"):
        trace = trace_reduce.load(result["trace_dir"])
        if plan.get("keep_trace"):
            common.write_json(os.path.join(plan["out_dir"], "trace.json"),
                              trace)
        if trace_reduce.device_planes(trace):
            ctx["trace"] = trace
            ctx["busy"] = trace_reduce.busy(trace)
            # The trace's clock has its own origin. The profiler was started
            # at trace_span[0] by the wall clock with the device busy, so
            # the first traced operation is taken to start there.
            ctx["trace_wall_t0"] = result["trace_span"][0]
            result["busy"] = {k: ctx["busy"][k]
                              for k in ("busy_s", "window_s")}
            result["breakdown"] = trace_reduce.breakdown(trace)
        else:
            result["trace_note"] = sorted(p["name"] for p in trace["planes"])
    # Off the chip (the tests' rehearsal) the readers that need the trace
    # or the peaks find nothing to read: a device number is never made
    # from a CPU run.
    result["per_layer"] = layers.read_all(plan["per_layer"], ctx)
    result.pop("trace", None)
    common.write_json(os.path.join(plan["out_dir"], "program.json"), result)
