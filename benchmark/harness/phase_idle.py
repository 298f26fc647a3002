"""The engine's own phases against the device trace and the counters.

The serving engine (``serving/batching.py _PhaseClock``) wraps every part
of its loop in ``jax.profiler.TraceAnnotation("engine:<phase>")`` and adds
the same phases' host time to ``/v1/stats`` ``tick_phase_ns``. The spans
land on a host plane of the profile on the device operations' clock, so
each idle stretch of the device (``trace_reduce.gaps`` over the first
device's ``XLA Ops``) can be laid under the phase the host was in. Six
groups; what lies under none of the first five is ``unnamed``, so the six
sum to the device's idle time, which ``device.serve_idle_pct`` reads from
outside. Per decode step: the ``engine:step.dispatch`` spans that start
in the traced window are the steps.

A program without the spans or the counters (before ISSUE 24) gives
every reader here nothing to read.
"""

from __future__ import annotations

from harness import trace_reduce

GROUPS = {
    "admit": ("engine:admit",),        # covers its `engine:admit.*` children
    "keys": ("engine:step.keys",),
    "launch": ("engine:step.upload", "engine:step.dispatch"),
    "readback": ("engine:step.readback",),
    "bookkeeping": ("engine:sweep", "engine:step.emit", "engine:observe"),
}
STEP_SPAN = "engine:step.dispatch"
WAITS_FOR_DEVICE = "step.readback"


def engine_spans(trace: dict) -> list[tuple[float, float, str]]:
    return [(ev["start"], ev["start"] + ev["dur"], ev["name"])
            for plane in trace_reduce.host_planes(trace)
            for line in plane["lines"] for ev in line["events"]
            if ev["name"].startswith("engine:")]


def idle_by_group(trace: dict) -> dict | None:
    """{"steps", "idle_s", "by_group": {group: idle seconds under its
    spans, "unnamed": the rest}}; None where the trace holds no device
    operation, no `engine:` span or no step."""
    spans = engine_spans(trace)
    if not spans or not trace_reduce.device_planes(trace):
        return None
    t0, t1 = trace_reduce.traced_window(trace)
    steps = sum(1 for start, _, name in spans
                if name == STEP_SPAN and t0 <= start <= t1)
    if not steps:
        return None
    idle = trace_reduce.gaps(
        trace_reduce.op_intervals(trace_reduce.device_planes(trace)[0]),
        t0, t1)
    idle_s = trace_reduce.union_seconds(idle)
    by_group = {}
    for group, names in GROUPS.items():
        mine = [(a, b) for a, b, name in spans if name in names]
        # |idle and mine| = |idle| + |mine| - |idle or mine|
        by_group[group] = (idle_s + trace_reduce.union_seconds(mine)
                           - trace_reduce.union_seconds(idle + mine))
    by_group["unnamed"] = idle_s - sum(by_group.values())
    return {"steps": steps, "idle_s": idle_s, "by_group": by_group}


def idle_ms_per_step(ctx: dict, group: str):
    """What an `engine.idle_<group>_ms` reader returns."""
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    if "idle_by_group" not in ctx:      # six readers, one reduction
        ctx["idle_by_group"] = idle_by_group(ctx["trace"])
    found = ctx["idle_by_group"]
    if found is None:
        return None
    return 1e3 * found["by_group"][group] / found["steps"]


def counter_edges(ctx: dict):
    """(`/v1/stats` at the window's open, at its close), where both were
    read (traced runs) and the engine counts its phases."""
    if ctx["kind"] != "serve" or "open" not in ctx["stats"]:
        return None
    a, b = ctx["stats"]["open"], ctx["stats"]["close"]
    if "tick_phase_ns" not in a or "tick_phase_ns" not in b:
        return None
    return a, b


def host_ms_per_step(ctx: dict):
    """Host time of the engine loop per decode step inside the window:
    every leaf phase but the one that waits for the device."""
    edges = counter_edges(ctx)
    if edges is None:
        return None
    a, b = edges
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    spent = sum(ns - a["tick_phase_ns"].get(name, 0)
                for name, ns in b["tick_phase_ns"].items()
                if name != WAITS_FOR_DEVICE)
    return 1e-6 * spent / steps


def admit_match_us(ctx: dict):
    """`PagePool.admit` (radix match, page allocation, CoW plan) per
    admission inside the window."""
    edges = counter_edges(ctx)
    if edges is None:
        return None
    a, b = edges
    admitted = b["admissions_total"] - a["admissions_total"]
    if admitted <= 0:
        return None
    return 1e-3 * (b["tick_phase_ns"]["admit.match"]
                   - a["tick_phase_ns"]["admit.match"]) / admitted
