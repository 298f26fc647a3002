"""Device idle per decode step lying under the engine's `engine:dry`
spans: the loop's wait with nothing live, queued or asked (ISSUE 35).
The arithmetic of `phase_idle.idle_by_group` for one more span. The span
lies outside every tick, so `engine.idle_unnamed_ms` counts this idle
too: unnamed less dry is the coverage check. Nothing to read on a
program that does not count its dry waits (whose trace has no such
span), or without a trace."""
from harness import phase_idle, trace_reduce

DRY_SPAN = "engine:dry"


def read(ctx):
    edges = phase_idle.counter_edges(ctx)
    trace = ctx.get("trace")
    if edges is None or "dry_ns" not in edges[0] or trace is None:
        return None
    spans = phase_idle.engine_spans(trace)
    t0, t1 = trace_reduce.traced_window(trace)
    steps = sum(1 for start, _, name in spans
                if name == phase_idle.STEP_SPAN and t0 <= start <= t1)
    if not steps:
        return None
    idle = trace_reduce.gaps(
        trace_reduce.op_intervals(trace_reduce.device_planes(trace)[0]),
        t0, t1)
    dry = [(a, b) for a, b, name in spans if name == DRY_SPAN]
    # |idle and dry| = |idle| + |dry| - |idle or dry|
    under = (trace_reduce.union_seconds(idle) + trace_reduce.union_seconds(dry)
             - trace_reduce.union_seconds(idle + dry))
    return 1e3 * under / steps
