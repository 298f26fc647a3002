"""Mean device time of the decode program in the trace: the executed
program in whose span `paged_decode` runs (the engine compiles it from a
`functools.partial`, so the profiler knows it only as `jit__unknown`)."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    events = trace_reduce.modules_running(ctx["trace"], r"^paged_decode")
    if not events:
        return None
    return 1e3 * sum(ev["dur"] for ev in events) / len(events)
