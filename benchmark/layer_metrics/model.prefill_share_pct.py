"""Device time of the prefill programs (`jit_run`: whole-prompt and
suffix) over the device's busy time in the trace."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    events = trace_reduce.module_events(ctx["trace"], r"^jit_run\b")
    return 100.0 * sum(ev["dur"] for ev in events) / ctx["busy"]["busy_s"]
