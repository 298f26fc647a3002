"""Device time of the suffix programs behind a match under a window
(`jit_run.suffix`: ``serving/batching.py _windowed_suffix_prefill``
names its program so, and `model.prefill_share_pct`'s ``jit_run`` finds
it with the whole-prompt programs) over the device's busy time in the
trace. A program that names no module so gives nothing to read."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    events = trace_reduce.module_events(ctx["trace"], r"^jit_run\.suffix\b")
    if not events:
        return None
    return 100.0 * sum(ev["dur"] for ev in events) / ctx["busy"]["busy_s"]
