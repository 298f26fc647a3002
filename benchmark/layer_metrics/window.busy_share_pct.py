"""`window_decode` kernel time (the window layers' decode attention,
``ops/paged_attention.py`` under a window) over the device's busy time
in the trace. A program without window layers runs no operation of that
name, and there is nothing to read."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"],
                                                   r"^window_decode")
    if not calls:
        return None
    return 100.0 * seconds / ctx["busy"]["busy_s"]
