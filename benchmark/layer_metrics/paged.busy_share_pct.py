"""`paged_decode` kernel time over the device's busy time in the trace."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"],
                                                   r"^paged_decode")
    if not calls:
        return None
    return 100.0 * seconds / ctx["busy"]["busy_s"]
