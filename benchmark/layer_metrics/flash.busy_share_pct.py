"""`flash_fwd` + `flash_bwd_dkdv` + `flash_bwd_dq` kernel time over the
device's busy time in the trace."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "train" or ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        ctx["trace"], r"^flash_(fwd|bwd_dkdv|bwd_dq)")
    if not calls:
        return None
    return 100.0 * seconds / ctx["busy"]["busy_s"]
