"""`grouped_matmul` kernel time (the prefill's grouped expert matmuls,
``ops/grouped_matmul.py``) over the device's busy time in the trace.

The counter that says the kernel engaged: a program whose sorted
dispatch still goes to the compiler's ``ragged-dot`` runs no operation
of that name, and there is nothing to read. A share of time and not of
a roofline: the bytes a call reads are those of the experts that hold a
pair, which the trace does not say."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"],
                                                   r"^grouped_matmul")
    if not calls:
        return None
    return 100.0 * seconds / ctx["busy"]["busy_s"]
