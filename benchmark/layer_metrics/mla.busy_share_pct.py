"""`mla_decode` kernel time (latent attention's decode,
``ops/mla_decode.py``) over the device's busy time in the trace. A
program without latent attention runs no operation of that name, and
there is nothing to read."""
from harness import trace_reduce


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"],
                                                   r"^mla_decode")
    if not calls:
        return None
    return 100.0 * seconds / ctx["busy"]["busy_s"]
