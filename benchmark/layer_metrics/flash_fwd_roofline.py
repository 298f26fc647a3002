"""Least time the chip needs for one causal `flash_fwd` call at the
cell's shapes (compute-bound: kernels/flash_fwd.py) over the mean time
a call took in the trace. A chip's call holds its share of the batch."""
from harness import spec, trace_reduce


def read(ctx):
    if ctx["kind"] != "train" or ctx.get("trace") is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"], r"^flash_fwd")
    if not calls:
        return None
    config = ctx["config"]
    kernel = spec.load_kernel("flash_fwd")
    least = kernel.least_seconds(
        ctx["peaks"], ctx["batch_per_chip"], ctx["seq_len"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"])
    return 100.0 * least * calls / seconds
