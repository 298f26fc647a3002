"""Least time the chip needs for the `window_decode` calls of the traced
window (memory-bound: kernels/window_decode.py, a live row's pages
within its window and nothing behind it) over the time they took. Live
lengths come from the client's records, sampled at every decode
program's start in the trace, as `paged_decode_roofline` takes them;
one call a window layer a step."""
from harness import layers, spec, trace_reduce


def read(ctx):
    config = ctx["config"]
    if (ctx["kind"] != "serve" or ctx.get("trace") is None
            or not config.get("sliding_window_size")):
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"],
                                                   r"^window_decode")
    steps = trace_reduce.modules_running(ctx["trace"], r"^window_decode")
    if not calls or not steps:
        return None
    kernel = spec.load_kernel("window_decode")
    serve = config["serve"]
    # Trace time -> wall clock: the traced span's wall-clock start.
    offset = ctx["trace_wall_t0"] - ctx["busy"]["t0"]
    least = 0.0
    for ev in steps:
        live = layers.live_lengths_at(ctx["records"], ev["start"] + offset)
        least += kernel.least_seconds(
            ctx["peaks"], live, serve["page_size"],
            config["num_key_value_heads"], config["num_attention_heads"],
            config["head_dim"], config["sliding_window_size"])
    per_step = calls / len(steps)          # one call a window layer
    return 100.0 * least * per_step / seconds
