"""Least time the chip needs for the `mla_decode` calls of the traced
window (kernels/mla_decode.py: a live row's latents at the published
576 values a token, whatever the pool pads them to) over the time they
took. Live lengths come from the client's records, sampled at every
decode program's start in the trace, as `window_decode_roofline` takes
them; one call a layer a step."""
from harness import layers, spec, trace_reduce


def read(ctx):
    config = ctx["config"]
    if (ctx["kind"] != "serve" or ctx.get("trace") is None
            or not config.get("kv_lora_rank")):
        return None
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"],
                                                   r"^mla_decode")
    steps = trace_reduce.modules_running(ctx["trace"], r"^mla_decode")
    if not calls or not steps:
        return None
    kernel = spec.load_kernel("mla_decode")
    # Trace time -> wall clock: the traced span's wall-clock start.
    offset = ctx["trace_wall_t0"] - ctx["busy"]["t0"]
    least = 0.0
    for ev in steps:
        live = layers.live_lengths_at(ctx["records"], ev["start"] + offset)
        least += kernel.least_seconds(
            ctx["peaks"], live, config["num_attention_heads"],
            config["kv_lora_rank"] + config["qk_rope_head_dim"],
            config["kv_lora_rank"])
    per_step = calls / len(steps)          # one call a layer
    return 100.0 * least * per_step / seconds
