"""Least time the chip needs for the decode steps' gated-delta state
updates in the traced window (memory-bound: kernels/gdn_update.py, every
live row's matrix state read once and written once a layer a step) over
the time they took.

The update's operations are `gdn.busy_share_pct`'s state operations (a
result or an operand of the state's type ``f32[.., Hv, dk, dv]``) that
ran inside a decode program, whether the compiler made the update a
fusion or a kernel; a program that copies the whole pool of states, or
passes over the state more often than once each way, takes longer and
reads lower. Live rows come from the client's records, sampled at every
decode program's start; one update a delta layer a step."""
import bisect

from harness import layers, spec, trace_reduce


def update_ops(events: list, steps: list, config: dict) -> list:
    """The state operations of `events` that ran inside one of the
    decode programs `steps` (module events)."""
    state, _ = spec.load_reader("gdn.busy_share_pct").shapes(config)
    spans = sorted((ev["start"], ev["start"] + ev["dur"]) for ev in steps)
    starts = [a for a, _ in spans]

    def in_a_step(ev):
        i = bisect.bisect_right(starts, ev["start"]) - 1
        return i >= 0 and ev["start"] < spans[i][1]

    return [ev for ev in events
            if state.search(ev["name"]) and in_a_step(ev)]


def delta_layers(config: dict) -> int:
    layers_ = config["num_hidden_layers"]
    return layers_ - layers_ // config["full_attention_interval"]


def read(ctx):
    config = ctx["config"]
    if (ctx["kind"] != "serve" or ctx.get("trace") is None
            or not config.get("linear_num_value_heads")):
        return None
    plane = trace_reduce.device_planes(ctx["trace"])[0]
    steps = trace_reduce.module_events(ctx["trace"], r"^jit_decode_step")
    mine = update_ops(trace_reduce.leaf_ops(plane), steps, config)
    seconds = sum(ev["dur"] for ev in mine)
    if not steps or not seconds:
        return None
    kernel = spec.load_kernel("gdn_update")
    # Trace time -> wall clock: the traced span's wall-clock start.
    offset = ctx["trace_wall_t0"] - ctx["busy"]["t0"]
    least = sum(
        kernel.least_seconds(
            ctx["peaks"],
            len(layers.live_lengths_at(ctx["records"], ev["start"] + offset)),
            config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])
        for ev in steps)
    return 100.0 * least * delta_layers(config) / seconds
