"""Host time of the window space's roll per decode step inside the
window (`WindowedPagePool.roll` over the live rows: a page taken and the
oldest handed back at a page boundary): `tick_phase_ns["step.window"]`
over `decode_steps`, `/v1/stats` at the window's two edges. An engine
whose pool has no window space has no such phase, and there is nothing
to read."""
from harness import phase_idle

PHASE = "step.window"


def read(ctx):
    edges = phase_idle.counter_edges(ctx)
    if edges is None:
        return None
    a, b = edges
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0 or PHASE not in b["tick_phase_ns"]:
        return None
    return 1e-3 * (b["tick_phase_ns"][PHASE]
                   - a["tick_phase_ns"].get(PHASE, 0)) / steps
