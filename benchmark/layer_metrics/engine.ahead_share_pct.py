"""Share of the window's decode steps that were launched while the step
before was still unread (the engine runs one step ahead of the host,
ISSUE 34): `decode_steps_ahead` over `decode_steps`, `/v1/stats` at the
window's two edges. Nothing to read on a program without the counter."""
from harness import phase_idle


def read(ctx):
    edges = phase_idle.counter_edges(ctx)
    if edges is None:
        return None
    a, b = edges
    if "decode_steps_ahead" not in a or "decode_steps_ahead" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    return 100.0 * (b["decode_steps_ahead"] - a["decode_steps_ahead"]) / steps
