"""Device idle per decode step under the engine's `engine:admit*` spans
(pick, radix match, CoW copy, the prefill program's dispatch), traced
window."""
from harness import phase_idle


def read(ctx):
    return phase_idle.idle_ms_per_step(ctx, "admit")
