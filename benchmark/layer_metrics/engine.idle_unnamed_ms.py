"""Device idle per decode step under none of the five groups of `engine:`
spans: the coverage check of the other `engine.idle_*` readers (a tick
cut by the trace's edges, the lines between phases)."""
from harness import phase_idle


def read(ctx):
    return phase_idle.idle_ms_per_step(ctx, "unnamed")
