"""Device idle per decode step under `engine:sweep`, `engine:step.emit`
and `engine:observe` (cancellations, the per-slot emit and retire loop,
the gauges' own walk), traced window."""
from harness import phase_idle


def read(ctx):
    return phase_idle.idle_ms_per_step(ctx, "bookkeeping")
