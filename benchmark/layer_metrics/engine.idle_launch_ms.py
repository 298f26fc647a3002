"""Device idle per decode step under `engine:step.upload` and
`engine:step.dispatch` (the six host arrays, the call), traced window."""
from harness import phase_idle


def read(ctx):
    return phase_idle.idle_ms_per_step(ctx, "launch")
