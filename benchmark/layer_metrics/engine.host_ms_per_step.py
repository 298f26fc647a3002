"""Host time of the engine loop per decode step inside the window, from
`/v1/stats` `tick_phase_ns` at its two edges: every leaf phase except
`step.readback`, which waits for the device."""
from harness import phase_idle


def read(ctx):
    return phase_idle.host_ms_per_step(ctx)
