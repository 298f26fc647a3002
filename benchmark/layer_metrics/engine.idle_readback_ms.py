"""Device idle per decode step under `engine:step.readback`: the host is
blocked in `np.asarray` and the device has nothing running (launch
latency before the decode program, copy-out and wake-up after it)."""
from harness import phase_idle


def read(ctx):
    return phase_idle.idle_ms_per_step(ctx, "readback")
