"""What the engine thread spent off the processor per decode step inside
the window (ISSUE 35): wall time less the thread's own CPU time, summed
over every leaf phase but `step.readback` (which waits for the device).
The engine reads its CPU clock on one tick in eight (`/v1/stats`
`ticks_sampled`) and keeps those ticks' wall and CPU time by leaf
(`tick_phase_sampled_ns`, `tick_phase_cpu_ns`): their difference a
sampled tick, times the window's ticks a step. It is the wait for the
interpreter lock plus whatever call blocked (the upload's transfer).
Nothing to read on a program without the CPU counter."""
from harness import phase_idle


def read(ctx):
    edges = phase_idle.counter_edges(ctx)
    if edges is None:
        return None
    a, b = edges
    if "tick_phase_cpu_ns" not in a or "tick_phase_cpu_ns" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    sampled = b["ticks_sampled"] - a["ticks_sampled"]
    if steps <= 0 or sampled <= 0:
        return None
    waited = sum(
        (ns - a["tick_phase_sampled_ns"][name])
        - (b["tick_phase_cpu_ns"][name] - a["tick_phase_cpu_ns"][name])
        for name, ns in b["tick_phase_sampled_ns"].items()
        if name != phase_idle.WAITS_FOR_DEVICE)
    ticks = b["ticks_total"] - a["ticks_total"]
    return 1e-6 * waited / sampled * ticks / steps
