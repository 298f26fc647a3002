"""`PagePool.admit` per admission inside the window (radix match, page
allocation, CoW plan): `tick_phase_ns["admit.match"]` over
`admissions_total`, `/v1/stats` at the window's two edges."""
from harness import phase_idle


def read(ctx):
    return phase_idle.admit_match_us(ctx)
