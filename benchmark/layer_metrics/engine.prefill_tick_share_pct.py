"""Share of the window's ticks that ran a prefill program (ISSUE 35):
`/v1/stats` `tick_ms_hist["with_prefill"]` over `["all"]`, both at the
window's two edges. Nothing to read on a program without the
histogram."""
from harness import loghist, phase_idle


def read(ctx):
    edges = phase_idle.counter_edges(ctx)
    if edges is None:
        return None
    ticks = loghist.window_counts(*edges, "tick_ms_hist", "all")
    admitting = loghist.window_counts(*edges, "tick_ms_hist", "with_prefill")
    if ticks is None or admitting is None or sum(ticks["counts"]) <= 0:
        return None
    return 100.0 * sum(admitting["counts"]) / sum(ticks["counts"])
