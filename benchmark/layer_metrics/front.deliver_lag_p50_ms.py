"""Median time from the engine's readback of a token to the end of its
streaming handler's write (ISSUE 35): `/v1/stats` `deliver_lag_hist` at
the window's two edges. One reading a write that brought a request
level with its output, taken by the handler and merged into the engine's
counts every 32. Nothing to read on a program without the histogram."""
from harness import loghist


def read(ctx):
    return loghist.window_quantile(ctx, "deliver_lag_hist", "counts", 0.5)
