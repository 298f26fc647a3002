"""99th percentile of the time from the engine's readback of a token to
the end of its streaming handler's write (ISSUE 35): as
`front.deliver_lag_p50_ms`, the same counts."""
from harness import loghist


def read(ctx):
    return loghist.window_quantile(ctx, "deliver_lag_hist", "counts", 0.99)
