"""99th percentile of the length of the engine's ticks inside the window
(ISSUE 35): `/v1/stats` `tick_ms_hist["all"]` at the window's two edges,
the rank's place interpolated inside its bucket. To be held beside
`front.itl_p99_ms`: a token's gap is a tick's length unless something
else stretches it. Nothing to read on a program without the histogram."""
from harness import loghist


def read(ctx):
    return loghist.window_quantile(ctx, "tick_ms_hist", "all", 0.99)
