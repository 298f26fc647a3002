"""Median first-token time from the due time, by the client's clock.
Per-layer, not end-to-end: it sits between lumps of the distribution
(admitted in the next tick or the one after) and read 251 and 270 ms in
two runs of one seed."""
from harness import window


def read(ctx):
    if ctx["kind"] != "serve" or ctx["traffic"]["kind"] != "open":
        return None
    times = window.first_token_ms(ctx["records"], ctx["t_open"],
                                  ctx["t_close"], ctx["t_end"])
    return window.percentile(times, 50.0) if times else None
