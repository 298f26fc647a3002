"""90th percentile of first-token time from the due time, by the client's
clock. It is set by the few bursts that find every slot taken, so it
swings from run to run (313-544 ms over 18 chip runs): per-layer, with
`front.ttft_p50_ms`, until a first-token metric is steady enough to be
end-to-end (PERF.md, Open questions)."""
from harness import window


def read(ctx):
    if ctx["kind"] != "serve" or ctx["traffic"]["kind"] != "open":
        return None
    times = window.first_token_ms(ctx["records"], ctx["t_open"],
                                  ctx["t_close"], ctx["t_end"])
    return window.percentile(times, 90.0) if times else None
