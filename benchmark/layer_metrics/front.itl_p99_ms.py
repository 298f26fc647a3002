"""99th percentile of single gaps between streamed tokens in the window."""
from harness import window


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    value, _ = window.itl_ms(ctx["records"], ctx["t_open"], ctx["t_close"])
    return value
