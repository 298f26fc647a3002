"""How late the generator sent requests due in the window (its own clock)."""
from harness import window


def read(ctx):
    if ctx["kind"] != "serve" or ctx["traffic"]["kind"] != "open":
        return None
    late = window.late_ms(ctx["records"], ctx["t_open"], ctx["t_close"])
    return window.percentile(late, 99.0) if late else None
