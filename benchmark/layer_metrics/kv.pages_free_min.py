"""Lowest `kv_pages_free` (/v1/stats) sampled once a second in the window."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    seen = [s["kv_pages_free"] for s in ctx["samples"]
            if ctx["t_open"] <= s["t"] <= ctx["t_close"]
            and s["kv_pages_free"] is not None]
    return min(seen) if seen else None
