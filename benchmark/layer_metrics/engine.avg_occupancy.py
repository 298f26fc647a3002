"""Share of slots live per decode step inside the window, from the
engine's `decode_steps` and `avg_occupancy` counters (/v1/stats) read at
the window's two edges."""


def read(ctx):
    if ctx["kind"] != "serve" or "open" not in ctx["stats"]:
        return None          # the edges are read in traced runs only
    a, b = ctx["stats"]["open"], ctx["stats"]["close"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0 or a["avg_occupancy"] is None:
        return None
    live = (b["avg_occupancy"] * b["decode_steps"]
            - a["avg_occupancy"] * a["decode_steps"])
    return 100.0 * live / steps
