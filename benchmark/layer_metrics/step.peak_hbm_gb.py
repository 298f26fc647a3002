"""Peak bytes in use on the fullest chip after the window (memory_stats)."""


def read(ctx):
    if ctx["kind"] != "train" or not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 1e9
