"""1 - (union of device operations) / traced window, training cells."""


def read(ctx):
    if ctx["kind"] != "train" or ctx.get("trace") is None:
        return None
    return 100.0 * (1.0 - ctx["busy"]["busy_s"] / ctx["busy"]["window_s"])
