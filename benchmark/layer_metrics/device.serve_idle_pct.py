"""1 - (union of device operations) / traced window, serving cells."""


def read(ctx):
    if ctx["kind"] != "serve" or ctx.get("trace") is None:
        return None
    return 100.0 * (1.0 - ctx["busy"]["busy_s"] / ctx["busy"]["window_s"])
