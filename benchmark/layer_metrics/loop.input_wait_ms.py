"""Mean of the loop's own `input_wait_ms` over the window's steps."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    waits = [s["vals"]["input_wait_ms"] for s in ctx["window_steps"]
             if "input_wait_ms" in s["vals"]]
    return sum(waits) / len(waits) if waits else None
