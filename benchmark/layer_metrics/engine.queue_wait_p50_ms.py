"""Median of the `queue_wait` span of the requests due in the window
(the program's request span tree, GET /requests/{id}/timeline)."""
from harness import window


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    waits = [spans["queue_wait"] for spans in ctx["timelines"].values()
             if spans.get("queue_wait") is not None]
    return window.percentile(waits, 50.0) if waits else None
