"""Of the prompt tokens the radix tree matched inside the window, those
a suffix program computed again: `prefill_tokens_recomputed` over
`prefill_tokens_matched` (/v1/stats at the window's two edges). Under a
window the suffix program starts below its match, by ``sliding_window``
positions a window layer (``serving/paged.py window_suffix_start``), so
that the window layers' K and V behind the match come out exact; a pool
without a window space recomputes nothing. A program without the two
counters, or a window in which nothing matched, gives nothing to
read."""


def read(ctx):
    if ctx["kind"] != "serve" or "open" not in ctx["stats"]:
        return None          # the edges are read in traced runs only
    a, b = ctx["stats"]["open"], ctx["stats"]["close"]
    if "prefill_tokens_matched" not in a or "prefill_tokens_matched" not in b:
        return None
    matched = b["prefill_tokens_matched"] - a["prefill_tokens_matched"]
    if matched <= 0:
        return None
    return 100.0 * (b["prefill_tokens_recomputed"]
                    - a["prefill_tokens_recomputed"]) / matched
