"""Prompt tokens the radix cache served inside the window, over those
asked: `prefill_tokens_skipped` / `prefill_tokens_total` (/v1/stats)."""


def read(ctx):
    if ctx["kind"] != "serve" or "open" not in ctx["stats"]:
        return None          # the edges are read in traced runs only
    a, b = ctx["stats"]["open"], ctx["stats"]["close"]
    total = b["prefill_tokens_total"] - a["prefill_tokens_total"]
    if total <= 0:
        return None
    return 100.0 * (b["prefill_tokens_skipped"]
                    - a["prefill_tokens_skipped"]) / total
