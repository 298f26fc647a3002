"""Arithmetic on the engine's log-spaced histograms (ISSUE 35).

``/v1/stats`` carries ``tick_ms_hist`` and ``deliver_lag_hist``: counts
by bucket, bucket ``k`` holding ``[first_edge_ms x 2^(k / per_octave),
first_edge_ms x 2^((k + 1) / per_octave))`` (what lies under the first
edge is in bucket 0, what lies past the last in the last). The counts
are cumulative over the engine's life, so a window is the difference of
its two edges, and a quantile is read off the difference: the bucket
that holds the rank, and inside it the rank's place laid on the
bucket's own (logarithmic) scale.
"""

from __future__ import annotations

from harness import phase_idle


def window_counts(opened: dict, closed: dict, hist: str, key: str):
    """Counts of `hist`[`key`] between two `/v1/stats`, with the
    histogram's own description; None where either lacks it (a program
    before ISSUE 35)."""
    a, b = opened.get(hist), closed.get(hist)
    if not a or not b or key not in a or key not in b:
        return None
    return {"first_edge_ms": b["first_edge_ms"],
            "per_octave": b["per_octave"],
            "counts": [after - before
                       for before, after in zip(a[key], b[key])]}


def quantile(hist: dict, q: float):
    """The q-quantile in ms of what `window_counts` returned; None where
    the window holds nothing."""
    counts = hist["counts"]
    total = sum(counts)
    if total <= 0:
        return None
    rank, below = q * total, 0
    for k, n in enumerate(counts):
        if n and below + n >= rank:
            place = k + max(rank - below, 0.0) / n
            return hist["first_edge_ms"] * 2.0 ** (place / hist["per_octave"])
        below += n
    return None


def window_quantile(ctx: dict, hist: str, key: str, q: float):
    """What a reader of one quantile returns: `quantile` of the
    window's counts (`phase_idle.counter_edges`); None where the run
    has no edges or the program no such histogram."""
    edges = phase_idle.counter_edges(ctx)
    if edges is None:
        return None
    counts = window_counts(*edges, hist, key)
    return None if counts is None else quantile(counts, q)
