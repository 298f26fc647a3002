"""From what the client saw to the end-to-end metrics: pure arithmetic.

A record is one request as the client saw it: when it was due and sent,
and the arrival time of every streamed token. A rate is taken over all
the tokens and all the time of the window; a tail is the tail of all
requests due in it.
"""

from __future__ import annotations


def percentile(values: list[float], pct: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * pct / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def out_tok_s(records: list[dict], t0: float, t1: float) -> float:
    """Output tokens whose arrival lies in [t0, t1), over its length:
    counted by the token, never by requests completed."""
    n = sum(1 for r in records for t in r["token_times"] if t0 <= t < t1)
    return n / (t1 - t0)


def request_gaps(record: dict, t0: float, t1: float) -> list[float]:
    """Gaps between consecutive tokens of one request, both ends inside
    the window (a request that straddles an edge keeps what lies inside)."""
    times = record["token_times"]
    return [b - a for a, b in zip(times, times[1:]) if t0 <= a and b < t1]


def tpot_ms(records: list[dict], t0: float, t1: float, pct: float = 50.0,
            min_gaps: int = 4):
    """Percentile over requests of their mean inter-token gap inside
    the window; (value in ms, requests counted)."""
    means = []
    for record in records:
        gaps = request_gaps(record, t0, t1)
        if len(gaps) >= min_gaps:
            means.append(1e3 * sum(gaps) / len(gaps))
    if not means:
        return None, 0
    return percentile(means, pct), len(means)


def itl_ms(records: list[dict], t0: float, t1: float, pct: float = 99.0):
    """Percentile over all single inter-token gaps inside the window."""
    gaps = [1e3 * g for r in records for g in request_gaps(r, t0, t1)]
    return (percentile(gaps, pct), len(gaps)) if gaps else (None, 0)


def first_token_ms(records: list[dict], t0: float, t1: float,
                   t_end: float) -> list[float]:
    """First-token times, from the moment each request was *due*, of the
    requests due in [t0, t1). One that failed, was refused or had no
    token by `t_end` counts as the worst: it waited until then."""
    out = []
    for record in records:
        if not t0 <= record["due"] < t1:
            continue
        if record["token_times"] and not record.get("error"):
            out.append(1e3 * (record["token_times"][0] - record["due"]))
        else:
            out.append(1e3 * (t_end - record["due"]))
    return out


def late_ms(records: list[dict], t0: float, t1: float) -> list[float]:
    """How late the generator sent each request due in the window."""
    return [1e3 * (r["sent"] - r["due"]) for r in records
            if t0 <= r["due"] < t1]
