"""Running the per-layer readers a cell reports."""

from __future__ import annotations

from harness import spec


def read_all(names: list[str], ctx: dict) -> dict:
    """{name: value} for every reader that finds something to read; a
    reader that returns None leaves its metric out of the line."""
    out = {}
    for name in names:
        value = spec.load_reader(name).read(ctx)
        if value is not None:
            out[name] = float(value)
    return out


def live_lengths_at(records: list[dict], t: float) -> list[int]:
    """Context length of every request in a slot at instant `t`, as the
    client saw it: prompt plus the tokens that had arrived, between a
    request's first token and its last."""
    out = []
    for r in records:
        times = r["token_times"]
        if not times or not times[0] <= t:
            continue
        if r["n_out"] >= r["max_new"] and t > times[-1]:
            continue
        if r.get("error") and t > times[-1]:
            continue
        out.append(r["prompt_len"] + sum(1 for x in times if x <= t))
    return out
