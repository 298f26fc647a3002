"""The readers of the host side of a tick (ISSUE 35): each of the six by
hand on made-up counters or a made-up trace, nothing to read leaves each
out, the entries as the issue gives them, and the engine's own `stats()`
so that the names the program writes are the names read."""

import json
import os
import sys

import pytest

from harness import layers, loghist, spec, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ENGINE = "engine (serving/batching.py)"
FRONT = "serve front (serving/server.py)"
ENTRIES = {     # name: (unit, source, layer)
    "engine.host_wait_ms_per_step": ("ms", "program_counter", ENGINE),
    "engine.tick_p99_ms": ("ms", "program_counter", ENGINE),
    "engine.prefill_tick_share_pct": ("%", "program_counter", ENGINE),
    "engine.idle_dry_ms": ("ms", "program_span", ENGINE),
    "front.deliver_lag_p50_ms": ("ms", "program_counter", FRONT),
    "front.deliver_lag_p99_ms": ("ms", "program_counter", FRONT),
}
NAMES = list(ENTRIES)
COUNTED = [name for name in NAMES if name != "engine.idle_dry_ms"]
SERVING = ["mistral7b_serve_batchgen", "mistral7b_serve_sharedprefix",
           "lfm2_8b_a1b_serve_batchgen", "nemotron3_super_serve_batchgen",
           "qwen3_next_serve_longgen"]
BUCKETS = 64


def _hist(**counts):
    """{key: {bucket: count}} as `/v1/stats` writes a histogram."""
    out = {"first_edge_ms": 0.25, "per_octave": 4}
    for key, by_bucket in counts.items():
        out[key] = [by_bucket.get(k, 0) for k in range(BUCKETS)]
    return out


def _stats(steps, wall, cpu, ticks, admitting, lags, dry_ns=0):
    # One tick in eight is sampled; a tick a step but for 8 in 200.
    total = sum(ticks.values())
    return {"decode_steps": steps, "ticks_total": total,
            "ticks_sampled": total // 8,
            "tick_phase_ns": {name: 8 * ns for name, ns in wall.items()},
            "tick_phase_sampled_ns": dict(wall),
            "tick_phase_cpu_ns": dict(cpu),
            "tick_ms_hist": _hist(all=ticks, with_prefill=admitting),
            "deliver_lag_hist": _hist(counts=lags),
            "dry_ns": dry_ns, "dry_waits": 0}


def _opened():
    return _stats(
        100,
        {"step.upload": 1_000_000_000, "tick.other": 500_000_000,
         "step.readback": 7_000_000_000, "step.announce": 0},
        {"step.upload": 400_000_000, "tick.other": 450_000_000,
         "step.readback": 100_000_000, "step.announce": 0},
        {20: 90, 28: 10}, {28: 10}, {17: 1000})


def _closed():
    # 200 steps in the window's 200 ticks, 25 of them sampled. Off the
    # processor in those: upload 600 - 100, other 100 - 80, announce
    # 300 - 120 ms: 700 ms, 28 ms a sampled tick and so a step (the
    # readback's 12.8 s of waiting left out). Ticks: 160 in bucket 20
    # (8-9.51 ms), 36 in 28 (32-38.1 ms), 4 in 32 (64-76.1 ms); 40 of
    # them ran a prefill. Lags: 300 in bucket 17, 100 in 18.
    return _stats(
        300,
        {"step.upload": 1_600_000_000, "tick.other": 600_000_000,
         "step.readback": 20_000_000_000, "step.announce": 300_000_000},
        {"step.upload": 500_000_000, "tick.other": 530_000_000,
         "step.readback": 300_000_000, "step.announce": 120_000_000},
        {20: 250, 28: 46, 32: 4}, {28: 46, 32: 4}, {17: 1300, 18: 100})


def _ctx(opened, closed, kind="serve", trace=None):
    return {"kind": kind, "trace": trace,
            "stats": {"open": opened, "close": closed}}


def test_quantiles_inside_a_bucket_and_on_an_edge():
    hist = {"first_edge_ms": 0.25, "per_octave": 4,
            "counts": [0, 10, 10] + [0] * 61}
    # rank 5 of the 10 in bucket 1: half way up it, on its own scale
    assert loghist.quantile(hist, 0.25) == pytest.approx(0.25 * 2 ** 0.375)
    # rank 10 is the last of bucket 1: the edge of buckets 1 and 2
    assert loghist.quantile(hist, 0.5) == pytest.approx(0.25 * 2 ** 0.5)
    assert loghist.quantile(hist, 1.0) == pytest.approx(0.25 * 2 ** 0.75)
    assert loghist.quantile(hist, 0.0) == pytest.approx(0.25 * 2 ** 0.25)
    assert loghist.quantile(dict(hist, counts=[0] * 64), 0.5) is None
    opened = {"h": {"first_edge_ms": 0.25, "per_octave": 4, "c": [3, 1]}}
    closed = {"h": {"first_edge_ms": 0.25, "per_octave": 4, "c": [5, 1]}}
    assert loghist.window_counts(opened, closed, "h", "c")["counts"] == [2, 0]
    assert loghist.window_counts({}, closed, "h", "c") is None
    assert loghist.window_counts(opened, closed, "h", "other") is None


def test_the_counter_readers_by_hand():
    got = layers.read_all(COUNTED, _ctx(_opened(), _closed()))
    assert got == pytest.approx({
        "engine.host_wait_ms_per_step": 28.0,
        # rank 198 of 200: the 2nd of the 36 in bucket 28... 160 + 36 =
        # 196 lie under bucket 32, so it is the 2nd of its 4
        "engine.tick_p99_ms": 0.25 * 2 ** ((32 + 2 / 4) / 4),
        "engine.prefill_tick_share_pct": 20.0,
        # rank 200 of 400, the 200th of bucket 17's 300
        "front.deliver_lag_p50_ms": 0.25 * 2 ** ((17 + 200 / 300) / 4),
        # rank 396: the 96th of bucket 18's 100
        "front.deliver_lag_p99_ms": 0.25 * 2 ** ((18 + 96 / 100) / 4)})
    assert 64.0 < got["engine.tick_p99_ms"] < 76.2
    assert 4.75 < got["front.deliver_lag_p50_ms"] < 5.66


def _events(spans):
    return [{"name": name, "start": a, "dur": b - a} for name, a, b in spans]


def dry_trace():
    """A window of 1 s, two steps. The device is busy 0-0.2, 0.5-0.6 and
    0.9-1.0: idle 0.3 + 0.3. The engine is dry 0.25-0.45 (all of it
    under the first gap: 0.2 s) and 0.75-1.05 (half over the second gap:
    0.15 s of it lie in the gap, 0.1 over a busy device, 0.05 past the
    window). A dry span on another thread's line counts as the engine's
    does (the reader takes every `engine:` span of the host planes)."""
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", 0.0, 0.2),
           ("%fusion.2 = f32[8]{0} fusion(%p)", 0.5, 0.6),
           ("%fusion.3 = f32[8]{0} fusion(%p)", 0.9, 1.0)]
    engine = [("engine:tick", 0.0, 0.25), ("engine:step.dispatch", 0.1, 0.12),
              ("engine:dry", 0.25, 0.45), ("engine:tick", 0.45, 0.75),
              ("engine:step.dispatch", 0.46, 0.48),
              ("engine:dry", 0.75, 1.05)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace_reduce.OPS_LINE, "events": _events(ops)},
            {"name": trace_reduce.MODULES_LINE, "events": []}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": _events(engine)}]}]}


def test_idle_under_the_dry_wait_by_hand():
    ctx = _ctx(_opened(), _closed(), trace=dry_trace())
    got = layers.read_all(["engine.idle_dry_ms", "engine.idle_unnamed_ms"],
                          ctx)
    # (0.2 + 0.15) s over two steps. The older reader counts it too:
    # all of the 0.6 s but the 0.02 s under the second launch. What it
    # holds beside the dry idle (inside the ticks, under no leaf) is
    # the coverage check.
    assert got["engine.idle_dry_ms"] == pytest.approx(175.0)
    assert got["engine.idle_unnamed_ms"] == pytest.approx(290.0)
    # a program that counts its dry waits but never went dry reads 0
    trace = dry_trace()
    lines = trace["planes"][1]["lines"]
    lines[0]["events"] = [ev for ev in lines[0]["events"]
                          if ev["name"] != "engine:dry"]
    ctx = _ctx(_opened(), _closed(), trace=trace)
    assert layers.read_all(["engine.idle_dry_ms"], ctx) == {
        "engine.idle_dry_ms": 0.0}


def _parent(stats):
    """The same `/v1/stats` as the program before ISSUE 35 wrote it."""
    return {k: v for k, v in stats.items()
            if k in ("decode_steps", "ticks_total", "tick_phase_ns")}


@pytest.mark.parametrize("ctx", [
    _ctx(_parent(_opened()), _parent(_closed()), trace=dry_trace()),
    _ctx(_opened(), _opened(), trace=None),
    _ctx(_opened(), _closed(), kind="train", trace=dry_trace()),
    {"kind": "serve", "trace": None, "stats": {"after": _closed()}},
    _ctx({"decode_steps": 1}, {"decode_steps": 9}),
], ids=["the_parent", "no_step", "train", "untraced", "before_issue_24"])
def test_nothing_to_read_leaves_the_metrics_out(ctx):
    assert layers.read_all(NAMES, ctx) == {}


def test_a_trace_without_a_step_or_no_trace_leaves_the_dry_idle_out():
    trace = dry_trace()
    lines = trace["planes"][1]["lines"]
    lines[0]["events"] = [ev for ev in lines[0]["events"]
                          if ev["name"] != "engine:step.dispatch"]
    for ctx in (_ctx(_opened(), _closed(), trace=trace),
                _ctx(_opened(), _closed(), trace=None)):
        assert layers.read_all(["engine.idle_dry_ms"], ctx) == {}
    # the counters' window is the whole 45 s, the trace its first 6: a
    # window whose counters saw no step still reads the trace's own
    got = layers.read_all(["engine.idle_dry_ms"],
                          _ctx(_opened(), _opened(), trace=dry_trace()))
    assert got == pytest.approx({"engine.idle_dry_ms": 175.0})


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_is_as_the_issue_gives_it(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    unit, source, layer = ENTRIES[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "tpot_p50_ms", "workloads": SERVING}
    # appended: the six are the list's last, in the issue's order
    assert [m["name"] for m in bench["per_layer"]][-6:] == NAMES
    for cell in SERVING:
        assert name in [m["name"] for m in spec.Cell(cell).per_layer]
    assert name not in [m["name"] for m in
                        spec.Cell("mistral7b_train_seq4k").per_layer]


def test_the_program_writes_the_names_the_readers_read():
    """The engine itself, tiny and on the CPU: two requests with 0.3 s
    between them. The tick counters are the engine's; the delivery's
    are a streaming handler's, so a merge stands in for one here."""
    sys.path.insert(0, ROOT)
    import time

    from polyaxon_tpu.serving import load_params
    from polyaxon_tpu.serving.batching import (LOG_BUCKETS, LOG_FIRST_EDGE_MS,
                                               LOG_PER_OCTAVE,
                                               ContinuousBatchingEngine,
                                               _PhaseClock, log_bucket)

    cfg, params = load_params("llama_tiny", seed=0)
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2,
                                      max_len=64, kv="paged", page_size=4)
    try:
        engine.generate([[5, 6, 7]], max_new_tokens=2, timeout=300)
        opened, t_open = engine.stats(), time.perf_counter_ns()
        engine.generate([[5, 6, 7]], max_new_tokens=40, timeout=300)
        time.sleep(0.3)
        engine.generate([[5, 6, 7, 8]], max_new_tokens=40, timeout=300)
        lags = [0] * LOG_BUCKETS
        lags[log_bucket(5_000_000)] = 7
        engine.merge_deliver_lags(lags)
    finally:
        engine.stop()
    closed, t_close = engine.stats(), time.perf_counter_ns()
    assert (LOG_BUCKETS, LOG_FIRST_EDGE_MS, LOG_PER_OCTAVE) == (64, 0.25, 4)
    assert "step.announce" in _PhaseClock.LEAVES
    got = layers.read_all(COUNTED, _ctx(opened, closed))
    assert set(got) == set(COUNTED)
    ticks = closed["ticks_total"] - opened["ticks_total"]
    assert ticks >= 80
    # two of the window's ticks admitted a request
    assert got["engine.prefill_tick_share_pct"] == pytest.approx(
        100.0 * 2 / ticks)
    assert 0.0 <= got["engine.host_wait_ms_per_step"] < 1e3
    assert (closed["ticks_sampled"] - opened["ticks_sampled"]
            == pytest.approx(ticks / _PhaseClock.CPU_EVERY, abs=1))
    assert (set(closed["tick_phase_sampled_ns"])
            == set(closed["tick_phase_ns"]))
    assert 0.25 <= got["engine.tick_p99_ms"] < 16_400.0
    # all seven lags in the bucket that holds 5 ms: 4.76-5.66
    assert 4.75 < got["front.deliver_lag_p50_ms"] < 5.66
    assert 4.75 < got["front.deliver_lag_p99_ms"] < 5.66
    # the wait between the two requests is counted, and is in no key
    # that `engine.host_ms_per_step` sums: ticks and waits lie side by
    # side inside the time that passed
    dry = closed["dry_ns"] - opened["dry_ns"]
    assert dry >= 0.25e9 and closed["dry_waits"] > opened["dry_waits"]
    in_ticks = sum(ns - opened["tick_phase_ns"][name]
                   for name, ns in closed["tick_phase_ns"].items())
    assert in_ticks + dry <= t_close - t_open
    assert set(closed["tick_phase_cpu_ns"]) == set(closed["tick_phase_ns"])
