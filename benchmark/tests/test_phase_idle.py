"""The readers of the engine's phases (ISSUE 24), on a hand-made trace
(device operations and `engine:` host spans, seconds) whose every value
is worked out below, on hand-made counters, and on the engine's own
`stats()` so that the names the program writes are the names read."""

import json
import os
import sys

import pytest

from harness import layers, phase_idle, spec, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
IDLE = ["engine.idle_admit_ms", "engine.idle_keys_ms",
        "engine.idle_launch_ms", "engine.idle_readback_ms",
        "engine.idle_bookkeeping_ms", "engine.idle_unnamed_ms"]
COUNTED = ["engine.host_ms_per_step", "kv.admit_match_us"]
CELLS = ["mistral7b_serve_batchgen", "mistral7b_serve_sharedprefix"]


def _events(spans):
    return [{"name": name, "start": a, "dur": b - a} for name, a, b in spans]


def hand_made_trace():
    """Two ticks, two steps, a window of 1 s. The device is busy 0-0.1,
    0.2-0.5, 0.6-0.65 and 0.7-1.0: idle 0.10 + 0.10 + 0.05 = 0.25 s.

    gap 0.10-0.20 lies under keys (0.04), upload (0.03), dispatch (0.02)
    and readback (0.01): one gap split over four phases. Gap 0.50-0.60:
    readback 0.01, emit 0.005, observe 0.005, nothing 0.01 (between the
    ticks), sweep 0.01, admit 0.06. Gap 0.65-0.70: keys, upload,
    dispatch 0.01 each, nothing 0.01 (0.68-0.69, inside the tick but
    under no leaf), readback 0.01."""
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", 0.0, 0.1),
           ("%paged_decode.5 = bf16[16,8,4,128]{3,2,1,0} custom-call(%q)",
            0.2, 0.5),
           ("%fusion.2 = f32[8]{0} fusion(%p)", 0.6, 0.65),
           ("%paged_decode.5 = bf16[16,8,4,128]{3,2,1,0} custom-call(%q)",
            0.7, 1.0)]
    engine = [
        ("engine:tick", 0.0, 0.52), ("engine:sweep", 0.0, 0.01),
        ("engine:admit", 0.01, 0.03), ("engine:admit.pick", 0.01, 0.015),
        ("engine:admit.match", 0.015, 0.02),
        ("engine:admit.prefill", 0.02, 0.03),
        ("engine:step.keys", 0.03, 0.14), ("engine:step.upload", 0.14, 0.17),
        ("engine:step.dispatch", 0.17, 0.19),
        ("engine:step.readback", 0.19, 0.51),
        ("engine:step.emit", 0.51, 0.515), ("engine:observe", 0.515, 0.52),
        ("engine:tick", 0.53, 1.0), ("engine:sweep", 0.53, 0.54),
        ("engine:admit", 0.54, 0.62), ("engine:step.keys", 0.62, 0.66),
        ("engine:step.upload", 0.66, 0.67),
        ("engine:step.dispatch", 0.67, 0.68),
        ("engine:step.readback", 0.69, 0.98),
        ("engine:step.emit", 0.98, 0.99), ("engine:observe", 0.99, 1.0)]
    others = [("np.asarray(jax.Array)", 0.19, 0.51),
              ("PjitFunction(concatenate)", 0.12, 0.14),
              ("engine:step.dispatch", 1.5, 1.6)]   # after the window
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace_reduce.OPS_LINE, "events": _events(ops)},
            {"name": trace_reduce.MODULES_LINE, "events": []}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": _events(engine)},
            {"name": "other-thread", "events": _events(others)}]}]}


def _ctx(trace=None, stats=None):
    ctx = {"kind": "serve", "trace": trace, "stats": stats or {"after": {}}}
    if trace is not None:
        ctx["busy"] = trace_reduce.busy(trace)
    return ctx


def test_idle_is_laid_under_the_phases_by_hand():
    found = phase_idle.idle_by_group(hand_made_trace())
    assert found["steps"] == 2          # the third dispatch is outside
    assert found["idle_s"] == pytest.approx(0.25)
    assert found["by_group"] == pytest.approx({
        "admit": 0.06, "keys": 0.04 + 0.01, "launch": 0.03 + 0.02 + 0.02,
        "readback": 0.01 + 0.01 + 0.01, "bookkeeping": 0.005 + 0.005 + 0.01,
        "unnamed": 0.01 + 0.01})


def test_each_reader_by_hand_and_the_six_sum_to_the_idle_per_step():
    ctx = _ctx(hand_made_trace())
    got = layers.read_all(IDLE, ctx)
    assert got == pytest.approx({
        "engine.idle_admit_ms": 30.0, "engine.idle_keys_ms": 25.0,
        "engine.idle_launch_ms": 35.0, "engine.idle_readback_ms": 15.0,
        "engine.idle_bookkeeping_ms": 10.0, "engine.idle_unnamed_ms": 10.0})
    assert sum(got.values()) == pytest.approx(250.0 / 2)
    # what `device.serve_idle_pct` reads from outside
    idle_pct = spec.load_reader("device.serve_idle_pct").read(ctx)
    assert idle_pct == pytest.approx(25.0)
    assert (sum(got.values()) * 1e-3 * 2 / ctx["busy"]["window_s"] * 100
            == pytest.approx(idle_pct))


def test_counter_readers_by_hand():
    def stats(steps, admitted, **ns):
        return {"decode_steps": steps, "admissions_total": admitted,
                "tick_phase_ns": ns}

    ctx = _ctx(stats={
        "open": stats(100, 10, **{"step.keys": 1_000_000_000,
                                  "step.readback": 5_000_000_000,
                                  "admit.match": 2_000_000, "observe": 0}),
        "close": stats(150, 14, **{"step.keys": 1_900_000_000,
                                   "step.readback": 9_000_000_000,
                                   "admit.match": 2_600_000,
                                   "observe": 99_400_000})})
    # (900 + 0.6 + 99.4) ms of host phases over 50 steps; readback left out
    assert layers.read_all(COUNTED, ctx) == pytest.approx({
        "engine.host_ms_per_step": 20.0, "kv.admit_match_us": 150.0})
    ctx["stats"]["close"]["admissions_total"] = 10      # none admitted
    assert layers.read_all(COUNTED, ctx) == pytest.approx({
        "engine.host_ms_per_step": 20.0})


def test_readers_find_nothing_without_a_trace_or_the_program_side():
    assert layers.read_all(IDLE + COUNTED, _ctx()) == {}
    assert layers.read_all(IDLE + COUNTED, {**_ctx(), "kind": "train"}) == {}
    # a program from before the spans: device operations, jax's own events
    bare = hand_made_trace()
    bare["planes"][1]["lines"] = bare["planes"][1]["lines"][1:]
    bare["planes"][1]["lines"][0]["events"].pop()
    old_stats = {"open": {"decode_steps": 1}, "close": {"decode_steps": 9}}
    assert layers.read_all(IDLE + COUNTED, _ctx(bare, old_stats)) == {}
    # spans but no device plane (a CPU rehearsal)
    hostonly = hand_made_trace()
    hostonly["planes"] = hostonly["planes"][1:]
    assert phase_idle.idle_by_group(hostonly) is None


def test_the_new_entries_are_as_the_issue_gives_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    added = [m for m in bench["per_layer"] if m["name"] in IDLE + COUNTED]
    assert sorted(m["name"] for m in added) == sorted(IDLE + COUNTED)
    layer_names = {m["layer"] for m in bench["per_layer"]
                   if m not in added}
    for metric in added:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["workloads"] == CELLS
        assert metric["moves"] == "tpot_p50_ms"
        assert metric["better"] == "lower" and metric["layer"] in layer_names
        assert metric["source"] == ("program_counter"
                                    if metric["name"] in COUNTED
                                    else "program_span")
        assert callable(spec.load_reader(metric["name"]).read)
    for cell in CELLS:
        names = [m["name"] for m in spec.Cell(cell).per_layer]
        assert set(IDLE + COUNTED) <= set(names)
    assert not set(IDLE + COUNTED) & {
        m["name"] for m in spec.Cell("mistral7b_train_seq4k").per_layer}


def test_the_program_writes_the_names_the_readers_read():
    """The engine itself, tiny and on the CPU: its `stats()` at two
    instants feed the counter readers. The values are this host's, and
    go nowhere."""
    sys.path.insert(0, ROOT)
    from polyaxon_tpu.serving import load_params
    from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

    cfg, params = load_params("llama_tiny", seed=0)
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2,
                                      max_len=64, kv="paged", page_size=4)
    try:
        opened = engine.stats()
        engine.generate([[5, 6, 7], [1, 2, 3, 4]], max_new_tokens=6,
                        timeout=300)
        closed = engine.stats()
    finally:
        engine.stop()
    got = layers.read_all(COUNTED, _ctx(stats={"open": opened,
                                               "close": closed}))
    assert set(got) == set(COUNTED) and all(v > 0 for v in got.values())
    waited = (closed["tick_phase_ns"][phase_idle.WAITS_FOR_DEVICE]
              - opened["tick_phase_ns"][phase_idle.WAITS_FOR_DEVICE])
    assert waited > 0
    leaves = {"engine:" + name for name in closed["tick_phase_ns"]}
    for names in phase_idle.GROUPS.values():
        assert set(names) <= leaves | {"engine:admit"}
    assert phase_idle.STEP_SPAN in leaves
    json.dumps(closed)      # the harness stores it in program.json


def test_a_traced_rehearsal_reports_the_counters_and_keeps_the_black_box():
    """A whole traced run of the dry open-loop cell on the CPU, with the
    new readers added to its list: the counters come through the window's
    edges; the idle readers find no device plane and leave their metrics
    out; `program.json` keeps `tick_phase_ns` and `slow_ticks`."""
    import run
    from rehearsal import DRY

    bench = spec.load_benchmark(DRY)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        for metric in json.load(fh)["per_layer"]:
            if metric["name"] in IDLE + COUNTED:
                bench["per_layer"].append({**metric,
                                           "workloads": ["tiny_open"]})
    cell = spec.Cell("tiny_open", bench, DRY)
    final = run.run_cell(cell, seed=4_000_000_007, seconds=3, trace=True,
                         require_chip=False)
    assert final["correct"] is True
    assert set(COUNTED) <= set(final["metrics"])
    assert not set(IDLE) & set(final["metrics"])
    assert final["metrics"]["kv.admit_match_us"]["unit"] == "us"
    assert 0 < final["metrics"]["engine.host_ms_per_step"]["value"] < 1000
    with open(os.path.join(run.ROOT, ".benchmark_out",
                           "tiny_open-4000000007-1", "program.json")) as fh:
        after = json.load(fh)["stats"]["after"]
    assert after["slow_ticks"] == [] or all(
        t["duration_ms"] > 1000 for t in after["slow_ticks"])
    assert sum(after["tick_phase_ns"].values()) > 0
    assert after["ticks_total"] >= after["decode_steps"] > 0
