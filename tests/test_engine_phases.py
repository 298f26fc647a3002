"""The engine's phase clock (ISSUE 24): host time of the decode loop by
phase in `/v1/stats`, the `engine:` spans a profile shows on the device
operations' clock, the slow tick's black box, and `stats()` off the
buffers the engine thread donates."""

import glob
import os
import threading
import time

import jax
import pytest

from polyaxon_tpu.obs import metrics as obs_metrics
from polyaxon_tpu.obs import reqtrace
from polyaxon_tpu.serving import load_params
from polyaxon_tpu.serving.batching import (ContinuousBatchingEngine,
                                           _PhaseClock)

STEP_PHASES = ["engine:step.keys", "engine:step.upload",
               "engine:step.dispatch", "engine:step.readback",
               "engine:step.emit"]
PROMPTS = [[5, 6, 7], [1, 2, 3, 4], [9, 8, 7, 6, 5]]


@pytest.fixture(scope="module")
def model():
    return load_params("llama_tiny", seed=0)


def _engine(model, **kwargs):
    cfg, params = model
    kwargs.setdefault("kv", "paged")
    return ContinuousBatchingEngine("llama_tiny", cfg, params, slots=4,
                                    max_len=96, page_size=4, **kwargs)


@pytest.fixture(scope="module")
def run(model):
    """One engine, three requests of 60 tokens, `stats()` read before,
    from a second thread all the while, and after."""
    registry = obs_metrics.MetricsRegistry()
    engine = _engine(model, registry=registry)
    samples, errors = [], []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            try:
                samples.append(engine.stats())
            except Exception as exc:  # noqa: BLE001 — the test's subject
                errors.append(exc)
            time.sleep(0.002)

    poller = threading.Thread(target=poll, daemon=True)
    try:
        before = engine.stats()
        poller.start()
        outs = engine.generate(PROMPTS, max_new_tokens=60, timeout=300)
        stop.set()
        poller.join(timeout=30)
    finally:
        stop.set()
        engine.stop()
    # Read with the engine's thread ended: a request is done before the
    # tick that finished it is counted.
    after = engine.stats()
    hist = registry.snapshot()[
        "polyaxon_serving_engine_tick_seconds"]["series"][""]
    return {"before": before, "after": after, "samples": samples,
            "errors": errors, "outs": outs, "hist": hist,
            "poller_alive": poller.is_alive()}


def test_leaf_phases_are_monotone_and_sum_to_the_ticks(run):
    series = [run["before"], *run["samples"], run["after"]]
    assert set(run["after"]["tick_phase_ns"]) == set(_PhaseClock.LEAVES)
    for earlier, later in zip(series, series[1:]):
        for name in _PhaseClock.LEAVES:
            assert (later["tick_phase_ns"][name]
                    >= earlier["tick_phase_ns"][name])
    phases = run["after"]["tick_phase_ns"]
    assert run["after"]["ticks_total"] >= 50
    # The histogram times `_tick` alone, by another clock: the leaves
    # cover it, and without the gauges' walk and the lines between
    # phases they do not exceed it.
    in_tick = sum(phases.values())
    assert in_tick >= 0.97 * run["hist"]["sum"] * 1e9
    assert (in_tick - phases["observe"] - phases["tick.other"]
            <= 1.03 * run["hist"]["sum"] * 1e9)
    assert phases["tick.other"] < 0.15 * in_tick
    for name in ("step.keys", "step.upload", "step.dispatch",
                 "step.readback", "step.emit", "admit.match",
                 "admit.prefill", "observe", "sweep"):
        assert phases[name] > 0, name
    assert phases["spec"] == phases["prefill_chunk"] == 0


def test_ticks_and_admissions_count(run):
    after = run["after"]
    assert run["before"]["ticks_total"] == 0
    assert run["before"]["admissions_total"] == 0
    assert after["admissions_total"] == len(PROMPTS)
    assert [len(out) for out in run["outs"]] == [60] * len(PROMPTS)
    # one decode step a tick at most, and the tick histogram still fed
    assert after["ticks_total"] >= after["decode_steps"] >= 60
    assert run["hist"]["count"] == after["ticks_total"]
    assert after["slow_ticks"] == [] or all(
        tick["duration_ms"] > 1000 for tick in after["slow_ticks"])


def test_stats_from_a_second_thread_while_the_engine_decodes(run):
    assert not run["poller_alive"]
    assert run["errors"] == []
    assert len(run["samples"]) >= 5
    assert any(s["active"] > 0 for s in run["samples"])


def test_stats_does_not_touch_the_donated_cache(model):
    """What a step donates is gone while it runs: `stats()` reports the
    byte counts taken where the cache was built."""
    engine = _engine(model)
    try:
        engine.generate([[5, 6, 7]], max_new_tokens=2, timeout=300)
        held = engine.stats()["device"]
    finally:
        engine.stop()
    for leaf in jax.tree.leaves(engine._cache):
        leaf.delete()
    assert engine.stats()["device"] == held
    assert held["kv_bytes"] > 0 and held["param_bytes"] > 0
    assert held["kv_bytes_per_device"] == {0: held["kv_bytes"]}


def test_decode_program_has_a_name(model):
    engine = _engine(model)
    try:
        engine.generate([[5, 6, 7]], max_new_tokens=2, timeout=300)
        text = engine._step_plain._compiled.as_text()
    finally:
        engine.stop()
    assert "jit_decode_step" in text.split("\n", 1)[0]


def test_a_stalled_step_leaves_one_slow_tick_with_its_phase_split(
        model, tmp_path):
    dump = str(tmp_path / "ring.json")
    engine = _engine(model, trace_dump_path=dump)
    try:
        # warm up: a compiling tick after the first may be a slow tick
        # of its own
        engine.generate([[5, 6, 7]], max_new_tokens=8, timeout=300)
        warm = len(engine.stats()["slow_ticks"])
        real, stalled = engine._step_plain, []

        def stall_once(*args):
            if not stalled:
                stalled.append(True)
                time.sleep(1.5)
            return real(*args)

        engine._step_plain = stall_once
        engine.generate([[5, 6, 7]], max_new_tokens=8, timeout=300)
        slow = engine.stats()["slow_ticks"]
    finally:
        engine.stop()
    assert len(slow) == warm + 1
    tick = slow[-1]
    assert set(tick) == {"t_wall", "duration_ms", "phases_ms", "live",
                         "prefilling", "queued", "decode_steps",
                         "kv_pages_free"}
    assert 1500 <= tick["duration_ms"] < 1500 + 1000
    worst = max(tick["phases_ms"], key=tick["phases_ms"].get)
    assert worst in ("step.dispatch", "step.readback")
    assert tick["phases_ms"][worst] >= 1500
    assert sum(tick["phases_ms"].values()) == pytest.approx(
        tick["duration_ms"])
    assert tick["live"] == 1 and tick["queued"] == 0
    assert abs(tick["t_wall"] - time.time()) < 600
    # the black box leaves with the ring dump
    assert reqtrace.read_ring_dump(dump)["slow_ticks"] == slow


@pytest.mark.parametrize("case", ["bounded", "floor_and_factor"])
def test_slow_tick_rule(case):
    clock = _PhaseClock(lambda: {"live": 0})
    if case == "bounded":
        clock.SLOW_FLOOR_S = clock.SLOW_FACTOR = 0.0
        for _ in range(_PhaseClock.SLOW_KEPT + 4):
            with clock.tick():
                pass
        assert len(clock.slow) == _PhaseClock.SLOW_KEPT
        assert clock.ticks == _PhaseClock.SLOW_KEPT + 4
        return
    # A floor of 30 ms, so that a 2 ms sleep stretched by a loaded host
    # (10.7 ms seen under six test workers) still lies under it.
    clock.SLOW_FLOOR_S = 0.03
    for pause in (0.1,) + (0.0,) * 8 + (0.002, 0.1) + (0.0,) * 4:
        with clock.tick():
            with clock.phase("sweep"):
                time.sleep(pause)
    # the first tick has no median to be held against; 2 ms is over eight
    # medians and under the floor; the later 100 ms is over both
    assert len(clock.slow) == 1
    assert clock.slow[0]["duration_ms"] >= 100
    assert max(clock.slow[0]["phases_ms"],
               key=clock.slow[0]["phases_ms"].get) == "sweep"
    assert clock.slow[0]["live"] == 0
    with pytest.raises(KeyError):
        with clock.phase("no_such_phase"):
            pass


@pytest.fixture(scope="module")
def profile(model, tmp_path_factory):
    """One request of 60 tokens under `jax.profiler`: the engine's spans
    on the host plane, in order of their start, and the counters over
    the traced run."""
    from jax.profiler import ProfileData

    tmp_path = tmp_path_factory.mktemp("profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    engine = _engine(model)
    try:
        engine.generate([[5, 6, 7]], max_new_tokens=4, timeout=300)
        before = engine.stats()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            engine.generate([[5, 6, 7]], max_new_tokens=60, timeout=300)
        finally:
            jax.profiler.stop_trace()
        after = engine.stats()
    finally:
        engine.stop()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for ev in line.events if ev.name.startswith("engine:")]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    lines = [events for events in lines if events]
    assert len(lines) == 1          # one thread: the engine's
    return {"events": sorted(lines[0], key=lambda ev: ev[1]),
            "before": before, "after": after}


def test_a_profile_holds_the_engine_spans_on_a_host_plane(profile):
    events = profile["events"]
    ticks = [ev for ev in events if ev[0] == "engine:tick"]
    assert len(ticks) >= 60
    whole = 0
    for _, t0, t1 in ticks:
        inside = [ev for ev in events
                  if ev[0] in STEP_PHASES and t0 <= ev[1] < t1]
        if not inside:
            continue            # a tick without a step
        names = [ev[0] for ev in inside]
        # The launch, then the step before it read and handed out; the
        # first step after an idle engine has none before it, and the
        # tick whose launch ends the last row reads that one too.
        assert names[:3] == STEP_PHASES[:3]
        assert names[3:] in (STEP_PHASES[4:], STEP_PHASES[3:],
                             STEP_PHASES[3:] * 2)
        whole += names == STEP_PHASES
        for (_, _, stop), (_, start, _) in zip(inside, inside[1:]):
            assert stop <= start
        assert inside[-1][2] <= t1
    assert whole >= 58
    leaves = [ev for ev in events
              if ev[0] not in ("engine:tick", "engine:admit")]
    for (_, _, stop), (_, start, _) in zip(leaves, leaves[1:]):
        assert stop <= start
    names = {ev[0] for ev in events}
    assert {"engine:sweep", "engine:admit", "engine:admit.pick",
            "engine:admit.match", "engine:admit.prefill",
            "engine:observe"} <= names


def test_a_step_is_launched_before_the_one_before_it_is_read(profile):
    """ISSUE 34: `engine:step.dispatch` of step n+1 begins (and ends)
    before `engine:step.readback` of step n begins; the leaves still
    fill their tick; nearly every step ran ahead."""
    events = profile["events"]
    launches = [ev for ev in events if ev[0] == "engine:step.dispatch"]
    reads = [ev for ev in events if ev[0] == "engine:step.readback"]
    assert len(launches) == len(reads) == 60    # every step read once
    for n in range(59):
        assert launches[n + 1][2] <= reads[n][1]
        assert launches[n][2] <= launches[n + 1][1]
    assert launches[59][2] <= reads[59][1]      # the last: by the drain
    for _, t0, t1 in (ev for ev in events if ev[0] == "engine:tick"):
        inside = [ev for ev in events
                  if ev[0] not in ("engine:tick", "engine:admit")
                  and t0 <= ev[1] < t1]
        if not any(ev[0] == "engine:step.dispatch" for ev in inside):
            continue
        # leaves apart from each other (checked above) and inside their
        # tick: their sum and `tick.other` are the tick
        assert all(t0 <= a and b <= t1 for _, a, b in inside)
        assert sum(b - a for _, a, b in inside) <= t1 - t0
    steps = (profile["after"]["decode_steps"]
             - profile["before"]["decode_steps"])
    ahead = (profile["after"]["decode_steps_ahead"]
             - profile["before"]["decode_steps_ahead"])
    assert steps == 60 and ahead / steps >= 0.95
    assert profile["after"]["decode_tokens_dropped"] == 0
