"""The engine's phase clock (ISSUE 24): host time of the decode loop by
phase in `/v1/stats`, the `engine:` spans a profile shows on the device
operations' clock, the slow tick's black box, and `stats()` off the
buffers the engine thread donates. ISSUE 35: the engine thread's CPU
beside each phase's wall time, the tick's length as a histogram, the
dry wait as a span and a counter, and a token's way from its readback
to the end of its handler's write."""

import glob
import json
import os
import threading
import time
import urllib.request

import jax
import pytest

from polyaxon_tpu.obs import metrics as obs_metrics
from polyaxon_tpu.obs import reqtrace
from polyaxon_tpu.serving import ServingServer, load_params
from polyaxon_tpu.serving import server as serving_server
from polyaxon_tpu.serving.batching import (LOG_BUCKETS,
                                           ContinuousBatchingEngine,
                                           _PhaseClock, log_bucket)

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


@pytest.fixture(scope="module")
def stalled(model, tmp_path_factory):
    """One engine whose decode program sleeps 1.5 s once, after a
    warm-up: `stats()` before and after the request that met it."""
    dump = str(tmp_path_factory.mktemp("stalled") / "ring.json")
    engine = _engine(model, trace_dump_path=dump)
    engine._clock.CPU_EVERY = 1     # the stalled tick reads its CPU time
    try:
        # warm up: a compiling tick after the first may be a slow tick
        # of its own
        engine.generate([[5, 6, 7]], max_new_tokens=8, timeout=300)
        warm = engine.stats()
        real, stalled = engine._step_plain, []

        def stall_once(*args):
            if not stalled:
                stalled.append(True)
                time.sleep(1.5)
            return real(*args)

        engine._step_plain = stall_once
        engine.generate([[5, 6, 7]], max_new_tokens=8, timeout=300)
    finally:
        engine.stop()
    return {"warm": warm, "after": engine.stats(), "dump": dump}


def test_a_stalled_step_leaves_one_slow_tick_with_its_phase_split(stalled):
    dump, slow = stalled["dump"], stalled["after"]["slow_ticks"]
    warm = len(stalled["warm"]["slow_ticks"])
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
            # The wait that follows the last tick begins inside the
            # trace; `stop()` ends it there (a span is written at its
            # end).
            time.sleep(0.05)
            engine.stop()
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


# ---- ISSUE 35: the host side of a tick ---------------------------------

def test_cpu_beside_wall_per_leaf(run):
    """One tick in eight reads the CPU clock too. `tick_phase_cpu_ns`
    and `tick_phase_sampled_ns` (those ticks' wall time): the keys of
    `LEAVES`, monotone from sample to sample, CPU never more than the
    leaf's wall time, the sampled ticks' wall never more than all
    ticks'."""
    series = [run["before"], *run["samples"], run["after"]]
    for key in ("tick_phase_cpu_ns", "tick_phase_sampled_ns"):
        assert set(run["after"][key]) == set(_PhaseClock.LEAVES)
        for earlier, later in zip(series, series[1:]):
            for name in _PhaseClock.LEAVES:
                assert later[key][name] >= earlier[key][name]
    for sample in series:
        for name in _PhaseClock.LEAVES:
            # (a parent's own share carries its children's clock
            # reads: a microsecond a child, hence the millisecond)
            assert (sample["tick_phase_cpu_ns"][name]
                    <= sample["tick_phase_sampled_ns"][name] + 1_000_000), name
    after = run["after"]
    for name in _PhaseClock.LEAVES:
        assert (after["tick_phase_sampled_ns"][name]
                <= after["tick_phase_ns"][name])
    assert _PhaseClock.CPU_EVERY == 8
    assert after["ticks_sampled"] == -(-after["ticks_total"] // 8)
    assert run["before"]["ticks_sampled"] == 0
    cpu = after["tick_phase_cpu_ns"]
    assert 0 < sum(cpu.values()) <= sum(
        after["tick_phase_sampled_ns"].values())
    # the engine's own Python is on the processor
    assert cpu["step.emit"] > 0 and cpu["step.upload"] > 0


def test_a_leaf_that_sleeps_shows_wall_far_above_cpu(stalled):
    """The stalled step: 1.5 s of wall time in the launch, next to no
    CPU; the record of the slow tick keeps its keys."""
    for key in _PhaseClock.LEAVES:   # every tick sampled: one wall time
        assert (stalled["after"]["tick_phase_sampled_ns"][key]
                == stalled["after"]["tick_phase_ns"][key])
    wall = {k: stalled["after"]["tick_phase_ns"][k]
            - stalled["warm"]["tick_phase_ns"][k] for k in _PhaseClock.LEAVES}
    cpu = {k: stalled["after"]["tick_phase_cpu_ns"][k]
           - stalled["warm"]["tick_phase_cpu_ns"][k]
           for k in _PhaseClock.LEAVES}
    waited = {k: wall[k] - cpu[k] for k in wall}
    worst = max(waited, key=waited.get)
    assert worst == "step.dispatch"
    assert wall[worst] >= 1.5e9 and cpu[worst] < 0.5e9
    assert waited[worst] >= 1.2e9
    assert "phases_cpu_ms" not in stalled["after"]["slow_ticks"][-1]


def test_the_announcement_is_a_leaf_of_its_own(run):
    phases = run["after"]["tick_phase_ns"]
    assert phases["step.announce"] > 0
    assert run["after"]["tick_phase_cpu_ns"]["step.announce"] > 0
    assert phases["tick.other"] < 0.15 * sum(phases.values())


def test_tick_lengths_as_a_histogram(run, profile):
    for stats in (run["before"], run["after"], *run["samples"][:3]):
        hist = stats["tick_ms_hist"]
        assert hist["first_edge_ms"] == 0.25 and hist["per_octave"] == 4
        assert len(hist["all"]) == len(hist["with_prefill"]) == LOG_BUCKETS
        assert all(w <= a for w, a in zip(hist["with_prefill"], hist["all"]))
    after = run["after"]
    assert sum(run["before"]["tick_ms_hist"]["all"]) == 0
    assert sum(after["tick_ms_hist"]["all"]) == after["ticks_total"]
    # three requests, admitted in one tick or in up to three
    assert 1 <= sum(after["tick_ms_hist"]["with_prefill"]) <= len(PROMPTS)
    # the mean of the buckets' edges brackets the ticks' own time
    low = sum(n * 0.25 * 2 ** (k / 4)
              for k, n in enumerate(after["tick_ms_hist"]["all"]) if k)
    assert low <= sum(after["tick_phase_ns"].values()) / 1e6
    # the traced run: the ticks that hold an `engine:admit.prefill`
    events = profile["events"]
    admitting = sum(
        1 for _, t0, t1 in (ev for ev in events if ev[0] == "engine:tick")
        if any(ev[0] == "engine:admit.prefill" and t0 <= ev[1] < t1
               for ev in events))
    grew = (sum(profile["after"]["tick_ms_hist"]["with_prefill"])
            - sum(profile["before"]["tick_ms_hist"]["with_prefill"]))
    assert grew == admitting == 1


@pytest.mark.parametrize("ns, bucket", [
    (0, 0), (249_999, 0), (250_000, 0), (297_302, 1), (500_000, 4),
    (8_000_000, 20), (15_999_999_999, 63), (10 ** 12, 63)])
def test_log_bucket_edges(ns, bucket):
    assert log_bucket(ns) == bucket


def _settled(engine) -> dict:
    """`stats()` once the loop has gone to its wait."""
    last = engine.stats()
    for _ in range(200):
        time.sleep(0.02)
        now = engine.stats()
        if now["ticks_total"] == last["ticks_total"]:
            return now
        last = now
    raise AssertionError("the engine never went idle")


def test_the_dry_wait_is_counted_and_is_no_tick(model):
    engine = _engine(model)
    try:
        engine.generate([[5, 6, 7]], max_new_tokens=4, timeout=300)
        first = _settled(engine)
        time.sleep(0.3)
        idle = engine.stats()
        engine.generate([[5, 6, 7]], max_new_tokens=4, timeout=300)
        second = _settled(engine)
    finally:
        engine.stop()
    # nothing ran while the engine was dry: no tick, no phase time
    assert idle["ticks_total"] == first["ticks_total"]
    assert idle["tick_phase_ns"] == first["tick_phase_ns"]
    assert idle["tick_phase_cpu_ns"] == first["tick_phase_cpu_ns"]
    # a wait is counted where it ends: the second request ended one
    assert second["dry_ns"] - first["dry_ns"] >= 0.25e9
    assert second["dry_waits"] - first["dry_waits"] >= 1
    assert "dry" not in second["tick_phase_ns"]
    assert not any("dry" in key for key in second["tick_phase_ns"])
    # and the wait is in no tick: the ticks of the second request are
    # far shorter than the 0.3 s between the two
    grown = (sum(second["tick_phase_ns"].values())
             - sum(first["tick_phase_ns"].values()))
    assert grown < second["dry_ns"] - first["dry_ns"] + 0.25e9
    assert (sum(second["tick_ms_hist"]["all"][log_bucket(250_000_000):])
            == sum(first["tick_ms_hist"]["all"][log_bucket(250_000_000):]))


def test_a_profile_holds_the_dry_wait_and_the_announcement(profile):
    """`engine:dry` and `engine:step.announce` on the one host line of
    the other `engine:` spans (the fixture asserts there is one), the
    wait outside every tick, the announcement after the launch."""
    events = profile["events"]
    ticks = [ev for ev in events if ev[0] == "engine:tick"]
    dry = [ev for ev in events if ev[0] == "engine:dry"]
    announced = [ev for ev in events if ev[0] == "engine:step.announce"]
    assert dry and len(announced) >= 60
    for _, d0, d1 in dry:
        assert d1 > d0
        assert not any(t0 < d1 and d0 < t1 for _, t0, t1 in ticks)
    # the wait after the last tick lasted until `stop()`: 50 ms
    assert max(d1 - d0 for _, d0, d1 in dry) >= 0.04e9
    for _, a0, a1 in announced:
        assert any(t0 <= a0 and a1 <= t1 for _, t0, t1 in ticks)
    launches = [ev for ev in events if ev[0] == "engine:step.dispatch"]
    reads = [ev for ev in events if ev[0] == "engine:step.readback"]
    between = 0
    for n in range(59):
        between += any(launches[n + 1][2] <= a0 and a1 <= reads[n][1]
                       for _, a0, a1 in announced)
    assert between == 59        # launch, announce, readback
    grew = profile["after"]["dry_waits"] - profile["before"]["dry_waits"]
    assert grew >= 1


@pytest.fixture(scope="module")
def streamed():
    """Three clients stream 40 tokens each over HTTP from one continuous
    server while a fourth thread reads `stats()`; the handlers' lags as
    `log_bucket` was handed them."""
    lags, real = [], serving_server.log_bucket

    def recording(ns):
        lags.append(ns)
        return real(ns)

    serving_server.log_bucket = recording
    samples, errors, tokens = [], [], []
    stop = threading.Event()

    def stream(url, row):
        request = urllib.request.Request(
            url + "/v1/generate", method="POST",
            data=json.dumps({"tokens": [row], "max_new_tokens": 40,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(request, timeout=300) as resp:
                for raw in resp:
                    if raw.startswith(b"data: ") and b'"token"' in raw:
                        tokens.append(json.loads(raw[6:])["token"])
        except Exception as exc:  # noqa: BLE001 — the test's subject
            errors.append(exc)

    try:
        with ServingServer("llama_tiny", seed=0, batching="continuous",
                           slots=4, kv="paged", page_size=4) as s:
            def poll():
                while not stop.is_set():
                    try:
                        samples.append(s.engine.stats())
                    except Exception as exc:  # noqa: BLE001
                        errors.append(exc)
                    time.sleep(0.002)

            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            clients = [threading.Thread(target=stream, args=(s.url, row))
                       for row in PROMPTS]
            for client in clients:
                client.start()
            for client in clients:
                client.join(timeout=300)
            stop.set()
            poller.join(timeout=30)
            after = s.engine.stats()
    finally:
        stop.set()
        serving_server.log_bucket = real
    return {"after": after, "samples": samples, "errors": errors,
            "tokens": tokens, "lags": lags}


def test_a_streamed_token_is_stamped_from_readback_to_socket(streamed):
    assert streamed["errors"] == []
    assert len(streamed["tokens"]) == 40 * len(PROMPTS)
    hist = streamed["after"]["deliver_lag_hist"]
    assert hist["first_edge_ms"] == 0.25 and hist["per_octave"] == 4
    assert len(hist["counts"]) == LOG_BUCKETS
    # One lag a write that brings a request level with its output: a
    # token written together with a later one has none. The stream's
    # end merges what is left, so nothing is lost to the merge's grain.
    assert sum(hist["counts"]) == len(streamed["lags"])
    assert 0.5 * 40 * len(PROMPTS) <= sum(hist["counts"]) <= 40 * len(PROMPTS)
    assert all(lag >= 0 for lag in streamed["lags"])
    assert max(streamed["lags"]) < 60e9


def test_stats_from_a_second_thread_while_the_handlers_merge(streamed):
    assert streamed["errors"] == []
    counts = [sum(s["deliver_lag_hist"]["counts"])
              for s in streamed["samples"]]
    assert len(counts) >= 5
    assert all(a <= b for a, b in zip(counts, counts[1:]))
    # merged every 32 lags and at a stream's end, never a token: what a
    # reader sees moves at most twice a stream of 40 tokens
    assert counts[-1] <= sum(
        streamed["after"]["deliver_lag_hist"]["counts"])
    moves = sum(1 for a, b in zip(counts, counts[1:]) if b > a)
    assert moves <= 2 * len(PROMPTS)
    assert serving_server._Handler.LAG_MERGE_EVERY == 32


def test_merges_from_more_threads_than_cores_lose_no_count(model):
    """Sixteen handlers' worth of merges against a reader, with the
    interpreter switching threads every 10 us: every count arrives."""
    import sys

    engine = _engine(model)
    engine.stop()
    threads, merges = 16, 200
    lags = [0] * LOG_BUCKETS
    lags[3], lags[40] = 2, 1
    seen, stop = [], threading.Event()

    def merge():
        for _ in range(merges):
            engine.merge_deliver_lags(lags)

    def read():
        while not stop.is_set():
            seen.append(sum(engine.stats()["deliver_lag_hist"]["counts"]))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read, daemon=True)
        reader.start()
        workers = [threading.Thread(target=merge) for _ in range(threads)]
        for worker in workers:
            worker.start()
        for worker in workers:
            worker.join(timeout=60)
        stop.set()
        reader.join(timeout=30)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not reader.is_alive()
    assert not any(worker.is_alive() for worker in workers)
    counts = engine.stats()["deliver_lag_hist"]["counts"]
    assert counts[3] == 2 * threads * merges
    assert counts[40] == threads * merges
    assert sum(counts) == 3 * threads * merges
    # a reader never sees part of a merge
    assert all(total % 3 == 0 for total in seen)
    assert all(a <= b for a, b in zip(seen, seen[1:]))
