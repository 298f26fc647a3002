"""Test bootstrap: force an 8-device virtual CPU mesh.

Tests run on the CPU: ``JAX_PLATFORMS=cpu`` and eight virtual host
devices, set before jax is imported (jax reads both from the
environment), with Pallas kernels in interpret mode. Multi-chip sharding
tests then run on 8 virtual CPU devices. What only the chip's compiler
can say is asked of it without a chip in tests/test_aot_tpu_compile.py,
and what only a chip can say, by chip_smoke.py on one.
"""

import os
import sys

# The package root, importable regardless of the invoking cwd (so
# harnesses like debug_fullsuite.sh can point pytest at this tree by
# absolute path from anywhere).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8-device virtual mesh + a collective watchdog sized for this
# oversubscribed 1-core host (utils/env.py has the full story; the
# helper never overrides operator-set flags).
from polyaxon_tpu.utils import cpu_mesh_xla_flags  # noqa: E402

cpu_mesh_xla_flags(8)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# NOTE: do NOT enable jax_compilation_cache_dir for this CPU-mesh suite.
# It was tried (4x warm-run speedup) and reverted: XLA:CPU persists AOT
# executables whose reload is unreliable on this host (cpu_aot_loader
# machine-feature mismatch warnings, then sharded executables hang at
# collective rendezvous until the 40s watchdog hard-aborts the whole
# pytest process). Reproduced deterministically on cache hits of the
# dp2xfsdp4 checkpoint tests, 2026-07-30.
#
# NOTE 2: `scripts/ci.sh --full` runs the suite as ONE pytest process
# (promoted to the default 2026-08-04 after the watchdog fix below
# validated green twice; VERDICT r5 #7). `--full-modules` keeps the
# old one-process-per-module loop as the crash-isolation fallback and
# `scripts/debug_fullsuite.sh` stays the diagnostic harness. History:
# hour-long single-process runs intermittently died with what looked
# like a segfault "inside backend_compile_and_load" (observed
# 2026-07-31 twice, with 120+ GB free — flaky, not test-correlated).
# Root cause IDENTIFIED 2026-08-01: XLA:CPU's collective rendezvous
# watchdog CHECK-aborts the process when any device thread misses a
# rendezvous for 40 s (`InProcessCommunicator::AllReduce` →
# `AwaitAndLogIfStuck` → "Termination timeout ... exceeded. Exiting to
# ensure a consistent program state") — reproduced standalone running
# a seq-16k sharded train step on this 1-core host, where 8 device
# threads + compile threads contend for one core and a straggler can
# easily starve >40 s. The SIGABRT's faulthandler dump shows the MAIN
# thread's Python stack (often mid-compile), which is why it
# masqueraded as a compiler segfault. Mitigation: the
# --xla_cpu_collective_call_terminate_timeout_seconds=600 flag above;
# per-module processes stay as defense in depth (scripts/
# debug_fullsuite.sh re-tests the single-process run under
# faulthandler + RSS sampling). VALIDATED 2026-08-01: with the raised
# watchdog the single-process suite ran green TWICE consecutively on
# this host (537 passed in 45:27, then 538 in 46:10) — it had never
# completed before; no crash, no core, peak RSS ~8 GB both runs.

import pytest  # noqa: E402

# ---------------------------------------------------------------- tiers
# Smoke tier: every subsystem's happy path in minutes, selected with
# `-m smoke` (the scripts/ci.sh default; `--full` runs everything).
# Whole modules here are cheap (pure-Python spec/control-plane layers,
# the C++ pool via ctypes); jax-heavy modules contribute only the
# curated representative nodes below. Centralized so the tier is tuned
# in one place instead of scattered markers.
SMOKE_MODULES = {
    "test_polyaxonfile.py", "test_polyflow.py", "test_compiler.py",
    "test_deploy.py", "test_connections.py", "test_fs.py", "test_cli.py",
    "test_api.py", "test_tracking.py", "test_schedules_cache.py",
    "test_joins_events.py", "test_sliced.py", "test_controlplane.py",
    "test_utils_env.py", "test_scheduling.py", "test_analysis.py",
    "test_oracle.py", "test_history.py",
    # Serving fleet (ISSUE 17): consistent-hash bounds, router decision
    # order, autoscaler state machine — fake engines, pure python (the
    # real-engine episode is the ci.sh fleet stage / gauntlet lane).
    "test_fleet.py",
}
SMOKE_NODES = (
    "test_models.py::TestLlama::test_forward_and_init_loss",
    "test_models.py::TestGemmaVariant::test_forward_and_init_loss",
    "test_models.py::TestT5::test_forward_and_init_loss",
    "test_models.py::TestEncoderModels",
    "test_models.py::TestRegistry",
    "test_ops.py::TestFlash::test_matches_reference",
    "test_ops.py::TestRing::test_matches_reference",
    "test_parallel.py::TestMesh",
    "test_parallel.py::TestRules",
    "test_parallel.py::TestBootstrap::test_env_contract",
    "test_runtime.py::TestData",
    "test_runtime.py::TestLmTextPacked::"
    "test_segments_follow_document_boundaries",
    "test_runtime.py::TestTrainLoop::test_loss_decreases",
    "test_prefetch.py::TestVectorizedGenerators",
    "test_prefetch.py::TestPrefetchIterator",
    "test_serving.py::TestServing::test_health_and_models",
    "test_serving.py::TestServing::test_generate_shapes_and_determinism",
    "test_serving.py::TestQuantize::test_static_serving_end_to_end_int8",
    "test_serving.py::TestQuantizeInLoop",
    "test_serving.py::TestLmLogitsChunked::test_pad_path",
    "test_ops.py::TestFlash::test_auto_blocks_pick",
    "test_ops.py::TestFlash::test_auto_blocks_committed_pick_table",
    "test_paged.py::TestPagedEngine::test_matches_dense_engine_greedy",
    "test_paged.py::TestPrefixCache::test_shared_prompt_pages_reused",
    # Suffix-bucket rounding math (ISSUE 12 satellite): pure python —
    # the compiling engine drill stays tier-1 only.
    "test_paged.py::TestSuffixBucketUnit",
    "test_speculative.py::TestSpeculative::test_lossless_vs_plain_greedy",
    "test_speculative.py::TestContinuousSpeculative::"
    "test_lossless_and_ragged_budgets",
    "test_lora.py::TestLoraWrapper::test_init_is_exactly_the_base_model",
    "test_moe_pp.py::TestMoE::test_ragged_matches_dense_no_drop_single_shard",
    "test_tune.py::TestOneShotManagers",
    "test_tune.py::TestHyperband::test_rung_shapes_paper_table",
    "test_convert_decode.py::TestDecode::test_decode_step_logits_match_forward",
    "test_acceptance.py::TestEstimate",
    # Communication audit: parser + budget-gate logic (pure python, no
    # compiles — the compiling golden tests are slow-tier and run in
    # the ci.sh audit stage / --full).
    "test_perf_audit.py::TestHloParse",
    "test_perf_audit.py::TestBudgetGate",
    # Overlap measurement (ISSUE 12): hand-computed window/ratio
    # fixtures + the overlap-floor gate (pure python — the compiling
    # pipeline-parity and AOT drills stay tier-1 / audit-stage).
    "test_perf_audit.py::TestOverlapParse",
    "test_perf_audit.py::TestOverlapBudgetGate",
    # Observability: span model + registry + timeline assembly, plus
    # the analysis plane (ISSUE 6) — quantile goldens, cardinality cap,
    # rule schema + fire/hysteresis/resolve lifecycle, flight-recorder
    # bounds/dump, and the report unit math (all pure python; the
    # jax-heavy e2e/chaos acceptance runs in the ci.sh obs stage and
    # the full tier).
    "test_obs.py::TestSpanModel",
    "test_obs.py::TestRegistry",
    "test_obs.py::TestTimelineBuild",
    "test_obs.py::TestHistogramQuantile",
    "test_obs.py::TestCardinalityCap",
    "test_obs.py::TestRuleSchema",
    "test_obs.py::TestRuleLifecycle",
    "test_obs.py::TestFlightRecorder",
    "test_obs.py::TestReportUnit",
    # Per-request serving observability (ISSUE 10): the span/ring/
    # summary scaffolding is pure python; the engine-driven burn drill
    # and the HTTP e2e run in the ci.sh obs stage and the full tier.
    "test_obs.py::TestRequestTraceUnit",
    # Fleet simulator: trace generation, synthetic-executor lifecycle,
    # budget-gate logic, and the per-tick query-count regression (pure
    # python + in-memory/tmp sqlite; the curve and day-trace runs are
    # the ci.sh sim stage / --full).
    "test_sim.py::TestTraces",
    "test_sim.py::TestSyntheticExecutor",
    "test_sim.py::TestBudgetGate",
    "test_sim.py::TestQueryCounts",
)


def _matches_node(nodeid: str, entry: str) -> bool:
    """Anchored at a node-ID component boundary: `entry` must be the
    whole id or be followed by '::' (class entry) / '[' (parametrized
    test) — bare-substring matching once let truncated entries pass
    and renames silently drop subsystems from the smoke gate."""
    prefix = f"tests/{entry}"
    return (nodeid == prefix
            or nodeid.startswith(prefix + "::")
            or nodeid.startswith(prefix + "["))


def pytest_collection_modifyitems(config, items):
    matched: set[str] = set()
    for item in items:
        fname = os.path.basename(str(item.fspath))
        hits = [n for n in SMOKE_NODES if _matches_node(item.nodeid, n)]
        if fname in SMOKE_MODULES or hits:
            item.add_marker(pytest.mark.smoke)
            matched.update(hits)
        if fname == "test_multiprocess_gang.py":
            item.add_marker(pytest.mark.gang)
        if fname == "test_chaos.py":
            # Fault-injection drills: selected as their own fixed-seed
            # CI stage (`-m chaos` in scripts/ci.sh) and part of tier-1.
            item.add_marker(pytest.mark.chaos)
        if fname == "test_scheduling.py":
            # Multi-tenant scheduling invariants (queues, quotas,
            # fair-share, preemption): deterministic + CPU-only, its
            # own `-m scheduling` stage in scripts/ci.sh.
            item.add_marker(pytest.mark.scheduling)
        if fname == "test_obs.py":
            # Observability: span/registry/timeline invariants + the
            # e2e and chaos-drill timelines — its own `-m obs` stage in
            # scripts/ci.sh, and part of tier-1.
            item.add_marker(pytest.mark.obs)
        if fname == "test_oracle.py":
            # Telemetry oracle + incident replay (ISSUE 13): invariant
            # goldens, rules-interplay, ring-dump round-trip, replay
            # determinism — rides the `-m obs` stage and is a smoke
            # module (the two-drain replay round-trip test carries the
            # `sim` marker on top for the sim-focused slice).
            item.add_marker(pytest.mark.obs)
        if fname == "test_history.py":
            # Temporal telemetry (ISSUE 15): the bounded metrics-
            # history ring, windowed-math goldens, the *_during /
            # quota_violation oracle kinds, and the history API/CLI —
            # rides the `-m obs` stage and the smoke tier.
            item.add_marker(pytest.mark.obs)
        if fname == "test_analysis.py":
            # Static-analysis gate (ISSUE 9): golden analyzer fixtures,
            # pragma/baseline semantics, CLI gate + injection
            # self-tests, and the runtime lockdep drills — pure python,
            # own `-m analysis` stage in scripts/ci.sh, whole module in
            # the smoke tier.
            item.add_marker(pytest.mark.analysis)
        if fname == "test_elastic.py":
            # Elastic gangs (ISSUE 14): shrink/regrow drills, resize
            # budget fallback, prewarm contract — its own `-m elastic`
            # stage in scripts/ci.sh, and part of tier-1.
            item.add_marker(pytest.mark.elastic)
        if fname == "test_sim.py":
            # Fleet simulator (ISSUE 8): traces, synthetic executor,
            # budget gate, query-count regressions — its own `-m sim`
            # stage in scripts/ci.sh; fast classes join the smoke tier
            # via SMOKE_NODES.
            item.add_marker(pytest.mark.sim)
    # A stale entry (renamed/deleted test) must fail collection loudly,
    # not silently shrink the default CI tier. Checked PER ENTRY: an
    # entry is stale only if its FILE was fully collected yet the node
    # didn't match — file/dir subsets stay runnable and renames in any
    # collected file are still caught. Explicit `::` node selections
    # and -k filters narrow WITHIN files, so the guard stands down for
    # those (a class-scoped run must not trip on its siblings).
    narrowed = (any("::" in str(arg) for arg in config.args)
                or bool(getattr(config.option, "keyword", "")))
    if not narrowed:
        collected = {os.path.basename(str(item.fspath)) for item in items}
        stale = {entry for entry in set(SMOKE_NODES) - matched
                 if entry.split("::", 1)[0] in collected}
        assert not stale, f"SMOKE_NODES entries match no test: {stale}"
    # SMOKE_MODULES gets the same guard: a renamed/deleted module must
    # fail loudly, not silently shrink the tier. Filesystem-based so it
    # holds for ANY collection subset (unlike the node guard, which
    # needs the file collected to judge).
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    ghost = {m for m in SMOKE_MODULES
             if not os.path.exists(os.path.join(tests_dir, m))}
    assert not ghost, f"SMOKE_MODULES name no file: {ghost}"


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture()
def tmp_store(tmp_path):
    """A throwaway artifacts-store root."""
    root = tmp_path / "store"
    root.mkdir()
    return str(root)
