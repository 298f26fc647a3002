#!/bin/sh
# CI sweep: Python suites (8-device virtual CPU mesh), native
# sanitizers, and the bench smoke contract.
#
# Default = the SMOKE tier (-m smoke: every subsystem's happy path,
# minutes not the full suite's ~40; tier curated in tests/conftest.py).
# Pass --full for the complete suite (pre-push / nightly).
set -e
cd "$(dirname "$0")/.."
# Static-analysis gate (ISSUE 9, docs/static-analysis.md): AST rules
# over polyaxon_tpu/** — lock-order inversions, locks held across
# blocking I/O, host syncs / wall clock / unseeded RNG in the step
# path, store writes outside transaction(), un-cataloged metrics,
# silent swallows, undrained daemon threads. Cheapest gate, so it runs
# first. New findings fail here; suppressions live AT THE SITE as
# reasoned `# polycheck: ignore[rule] -- why` pragmas (the committed
# baseline is empty and only shrinks).
echo "== polycheck (static analysis gate)"
python -m polyaxon_tpu.analysis --check
# The gate must be able to FAIL: each planted violation must flip
# --check to exit 1 (the --deopt / --inject-reshard self-test pattern)
# so a refactor that quietly breaks an analyzer fails the build.
if python -m polyaxon_tpu.analysis --check --inject-lock-inversion >/dev/null 2>&1; then
    echo "polycheck self-test FAILED: injected lock inversion passed the gate"
    exit 1
fi
if python -m polyaxon_tpu.analysis --check --inject-uncataloged-metric >/dev/null 2>&1; then
    echo "polycheck self-test FAILED: injected uncataloged metric passed the gate"
    exit 1
fi
python -m pytest tests/test_analysis.py -q -m 'not slow'
if [ "$1" = "--full" ]; then
    # Single-process full suite — the default since the XLA:CPU
    # collective-watchdog root cause was fixed and validated (two
    # consecutive green runs, tests/conftest.py NOTE 2; VERDICT r5 #7
    # promoted it). The old per-module loop survives below as
    # --full-modules: the crash-isolation fallback if a native
    # flake ever resurfaces (scripts/debug_fullsuite.sh remains the
    # diagnostic harness with faulthandler + RSS sampling).
    echo "== pytest (full, single process; --full-modules = per-module fallback)"
    python -m pytest tests/ -q
elif [ "$1" = "--full-modules" ]; then
    # Crash fallback: one pytest process per module bounds each
    # process's compile-cache/lifetime and isolates a native crash to
    # one module's rerun; accumulate failures instead of aborting at
    # the first failing module (set -e would otherwise mask later
    # modules' results).
    echo "== pytest (full, per-module processes)"
    rc=0
    failed=""
    for mod in tests/test_*.py; do
        echo "-- $mod"
        python -m pytest "$mod" -q || { rc=1; failed="$failed $mod"; }
    done
    if [ "$rc" -ne 0 ]; then
        echo "FAILED modules:$failed"
        exit "$rc"
    fi
else
    echo "== pytest (smoke tier; use --full for the whole suite)"
    python -m pytest tests/ -q -m smoke
fi
# Chaos stage: every fault plan is fixed-seed/counter-deterministic
# (tests/test_chaos.py), so this runs in tier-1 on every invocation —
# restart policies, store retries, checkpoint fallback, gang reaping,
# and serving load-shedding all exercised under injected faults.
echo "== chaos drills (fixed-seed fault plans)"
python -m pytest tests/test_chaos.py -q -m chaos
# Elastic-gang stage (ISSUE 14): chaos kills slices mid-train twice
# against the REAL agent/scheduler/runtime — the run must SUCCEED with
# both resizes (shrink then regrow) recorded as timeline spans and
# loss-curve continuity judged by the telemetry oracle; the
# budget-exhausted path must degrade cleanly to PREEMPTED → backoff
# requeue; the slow-marked prewarm-failure drills (induced PrewarmError
# on shrink and on grow) prove the fallback-to-requeue seam.
echo "== elastic gangs (shrink/regrow drills + prewarm fallbacks)"
python -m pytest tests/test_elastic.py -q -m elastic
# Multi-tier checkpointing stage (ISSUE 16): the cross-tier fallback
# ladder on the REAL TieredCheckpointManager — tier-0 hit, corrupt
# replica → local spill (with re-promotion), cheap tiers gone → store,
# all tiers corrupt at latest → older clean step — plus the atomic
# spill commit, the tier0-loss chaos seam, the restore-phase audit in
# the attribution report, and the acceptance timing claim (tier-0
# measurably beats the store round trip on the same checkpoint).
echo "== tiered checkpointing (fallback ladder + restore audit)"
python -m pytest tests/test_checkpoint_tiers.py -q
# Scheduling stage: multi-tenant admission invariants (queue priority,
# fair-share convergence, quota walls, bounded starvation, the
# preemption-for-priority drill) — deterministic and CPU-only.
echo "== scheduling invariants (queues/quotas/fair-share/preemption)"
python -m pytest tests/test_scheduling.py -q -m scheduling
# Host/device overlap stage: prefetch pipeline + vectorized generators
# on CPU — functional invariants (resume-exactness, drain-on-stop,
# per-(seed,i) determinism) plus the `perf`-marked relative-timing
# checks (prefetch-vs-sync throughput, compile-cache reuse).
echo "== input pipeline (prefetch/generators/compile-cache)"
python -m pytest tests/test_prefetch.py -q
# Alert-rule schema gate: the committed default ruleset
# (polyaxon_tpu/obs/rules.json) must load clean — unknown metric names
# (checked against the registry catalog), malformed windows, duplicate
# rule ids, bad kinds/ops all fail the build HERE, not as an alert
# that silently never fires in production.
echo "== obs rules (schema-validate the committed ruleset)"
python -c "from polyaxon_tpu.obs import rules; \
    raise SystemExit(rules._main(['--check']))"
# Telemetry-oracle schema gate (ISSUE 13): the committed invariant set
# (polyaxon_tpu/obs/oracle.json) must load clean — unknown kinds/ops,
# metric names outside the registry catalog, duplicate ids, bad
# quantiles/objectives all fail HERE, not as an invariant that
# silently never judges anything.
echo "== obs oracle (schema-validate the committed invariant set)"
python -c "from polyaxon_tpu.obs import oracle; \
    raise SystemExit(oracle._main(['--check']))"
# Observability stage: span/registry/timeline invariants plus the
# analysis plane (ISSUE 6) — alert-rule fire→hysteresis→resolve
# lifecycle, histogram_quantile goldens, label-cardinality cap,
# flight-recorder ring bounds + dump-on-FAILED — and the acceptance
# drills: an e2e jaxjob whose report's phase decomposition sums to the
# wall clock, and a chaos gauntlet that leaves a postmortem.json, a
# fired-then-resolved retry-storm alert, and an attributed report.
echo "== observability (spans / registry / rules / reports / flight)"
python -m pytest tests/test_obs.py tests/test_oracle.py -q -m obs
# Serving-request observability drill (ISSUE 10): concurrent streams
# against a real continuous server must leave queue→prefill→decode
# span timelines behind /requests/{id}/timeline, per-class TTFT/TPOT
# series on a line-parsed /metrics scrape, and shed-load accounting;
# the TTFT burn-rule fire→resolve episode rides the obs run above
# (TestServingObsDrill). The tracing-overhead parity check (on vs off
# within 5%) is slow-marked and runs under --full.
echo "== serving observability (request timelines / SLO series)"
python -m pytest "tests/test_serving.py::TestRequestObservability" -q
# Radix prefix-cache smoke (ISSUE 11): the real server under the
# shared-system-prompt mix, paged vs paged-nocache. --check-prefix
# fails the build unless the radix tree actually served prefill tokens
# (prefix_hit_rate > 0) AND the page refcount/CoW invariants came out
# clean after the run (kv_invariant_violations == 0) — a leak or
# double-free in the fork/evict/release lifecycle fails HERE, not as
# pool exhaustion hours into a soak.
echo "== radix prefix-cache smoke (hit rate + refcount invariants)"
JAX_PLATFORMS=cpu python scripts/bench_serve.py --model llama_tiny \
    --quick --workload shared-prefix --slots 2 --kv-page-size 8 \
    --configs paged,paged-nocache --check-prefix \
    --out /tmp/bench_serve_smoke.json
# Lane A/B smoke (ISSUE 18): interleaved vs disaggregated
# prefill/decode over the long-prompt-storm mix, paired per trial.
# --check-lanes fails the build unless pages actually moved
# prefill→decode (handoffs > 0), refcount invariants came out clean
# on BOTH arms, decode gap p99 stayed <= 1.15x interleaved (the
# whole point of the split), and prefill throughput held >= 0.90x
# (pacing, not starvation).
echo "== lane A/B smoke (disaggregated prefill/decode handoff)"
JAX_PLATFORMS=cpu python scripts/bench_serve.py --model llama_tiny \
    --quick --workload long-prompt-storm --slots 4 --kv-page-size 8 \
    --check-lanes --out /tmp/bench_serve_lanes.json
# The lane gate must be able to FAIL: zeroing the decode lane budget
# starves every request of its decode steps — nothing completes, and
# the run must exit 1.
if JAX_PLATFORMS=cpu python scripts/bench_serve.py --model llama_tiny \
    --quick --workload long-prompt-storm --slots 4 --kv-page-size 8 \
    --inject lane-starve --out /tmp/bench_serve_starve.json \
    >/dev/null 2>&1; then
    echo "lane self-test FAILED: a starved decode lane passed the gate"
    exit 1
fi
# Class-admission A/B (ISSUE 19): a thousand-plus concurrent
# mixed-class streams land on a slot-camped engine, three arms
# (interactive-only unloaded, class-aware admission + preemptive
# eviction, FIFO baseline). --check-classes fails the build unless
# interactive TTFT p99 stays <= 1.5x its unloaded value WITH
# best-effort preemptions > 0 (the policy actually fired), page
# refcount invariants clean on every arm, peak concurrency >= 1000,
# and the FIFO pair beaten (p99 lower, aggregate tok/s >= 0.90x).
echo "== class-admission A/B (thousand-stream preemption gate)"
JAX_PLATFORMS=cpu python scripts/bench_serve.py --model llama_tiny \
    --streams 1100 --check-classes --out /tmp/bench_serve_classes.json
# The class gate must be able to FAIL: disabling eviction leaves
# interactive TTFT at the natural-retirement wall with zero
# preemptions, and the run must exit 1.
if JAX_PLATFORMS=cpu python scripts/bench_serve.py --model llama_tiny \
    --streams 120 --check-classes --inject no-preempt \
    --out /tmp/bench_serve_nopreempt.json >/dev/null 2>&1; then
    echo "class self-test FAILED: disabled preemption passed the gate"
    exit 1
fi
# Fleet-sim stage (ISSUE 8): drive the REAL scheduler + admission +
# store through the quick load points (idle → storm, seconds not the
# full compressed day) and gate tick cost against
# polyaxon_tpu/sim/budgets.json — a refactor that reintroduces
# per-status scans or per-pass live rebuilds fails HERE on the
# deterministic per-tick query count, not at the next fleet incident.
# The module's fast tier (trace/budget/executor classes) rides along;
# full-curve and day-trace tests run under --full. Update budgets
# after an INTENTIONAL change: python -m polyaxon_tpu.sim
# --update-budgets.
echo "== fleet sim (control-plane tick budgets)"
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --quick --check --json '' >/dev/null
JAX_PLATFORMS=cpu python -m pytest tests/test_sim.py -q -m 'not slow'
# Mini-gauntlet (ISSUE 13): a compressed composed episode — low-prio
# train + preemptible tune churn + serving deploys + a preemption
# storm + a chaos plan — through the REAL scheduler/admission/store,
# judged EXCLUSIVELY by telemetry-oracle verdicts (obs/oracle.json):
# all runs terminal, phase accounting closes, zero unresolved alerts.
echo "== mini-gauntlet (oracle-judged fleet episode)"
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --gauntlet
# The oracle must be able to FAIL: suppressing the scheduler's
# preempted-run requeue path strands the storm's victims in PREEMPTED,
# and the all-runs-terminal invariant must flip the stage to exit 1.
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --gauntlet \
    --inject stuck-requeue >/dev/null 2>&1; then
    echo "gauntlet self-test FAILED: stuck requeues passed the oracle"
    exit 1
fi
# ...and so must the elastic lane: wedging resize completion strands
# the shrink mid-flight (resizing=True forever), and the oracle's
# all-runs-terminal invariant must flip the stage to exit 1.
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --gauntlet \
    --inject stuck-resize >/dev/null 2>&1; then
    echo "gauntlet self-test FAILED: stuck resize passed the oracle"
    exit 1
fi
# Cluster-day gauntlet (ISSUE 15): the compressed day — morning trace,
# Hyperband sweep lane, cron + DAG lanes, real-engine serving under
# continuous mixed-class traffic, store-fault chaos, and a MARKED
# mid-day preemption storm — judged exclusively by oracle verdicts,
# including metric_during (interactive serving p99 inside the storm
# window) and quota_violation (no sampled instant over quota). The
# full day profile is the slow-marked tier; CI runs the compressed
# form.
echo "== cluster-day gauntlet (window-scoped oracle verdicts)"
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --cluster-day --quick
# The quota invariant must be able to FAIL: bypassing admission's
# quota check while the limit gauges stay published must put sampled
# usage over the limit, and quota-violations-zero must flip the stage
# to exit 1.
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --cluster-day --quick \
    --inject quota-breach >/dev/null 2>&1; then
    echo "cluster-day self-test FAILED: quota breach passed the oracle"
    exit 1
fi
# The checkpoint ladder must DEGRADE, not fail: dropping the tier-0
# replica and local spill on every restore (tier0-loss chaos) forces
# the whole day onto the store tier — the day must still PASS (the
# tier-0 restore-budget anchor rightly skips: no tier-0 samples land).
echo "== cluster-day tier0-loss drill (store fallback must carry the day)"
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --cluster-day --quick \
    --inject tier0-loss >/dev/null
# ...and the commit protocol must be able to FAIL: wedging tier-1
# commits (tmp written, rename withheld) strands every gang behind an
# uncommitted checkpoint, and all-runs-terminal must flip to exit 1.
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --cluster-day --quick \
    --inject stuck-tier0-commit >/dev/null 2>&1; then
    echo "cluster-day self-test FAILED: wedged tier commits passed the oracle"
    exit 1
fi
# Incident replay (ISSUE 13): the committed preemption-storm
# postmortem converts deterministically into an arrival trace and
# replays through the real control plane; the oracle must see every
# run terminal and a clean alert board at the end.
echo "== incident replay (committed scenario, oracle-judged)"
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim \
    --replay polyaxon_tpu/sim/scenarios/preemption-storm.json >/dev/null
# ISSUE 16 companion scenario: a mid-storm preemption whose rerun
# found both cheap checkpoint tiers gone and walked the ladder to the
# store (budget floor breached, alert fired→resolved) — replayed
# against a loaded fleet, the oracle must still come back clean.
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim \
    --replay polyaxon_tpu/sim/scenarios/tier0-loss-during-storm.json >/dev/null
# ISSUE 17 companion scenario: an interactive traffic spike that
# drove a rule-fired scale-up (warm-standby promotion mid-spike) and
# a post-quiet drain + scale-down — replayed against a loaded fleet,
# the oracle must come back clean.
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim \
    --replay polyaxon_tpu/sim/scenarios/traffic-spike-scale.json >/dev/null
# Serving fleet (ISSUE 17): real-engine replicas behind the
# prefix-affinity router + SLO-driven autoscaler — spike traffic in a
# marked window, rule-fired warm-standby promotion, drain-before-
# release scale-down; judged by the telemetry oracle (interactive
# TTFT p99 inside the scale-up window) plus the fleet-wide prefix
# hit-rate floor and per-replica KV invariants.
echo "== serving fleet (prefix-affinity router + SLO autoscaler)"
JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --fleet-serve --quick
# The hit-rate gate must be able to FAIL: a router that round-robins
# (ignoring affinity AND the hash) sprays conversations across
# replicas; under the episode's deliberately tight per-replica KV
# budget every replica churns through everyone's prefixes and the
# fleet-wide hit rate collapses below the floor.
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --fleet-serve --quick \
    --inject route-blind >/dev/null 2>&1; then
    echo "fleet-serve self-test FAILED: blind routing passed the gate"
    exit 1
fi
# ...and so must the scale-up SLO: skipping prewarm leaves the
# promoted standby's jit caches empty, its first in-window requests
# eat the XLA compiles, and serving-ttft-during-scaleup must flip the
# stage to exit 1.
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --fleet-serve --quick \
    --inject cold-scale >/dev/null 2>&1; then
    echo "fleet-serve self-test FAILED: cold scale-up passed the TTFT oracle"
    exit 1
fi
# Fleet telemetry (ISSUE 20): every replica records through its own
# component-scoped registry view, the oracle judges the FEDERATED
# per-component series, and the coverage gate requires every replica
# that served to appear as a component. The red-team half: building
# one replica without its scoped view (it records unscoped — every
# aggregate SLO number still looks healthy) must flip the episode to
# exit 1 on federated-view coverage.
echo "== fleet telemetry (scoped views + federated coverage)"
if JAX_PLATFORMS=cpu python -m polyaxon_tpu.sim --fleet-serve --quick \
    --inject mute-replica >/dev/null 2>&1; then
    echo "fleet-telemetry self-test FAILED: muted replica passed the federated-view gate"
    exit 1
fi
# Communication-audit stage: compile every standard schedule's REAL
# train step on the 8-device virtual CPU mesh, census the collectives
# in the compiled HLO, and gate against polyaxon_tpu/perf/budgets.json
# — an accidental reshard (a rule-table typo, a manual schedule's spec
# gathering the batch) fails CI here instead of silently costing a
# multiple at the next measurement round. The module's fast tier
# (parser/gate/probe-containment) rides along; its slow-marked golden
# recompiles run under --full. Update budgets after an INTENTIONAL
# sharding change: python -m polyaxon_tpu.perf --update-budgets.
echo "== communication audit (collective budgets)"
python -m polyaxon_tpu.perf --check --json ''
python -m pytest tests/test_perf_audit.py -q -m 'not slow'
# Overlap-budget stage (ISSUE 12): compile the standard schedules
# against a TPU topology description with the latency-hiding scheduler
# pinned, measure each schedule's collective overlap_ratio from the
# scheduled HLO, and gate against the _overlap floors in
# perf/budgets.json — a knob/scheduler regression that re-serializes
# the fsdp all-gathers fails CI here, not at the next MFU measurement.
# Exit 3 = the probe itself found no workable topology (no TPU
# compiler on this host): recorded as a skip, not a red build. Update
# floors after an INTENTIONAL schedule change:
# python -m polyaxon_tpu.perf --audit --update-budgets.
echo "== overlap budget (async-collective latency hiding)"
overlap_rc=0
python -m polyaxon_tpu.perf --audit --check --json '' || overlap_rc=$?
if [ "$overlap_rc" -eq 3 ]; then
    echo "overlap budget: SKIPPED (no workable TPU topology on this host)"
elif [ "$overlap_rc" -ne 0 ]; then
    exit "$overlap_rc"
else
    # The gate must be able to FAIL: forcing the scheduler OFF must
    # flip --check to exit 1 (one schedule keeps the self-test cheap).
    if python -m polyaxon_tpu.perf --audit --check --schedules fsdp \
        --inject-serialize --json '' >/dev/null 2>&1; then
        echo "overlap self-test FAILED: serialized compile passed the gate"
        exit 1
    fi
fi
echo "== native ASan/UBSan"
make -C native sanitize
printf 'ADD a 4x4 0\nREQ r 2x2 0 0\nTICK 0 30\nQUIT\n' | ./native/build/sliced_san >/dev/null
echo "== native TSan stress"
make -C native tsan
TSAN_OPTIONS=halt_on_error=1 ./native/build/sliced_tsan
echo "== bench smoke"
# Contract check only (one JSON line, labelled platform=cpu): the
# control flow at a toy size. Measurements run on the chip
# (chip_smoke.py is the quickest proof the system still starts there).
JAX_PLATFORMS=cpu python bench.py --smoke
echo "CI OK"
