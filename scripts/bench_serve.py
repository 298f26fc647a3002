#!/usr/bin/env python
"""Serving engine load benchmark: tokens/sec and latency under
concurrent requests, across engine configs (dense / paged / +int8).

Drives the real HTTP surface (ServingServer) with N concurrent client
threads issuing mixed-length prompts, and reads /v1/stats occupancy so
the result shows WHY a config wins (slots busy vs admission-bound).
Writes bench_serve_results.json at the repo root.

Usage: python scripts/bench_serve.py [--model llama3_1b] [--clients 8]
       [--requests 32] [--max-new 64] [--slots 8] [--quick]
       [--workload mixed|shared-prefix|conversation-tree]
       [--configs paged,paged-nocache] [--check-prefix] [--fleet N]
CPU smoke: JAX_PLATFORMS=cpu ... --model llama_tiny --quick
Fleet A/B (ISSUE 17): --fleet N routes the workload through a
ServingFleet of N paged replicas twice — prefix-affinity router vs
blind round-robin — recording per-mode hit rate, p50 latency, and the
routed-reason breakdown.
Radix A/B (ISSUE 11): the paged vs paged-nocache rows + the top-level
`prefix_ab` block record prefill tokens skipped, hit rate, and the
interactive p50-TTFT dividend per workload.
Lane A/B (ISSUE 18): --workload long-prompt-storm drives short
interactive traffic against concurrent long prefills through the same
engine twice — interleaved vs disaggregated prefill/decode — recording
decode-step gap p99 and computed-prefill tokens/s per arm; the
`lane_ab` block carries the ratios --check-lanes gates on, and
--inject lane-starve is the must-fail self-test.
Class A/B (ISSUE 19): --streams N drives N concurrent mixed-class
streams (best-effort camps every slot first, then batch+interactive
land on a saturated engine) through three engine-level arms — an
interactive-only unloaded baseline, class-aware admission with
preemptive eviction, and the FIFO baseline (--no-class-admission) —
recording per-class TTFT/TPOT p50/p99, preemption/re-admission
counts, and aggregate tok/s; --check-classes gates on interactive
TTFT p99 ≤ 1.5x unloaded with preemptions > 0, invariants clean, and
the FIFO pair (p99 improves, tok/s ≥ 0.9x); --inject no-preempt is
the must-fail self-test.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def drive(url: str, prompts: list[list[int]], max_new: int,
          clients: int, klass: str = "interactive",
          timeout: float = 600) -> dict:
    """Fan the prompts over `clients` threads; returns latency stats."""
    lat: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    queue = list(enumerate(prompts))

    def worker():
        while True:
            with lock:
                if not queue:
                    return
                i, prompt = queue.pop()
            body = json.dumps({"tokens": [prompt], "max_new_tokens": max_new,
                               "seed": i, "class": klass}).encode()
            req = urllib.request.Request(
                url + "/v1/generate", method="POST", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    out = json.load(resp)
                assert len(out["tokens"][0]) == max_new
                with lock:
                    lat.append(time.perf_counter() - t0)
            except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                with lock:
                    errors.append(f"{type(exc).__name__}: {exc}"[:200])

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    n = len(lat)
    return {
        "wall_s": round(wall, 2),
        "completed": n,
        "errors": errors[:5],
        "tokens_per_sec": round(n * max_new / wall, 2) if wall else None,
        "latency_p50_s": round(lat[n // 2], 3) if n else None,
        "latency_p95_s": round(lat[int(n * 0.95)], 3) if n else None,
    }


def _stats(url: str) -> dict:
    return json.load(urllib.request.urlopen(url + "/v1/stats", timeout=10))


def _timeline_ttft_p50_ms(url: str, n: int):
    """Exact p50 TTFT (ms) over the last `n` requests, read from their
    span timelines — the SLO histograms answer the same question but
    at bucket resolution, too coarse for a CPU-scale A/B delta."""
    try:
        recent = json.load(urllib.request.urlopen(url + "/requests",
                                                  timeout=10))
    except Exception:  # noqa: BLE001 — static engine / tracing off
        return None
    ttfts = []
    for row in (recent.get("requests") or recent or [])[:n]:
        rid = row.get("request_id") if isinstance(row, dict) else None
        if not rid:
            continue
        try:
            tl = json.load(urllib.request.urlopen(
                f"{url}/requests/{rid}/timeline", timeout=10))
        except Exception:  # noqa: BLE001 — evicted from the ring
            continue
        ttft = (tl.get("summary") or {}).get("ttft_ms")
        if ttft is not None:
            ttfts.append(float(ttft))
    if not ttfts:
        return None
    ttfts.sort()
    return round(ttfts[len(ttfts) // 2], 3)


def _slo_percentiles() -> dict:
    """Per-class TTFT/TPOT p50/p99 straight from the in-process
    registry (ServingServer shares this process): the trajectory
    record item 1's per-class policies will be judged against."""
    from polyaxon_tpu.obs import metrics as obs_metrics

    out: dict[str, dict] = {}
    for stem, hist in (("ttft", obs_metrics.serving_ttft_hist()),
                       ("tpot", obs_metrics.serving_tpot_hist())):
        # Under a fleet each series key may carry a trailing replica
        # component ("interactive,r0"); the per-class numbers here are
        # the FEDERATED view, so strip to the base class and merge
        # every component's buckets.
        classes = {key.split(",")[0] for key in hist.snapshot()["series"]}
        for klass in classes:
            entry = out.setdefault(klass or "batch", {})
            for q, tag in ((0.5, "p50"), (0.99, "p99")):
                value = hist.quantile_merged(q, **{"class": klass})
                entry[f"{stem}_{tag}_s"] = (round(value, 4)
                                            if value is not None else None)
    return out


def run_config(name: str, model: str, prompts, max_new, clients,
               **server_kw) -> dict:
    import jax

    from polyaxon_tpu.obs import metrics as obs_metrics
    from polyaxon_tpu.serving import ServingServer

    print(f"→ {name} ...", flush=True)
    with ServingServer(model, batching="continuous", **server_kw) as s:
        # Warm EVERY distinct prompt-length's prefill compile (the
        # engine jits per exact length) outside the timed window —
        # otherwise the timed run measures XLA compile, not serving.
        # This also warms the prefix cache: the timed numbers describe
        # steady-state serving of a repeated-prefix workload.
        seen: dict[int, list[int]] = {}
        for p in prompts:
            seen.setdefault(len(p), p)
        # Twice: the first pass populates the radix tree and compiles
        # the monolithic prefills; the SECOND pass re-admits against a
        # warm tree and compiles the suffix-prefill programs the timed
        # window will actually run (per distinct suffix length).
        drive(s.url, list(seen.values()), max_new, clients=2)
        drive(s.url, list(seen.values()), max_new, clients=2)
        # The warm-up polluted the SLO histograms (compile-dominated
        # TTFTs): reset so the per-class percentiles describe the
        # timed window only. Accessor-style recorders re-create their
        # families on next touch, so the engine keeps recording.
        obs_metrics.REGISTRY.reset()
        before = _stats(s.url)
        result = drive(s.url, prompts, max_new, clients)
        after = _stats(s.url)
        slo_by_class = _slo_percentiles()
        ttft_exact = _timeline_ttft_p50_ms(s.url, len(prompts))
    # Timed-window deltas (the raw gauges are lifetime counters).
    occupancy = None
    dsteps = (after.get("decode_steps") or 0) - (before.get("decode_steps") or 0)
    if dsteps > 0 and after.get("avg_occupancy") is not None:
        live = (after["avg_occupancy"] * after["decode_steps"]
                - (before["avg_occupancy"] or 0) * before["decode_steps"])
        occupancy = round(live / dsteps, 4)
    row = {"name": name, **result, "avg_occupancy": occupancy,
           # Comparable across pod sizes: per-chip normalization +
           # per-class SLO numbers.
           "tokens_per_sec_per_chip": (
               round(result["tokens_per_sec"] / jax.device_count(), 2)
               if result["tokens_per_sec"] is not None else None),
           "slo_by_class": slo_by_class,
           "ttft_p50_ms": ttft_exact,
           "rejected": after.get("rejected") or {}}
    if after.get("spec_rounds") is not None:
        row["spec_tokens_per_round"] = after.get("spec_tokens_per_round")
    if after.get("kv_prefix_hits") is not None:
        row["kv_prefix_hits"] = (after["kv_prefix_hits"]
                                 - before["kv_prefix_hits"])
        row["kv_prefix_misses"] = (after["kv_prefix_misses"]
                                   - before["kv_prefix_misses"])
    if after.get("prefill_tokens_total") is not None:
        # Radix prefix-reuse dividend over the TIMED window only.
        total = (after["prefill_tokens_total"]
                 - (before.get("prefill_tokens_total") or 0))
        skipped = (after["prefill_tokens_skipped"]
                   - (before.get("prefill_tokens_skipped") or 0))
        row["prefill_tokens_total"] = total
        row["prefill_tokens_skipped"] = skipped
        row["prefix_hit_rate"] = (round(skipped / total, 4)
                                  if total else None)
        row["kv_cow_forks"] = (after.get("kv_cow_forks") or 0) - (
            before.get("kv_cow_forks") or 0)
        row["kv_prefix_evictions"] = (
            (after.get("kv_prefix_evictions") or 0)
            - (before.get("kv_prefix_evictions") or 0))
        # Headroom: free pages INCLUDE resident-but-unreferenced radix
        # pages (reclaimable on demand) — the cache costs no capacity.
        radix = after.get("kv_radix") or {}
        row["kv_pages_total"] = after.get("kv_pages_total")
        row["kv_pages_free"] = after.get("kv_pages_free")
        row["kv_pages_headroom_reclaimable"] = max(
            (radix.get("resident") or 0) - (radix.get("referenced") or 0), 0)
        row["kv_invariant_violations"] = after.get("kv_invariant_violations")
    print(f"  {name}: {result['tokens_per_sec']} tok/s, "
          f"p50 {result['latency_p50_s']}s, "
          f"occupancy {row['avg_occupancy']}", flush=True)
    return row


def make_prompts(workload: str, requests: int, prompt_len: int,
                 rng) -> list[list[int]]:
    """The three serving mixes the radix cache is judged against.

    - ``mixed``: half the requests share one system prompt, half are
      cold — the honest production blend.
    - ``shared-prefix``: EVERY request is system-prompt + short user
      turn — the workload prefix caching exists for (the acceptance
      trace: >= 40% of prefill tokens skipped).
    - ``conversation-tree``: multi-turn chats forking from shared
      histories at non-page-aligned points — exercises radix splits
      and copy-on-write forks, not just whole-page adoption.
    """
    if workload == "mixed":
        sys_prefix = [rng.randrange(100) for _ in range(prompt_len // 2)]
        prompts = []
        for i in range(requests):
            tail_len = rng.randrange(4, max(prompt_len // 2, 5))
            tail = [rng.randrange(100) for _ in range(tail_len)]
            prompts.append((sys_prefix + tail) if i % 2 == 0 else
                           ([rng.randrange(100) for _ in range(8)] + tail))
        return prompts
    if workload == "shared-prefix":
        sys_prefix = [rng.randrange(100)
                      for _ in range(max(prompt_len * 3 // 4, 8))]
        return [sys_prefix + [rng.randrange(100) for _ in range(
                    rng.randrange(4, max(prompt_len // 4, 5)))]
                for _ in range(requests)]
    if workload == "conversation-tree":
        # A branching tree of token blocks; each request's prompt is a
        # root→node path (a chat history). Block length is NOT a page
        # multiple, so sibling branches diverge mid-page.
        block = max(prompt_len // 8, 3)
        paths = [[rng.randrange(100) for _ in range(block * 2)]]  # root
        prompts: list[list[int]] = []
        while len(prompts) < requests:
            parent = paths[rng.randrange(len(paths))]
            child = parent + [rng.randrange(100) for _ in range(block)]
            if len(child) <= prompt_len * 2:
                paths.append(child)
            prompts.append(list(child))
        return prompts
    raise ValueError(f"unknown workload {workload!r}")


def make_storm_prompts(requests: int, prompt_len: int, rng,
                       trials: int = 3):
    """The ``long-prompt-storm`` mix (ISSUE 18): a stream of short
    interactive prompts plus concurrent LONG prompts whose prefills
    ARE the storm. Returns ``(warm_rows, trial_sets)``: one
    ``(short, long)`` prompt pair per timed trial, all disjoint.

    One fixed length per class and a distinct first token per prompt
    (across warm AND every trial — each admission is a radix miss)
    keep both arms replaying the same warm skip=0 programs, so the
    A/B measures *scheduling*, not XLA compiles or cache luck. The
    trials exist because a single sub-second window on a busy CPU is
    one tick of noise away from any throughput ratio — the gate reads
    the per-trial median."""
    short_len = max(prompt_len // 4, 6)
    long_len = prompt_len * 2
    n_short = max(requests, 12)
    n_long = max(requests // 2, 8)
    counter = iter(range(1_000_000))

    def mk(length: int) -> list[int]:
        return ([next(counter) % 250]
                + [rng.randrange(100) for _ in range(length - 1)])

    warm_rows = [mk(short_len), mk(long_len)]
    trial_sets = [([mk(short_len) for _ in range(n_short)],
                   [mk(long_len) for _ in range(n_long)])
                  for _ in range(trials)]
    return warm_rows, trial_sets


def _run_storm(eng, short, long_rows, max_new, clients,
               timeout) -> tuple:
    """One timed storm trial against one engine: short interactive
    traffic and long batch prefills drive it concurrently. Returns
    ``(wall_seconds, completed, errors)``."""
    completed = 0
    errors: list[str] = []
    lock = threading.Lock()

    def _drive(rows, klass):
        nonlocal completed
        for prompt in rows:
            try:
                req = eng.submit(prompt, max_new, klass=klass)
                out = req.wait(timeout=timeout)
                assert len(out) == max_new
                with lock:
                    completed += 1
            except Exception as exc:  # noqa: BLE001 — recorded
                with lock:
                    errors.append(f"{type(exc).__name__}: {exc}"[:200])

    # >= 2 clients per class: a one-client "storm" serializes its own
    # prefills and measures chunk-pacing latency, not lane throughput
    # — the disaggregation trade only exists under concurrency.
    nc = max(clients // 2, 2)
    threads = ([threading.Thread(target=_drive, daemon=True,
                                 args=(long_rows[i::nc], "batch"))
                for i in range(nc)]
               + [threading.Thread(target=_drive, daemon=True,
                                   args=(short[i::nc], "interactive"))
                  for i in range(nc)])
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, completed, errors


def _median(values):
    if not values:
        return None
    ordered = sorted(values)
    return ordered[len(ordered) // 2]


def run_lane_ab(arms, model: str, warm_rows, trial_sets, max_new,
                clients, *, warm: bool = True,
                timeout: float = 600) -> list:
    """Run the lane A/B *paired*: every arm's engine is built and
    warmed up front, then each trial's prompt set runs back-to-back on
    every arm before the next trial starts. Each engine has its own
    radix tree, so the same prompts are a fresh skip=0 storm on every
    arm — identical inputs, near-identical machine conditions. The
    gate downstream reads the median of PER-TRIAL ratios, which
    cancels the slow cross-minute CPU drift that made sequential
    whole-arm runs flap.

    Per (arm, trial) the metrics registry is reset so the decode-gap
    histogram (``polyaxon_serving_decode_tpot_seconds``) holds exactly
    that trial's observations; idle engines record nothing (the gap
    clock parks on idle), so arms can't pollute each other.

    Engine-level, no HTTP (the run_fleet posture): the A/B compares
    SCHEDULERS, and on a CPU box the HTTP stack's queueing jitter is
    the same order of magnitude as the per-tick effect under test."""
    from polyaxon_tpu.obs import metrics as obs_metrics
    from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
    from polyaxon_tpu.serving.server import load_params

    cfg, params = load_params(model, seed=0)
    engines = []
    try:
        for name, kw in arms:
            kw = dict(kw)
            if "kv_pages" not in kw:
                # Equal-memory A/B: lane rows should add BLOCK-TABLE
                # rows, not pool capacity — both arms get the decode
                # pool's dense-equivalent page budget. Without this
                # the disaggregated arm's default pool is
                # (slots+prefill_slots)/slots times larger, and on CPU
                # every decode step pays for the bigger buffers — the
                # ratio would measure memory, not scheduling.
                kw["kv_pages"] = (kw.get("slots", 4)
                                  * (cfg.max_seq_len
                                     // kw.get("page_size", 16)))
            engines.append(
                (name, ContinuousBatchingEngine(model, cfg, params,
                                                **kw)))
        acc = {name: {"tps": [], "gaps": [], "completed": 0,
                      "expected": 0, "errors": [], "computed": 0,
                      "wall": 0.0, "slo": None}
               for name, _ in engines}
        if warm:
            # One pass per length class compiles every program the
            # timed trials will run (storm prompts carry distinct
            # first tokens, so re-admissions replay these skip=0
            # shapes instead of discovering suffix shapes mid-storm).
            for name, eng in engines:
                print(f"→ warming {name} ...", flush=True)
                for prompt in warm_rows:
                    eng.generate([prompt], max_new_tokens=max_new)
        for trial, (short, long_rows) in enumerate(trial_sets):
            for name, eng in engines:
                a = acc[name]
                obs_metrics.REGISTRY.reset()
                before = eng.stats()
                wall, completed, errors = _run_storm(
                    eng, short, long_rows, max_new, clients, timeout)
                after = eng.stats()
                computed = (
                    (after.get("prefill_tokens_total") or 0)
                    - (before.get("prefill_tokens_total") or 0)
                    - ((after.get("prefill_tokens_skipped") or 0)
                       - (before.get("prefill_tokens_skipped") or 0)))
                a["computed"] += computed
                a["wall"] += wall
                a["completed"] += completed
                a["expected"] += len(short) + len(long_rows)
                a["errors"].extend(errors)
                if wall:
                    a["tps"].append(computed / wall)
                gap = obs_metrics.serving_decode_tpot_hist() \
                    .quantile(0.99)
                if gap is not None:
                    a["gaps"].append(gap)
                # Snapshot is per-trial (registry was just reset), so
                # this ends up holding the LAST trial's class SLOs —
                # a representative sample, not a pooled aggregate.
                a["slo"] = _slo_percentiles()
    finally:
        for _, eng in engines:
            eng.stop()
    rows = []
    for name, eng in engines:
        a = acc[name]
        final = eng.stats()
        gap_med = _median(a["gaps"])
        tps_med = _median(a["tps"])
        row = {
            "name": name,
            "trials": len(trial_sets),
            "wall_s": round(a["wall"], 2),
            "completed": a["completed"],
            "expected": a["expected"],
            "errors": a["errors"][:5],
            # THE decode-lane number: p99 wall gap between consecutive
            # decode steps, including whatever prefill work the
            # scheduler let land in between (median over trials).
            "decode_gap_p99_s": (round(gap_med, 4)
                                 if gap_med is not None else None),
            "decode_gap_p99_s_trials": [round(g, 4)
                                        for g in a["gaps"]],
            "prefill_tokens_computed": a["computed"],
            "prefill_tokens_per_sec": (round(tps_med, 1)
                                       if tps_med is not None
                                       else None),
            "prefill_tokens_per_sec_trials": [round(t, 1)
                                              for t in a["tps"]],
            "slo_by_class": a["slo"],
            "kv_invariant_violations":
                final.get("kv_invariant_violations"),
        }
        if final.get("handoffs") is not None:
            row["handoffs"] = final["handoffs"]
            row["handoff_pages"] = final["handoff_pages"]
        print(f"  {name}: decode gap p99 {row['decode_gap_p99_s']}s, "
              f"prefill {row['prefill_tokens_per_sec']} tok/s (median "
              f"of {len(a['tps'])} trials), completed "
              f"{row['completed']}/{row['expected']}", flush=True)
        rows.append(row)
    return rows


def _paired_ratio(num_trials, den_trials):
    """Median of per-trial ratios — the paired statistic the lane gate
    reads. Falls back to None when a trial pair is missing/zero."""
    ratios = [n / d for n, d in zip(num_trials, den_trials) if d]
    med = _median(ratios)
    return round(med, 3) if med is not None else None


def run_lanes(args) -> int:
    """The ``--workload long-prompt-storm`` path: interleaved vs
    disaggregated over the same storm, plus the ``lane-starve``
    red-team arm (decode budget zeroed → nothing completes → exit 1,
    which ci.sh inverts)."""
    import random

    import jax

    rng = random.Random(0)
    warm_rows, trial_sets = make_storm_prompts(args.requests,
                                               args.prompt_len, rng,
                                               trials=5)
    base = dict(slots=args.slots, kv="paged", page_size=args.kv_page_size)
    # Chunk sizing is the fairness/throughput dial: 4 pages per chunk
    # keeps each lane program well under a monolithic long prefill
    # (the decode-gap ceiling) without paying per-tick overhead per
    # page, and 2 chunks/tick keeps lane throughput at parity while
    # decode rows are live.
    chunk = max(6 * args.kv_page_size, 48)
    disagg_kw = dict(prefill_slots=4, prefill_chunk=chunk,
                     prefill_lane_budget=3, decode_lane_budget=2,
                     **base)
    if args.inject == "lane-starve":
        # No warm pass: nothing ever completes under a zeroed decode
        # budget, so warming would just burn a full timeout. One trial
        # is enough — the arm exists to prove it CANNOT complete.
        rows = run_lane_ab(
            [("disaggregated-starved",
              dict(prefill_slots=2, prefill_chunk=chunk,
                   decode_lane_budget=0, **base))],
            args.model, warm_rows, trial_sets[:1], args.max_new,
            args.clients, warm=False, timeout=5)
    else:
        rows = run_lane_ab(
            [("interleaved", dict(base)),
             ("disaggregated", disagg_kw)],
            args.model, warm_rows, trial_sets, args.max_new,
            args.clients)
    by_name = {r["name"]: r for r in rows}
    out = {
        "backend": jax.devices()[0].platform,
        "model": args.model, "workload": "long-prompt-storm",
        "load": {"clients": args.clients, "requests": args.requests,
                 "max_new": args.max_new, "slots": args.slots,
                 "prompt_len": args.prompt_len,
                 "kv_page_size": args.kv_page_size,
                 "prefill_slots": disagg_kw["prefill_slots"],
                 "prefill_chunk": chunk,
                 "prefill_lane_budget":
                     disagg_kw["prefill_lane_budget"],
                 "decode_lane_budget": disagg_kw["decode_lane_budget"],
                 "inject": args.inject},
        "results": rows,
    }
    inter = by_name.get("interleaved")
    disagg = by_name.get("disaggregated")
    if inter is not None and disagg is not None:
        gi, gd = inter["decode_gap_p99_s"], disagg["decode_gap_p99_s"]
        pi = inter["prefill_tokens_per_sec"]
        pd = disagg["prefill_tokens_per_sec"]
        out["lane_ab"] = {
            "decode_gap_p99_s_interleaved": gi,
            "decode_gap_p99_s_disaggregated": gd,
            # Paired statistics: per-trial ratio (same prompts, same
            # machine minute, both engines), median over trials. The
            # pooled medians above are reported for eyeballs; the GATE
            # reads these.
            "decode_gap_p99_ratio": _paired_ratio(
                disagg["decode_gap_p99_s_trials"],
                inter["decode_gap_p99_s_trials"]),
            "prefill_tokens_per_sec_interleaved": pi,
            "prefill_tokens_per_sec_disaggregated": pd,
            "prefill_throughput_ratio": _paired_ratio(
                disagg["prefill_tokens_per_sec_trials"],
                inter["prefill_tokens_per_sec_trials"]),
            "handoffs": disagg.get("handoffs"),
            "handoff_pages": disagg.get("handoff_pages"),
        }
        print(f"lane A/B: decode gap p99 {gd}s disaggregated vs {gi}s "
              f"interleaved (ratio "
              f"{out['lane_ab']['decode_gap_p99_ratio']}), prefill "
              f"{pd} vs {pi} tok/s (ratio "
              f"{out['lane_ab']['prefill_throughput_ratio']})",
              flush=True)
    path = args.out or os.path.join(REPO, "bench_serve_results.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"wrote {path}")
    incomplete = [r["name"] for r in rows
                  if r["completed"] < r["expected"]]
    if incomplete:
        print(f"ERROR: configs with failed requests: {incomplete} "
              "(see errors in the JSON)", file=sys.stderr)
        return 1
    if args.check_lanes:
        if inter is None or disagg is None:
            print("ERROR: --check-lanes needs both A/B arms",
                  file=sys.stderr)
            return 1
        ab = out["lane_ab"]
        failures = []
        if not (disagg.get("handoffs") or 0) > 0:
            failures.append("no prefill→decode page handoffs happened")
        for r in (inter, disagg):
            if r["kv_invariant_violations"] != 0:
                failures.append(
                    f"{r['name']}: {r['kv_invariant_violations']} page "
                    "refcount invariant violations")
        ratio = ab["decode_gap_p99_ratio"]
        if ratio is None or ratio > 1.15:
            failures.append(
                f"decode gap p99 ratio {ratio} > 1.15 — the prompt "
                "storm is occupying ticks the decode batch needed")
        # 0.90, not parity: pacing prefill behind a per-tick budget is
        # the POINT of the lane split — it deliberately trades a few
        # percent of prefill throughput (lane bookkeeping + handoff +
        # chunk pacing, ~5% observed on the CPU sim) for a >10x
        # decode-gap improvement under the storm. The gate catches
        # starvation (budget bugs collapse this ratio toward 0), not
        # the designed trade.
        tput = ab["prefill_throughput_ratio"]
        if tput is None or tput < 0.90:
            failures.append(
                f"prefill throughput ratio {tput} < 0.90 — the lane "
                "split is starving prefill instead of pacing it")
        if failures:
            for f in failures:
                print(f"ERROR: {f}", file=sys.stderr)
            return 1
        print(f"lane check ok: decode gap ratio {ratio}, prefill "
              f"throughput ratio {tput}, "
              f"{disagg['handoffs']} handoffs, invariants clean")
    return 0


def run_fleet(model: str, prompts: list[list[int]], max_new: int,
              clients: int, *, replicas: int, slots: int,
              page_size: int, blind: bool) -> dict:
    """Drive the workload through a ServingFleet (router + replicas,
    no HTTP — the fleet front door is engine-level). The affinity vs
    blind pair is the fleet A/B: same replicas, same pool, only the
    routing discipline differs."""
    from polyaxon_tpu.obs import metrics as obs_metrics
    from polyaxon_tpu.serving.fleet import ServingFleet, engine_factory
    from polyaxon_tpu.serving.router import FleetRouter

    fleet = ServingFleet(
        engine_factory(model, slots=slots, kv="paged",
                       page_size=page_size),
        replicas=replicas, standby=0, min_replicas=1,
        max_replicas=replicas,
        router=FleetRouter(blind=blind), warmup_rows=[prompts[0]])
    fleet.start()
    # start() drove the warm-up row through every replica (compile
    # churn): reset so the SLO percentiles describe the timed window
    # only. run_config has done this since the radix A/B; the fleet
    # path shipped without it, so its per-class numbers silently
    # included warm-up compiles.
    obs_metrics.REGISTRY.reset()
    lat: list[float] = []
    lock = threading.Lock()
    queue = list(prompts)
    t0 = time.monotonic()
    try:
        def worker():
            while True:
                with lock:
                    if not queue:
                        return
                    row = queue.pop()
                start = time.monotonic()
                req, _ = fleet.submit(row, max_new, klass="interactive")
                req.wait(timeout=300)
                with lock:
                    lat.append(time.monotonic() - start)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        stats = fleet.stats()
        # Per-replica breakdown from the component-scoped series
        # (ISSUE 20): which replica served how much, at what TTFT,
        # evicting how often — the routing A/B's per-node evidence.
        per_replica = fleet.per_replica_telemetry()
        for rid, row in per_replica.items():
            row["served"] = (stats["replicas"].get(rid)
                             or {}).get("served", 0)
    finally:
        fleet.stop()
    lat.sort()
    return {
        "name": "fleet-blind" if blind else "fleet-affinity",
        "replicas": replicas, "completed": len(lat),
        "wall_seconds": round(wall, 3),
        "latency_p50_ms": (round(lat[len(lat) // 2] * 1e3, 1)
                           if lat else None),
        # Post-reset per-class percentiles: timed window only.
        "slo_by_class": _slo_percentiles(),
        "prefix_hit_rate": stats["prefix_hit_rate"],
        "prefill_tokens_skipped": stats["prefill_tokens_skipped"],
        "kv_invariant_violations": stats["kv_invariant_violations"],
        "routed": stats["router"]["routed"],
        "per_replica": per_replica,
    }


def make_stream_specs(streams: int, rng) -> list:
    """(klass, tokens, max_new) per stream for the --streams harness.

    70% best-effort / 20% batch / 10% interactive — the shape the
    admission catalog was designed for: a deep well of preemptible
    bulk work, a mid-tier, and a thin latency-critical stream. Each
    class draws a 4-token family prefix from a small pool (radix
    hotness is a live rank input, so the workload must have some) and
    a unique suffix (so prompts are distinct streams, not replays).
    max_new is the pressure dial: best-effort decodes long enough to
    wall every slot, interactive is a handful of tokens whose latency
    is entirely admission-bound."""
    n_int = max(streams // 10, 8)
    n_batch = max(streams // 5, 8)
    n_be = max(streams - n_int - n_batch, 8)
    shapes = {"best-effort": (n_be, 12, 48), "batch": (n_batch, 16, 8),
              "interactive": (n_int, 8, 4)}
    fams = {k: [[rng.randrange(2, 250) for _ in range(4)]
                for _ in range(8)] for k in shapes}
    specs = []
    for klass, (count, plen, max_new) in shapes.items():
        for i in range(count):
            prefix = fams[klass][i % len(fams[klass])]
            suffix = [rng.randrange(2, 250) for _ in range(plen - 4)]
            specs.append((klass, prefix + suffix, max_new))
    return specs


def _exact_pct(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(int(len(sorted_vals) * q), len(sorted_vals) - 1)
    return sorted_vals[idx]


def _run_stream_arm(name: str, model: str, cfg, params, specs, *,
                    slots: int, page_size: int, class_admission: bool,
                    preemption: bool = True, rng=None,
                    timeout: float = 1800) -> dict:
    """One arm of the thousand-stream A/B: every best-effort stream is
    submitted first and the engine runs until all slots are decoding
    (the camped-full posture the admission policy exists for), THEN
    the batch+interactive mix lands on the saturated engine all at
    once. TTFT is exact per request (submit → first emission, which
    spans any preemptions — an evicted-then-readmitted victim's clock
    restarts, see batching._evict_slot); TPOT rides along bucketed in
    slo_by_class. Engine-level, no HTTP, same rationale as
    run_lane_ab: the A/B compares ADMISSION POLICIES."""
    from polyaxon_tpu.obs import metrics as obs_metrics
    from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

    print(f"→ {name}: {len(specs)} streams ...", flush=True)
    engine = ContinuousBatchingEngine(
        model, cfg, params, slots=slots, kv="paged",
        page_size=page_size, class_admission=class_admission,
        preemption=preemption)
    campers = [s for s in specs if s[0] == "best-effort"]
    rest = [s for s in specs if s[0] != "best-effort"]
    if rng is not None:
        rng.shuffle(rest)
    try:
        # Compile every prompt-length's prefill outside the timed
        # window (token 1 prefix: disjoint from the spec prompts, so
        # the radix tree stays cold for the measured streams).
        for length in sorted({len(t) for _, t, _ in specs}):
            engine.generate([[1] * length], max_new_tokens=2)
        obs_metrics.REGISTRY.reset()
        reqs = []
        for klass, toks, max_new in campers:
            reqs.append((klass, engine.submit(toks, max_new,
                                              klass=klass)))
        deadline = time.monotonic() + 120
        while (engine.health()["decode_active"] < slots
               and time.monotonic() < deadline):
            time.sleep(0.01)
        t0 = time.monotonic()
        peak = len([1 for _, r in reqs if not r.done.is_set()])
        for klass, toks, max_new in rest:
            in_flight = sum(1 for _, r in reqs
                            if not r.done.is_set()) + 1
            peak = max(peak, in_flight)
            reqs.append((klass, engine.submit(toks, max_new,
                                              klass=klass)))
        for _, r in reqs:
            r.wait(timeout=timeout)
        wall = time.monotonic() - t0
        stats = engine.stats()
    finally:
        engine.stop()
    ttft: dict[str, list[float]] = {}
    for klass, r in reqs:
        if r.first_token_at is not None:
            ttft.setdefault(klass, []).append(
                r.first_token_at - r.submitted_at)
    per_class = {}
    for klass, vals in ttft.items():
        vals.sort()
        per_class[klass] = {
            "requests": len(vals),
            "ttft_p50_s": round(_exact_pct(vals, 0.5), 4),
            "ttft_p99_s": round(_exact_pct(vals, 0.99), 4),
        }
    completed = sum(1 for _, r in reqs
                    if r.done.is_set() and not r.error)
    return {
        "name": name, "streams": len(specs),
        "streams_in_flight_peak": peak,
        "completed": completed, "wall_s": round(wall, 2),
        "tokens_per_sec": round(stats["tokens_generated"] / wall, 1)
        if wall else None,
        "per_class_ttft": per_class,
        "slo_by_class": _slo_percentiles(),
        "preemptions": stats.get("preemptions", {}),
        "readmit_suffix_tokens": stats.get("readmit_suffix_tokens", 0),
        "kv_invariant_violations": stats.get("kv_invariant_violations"),
    }


def run_streams(args) -> int:
    """The ``--streams N`` path (ISSUE 19): class-aware admission +
    preemptive eviction judged under N concurrent mixed-class streams,
    paired against the FIFO baseline, with an interactive-only
    unloaded pass as the TTFT yardstick. ``--inject no-preempt`` runs
    the class arm with eviction disabled — interactive TTFT climbs to
    the natural-retirement wall and preemptions stay 0, so the gate
    MUST exit 1 (ci.sh inverts this as the red-team self-test)."""
    import random

    import jax

    from polyaxon_tpu.serving.server import load_params

    streams = args.streams
    if args.quick:
        streams = min(streams, 64)
    rng = random.Random(0)
    specs = make_stream_specs(streams, rng)
    unloaded_specs = [s for s in specs if s[0] == "interactive"]
    cfg, params = load_params(args.model, seed=0)
    kw = dict(slots=args.slots, page_size=args.kv_page_size)
    results = [_run_stream_arm(
        "unloaded-interactive", args.model, cfg, params,
        unloaded_specs, class_admission=True, **kw)]
    if args.inject == "no-preempt":
        results.append(_run_stream_arm(
            "class-admission-no-preempt", args.model, cfg, params,
            specs, class_admission=True, preemption=False,
            rng=random.Random(1), **kw))
    else:
        if not args.no_class_admission:
            results.append(_run_stream_arm(
                "class-admission", args.model, cfg, params, specs,
                class_admission=True, rng=random.Random(1), **kw))
        results.append(_run_stream_arm(
            "fifo", args.model, cfg, params, specs,
            class_admission=False, rng=random.Random(1), **kw))
    by_name = {r["name"]: r for r in results}
    unloaded = by_name["unloaded-interactive"]
    klass_arm = (by_name.get("class-admission")
                 or by_name.get("class-admission-no-preempt"))
    fifo = by_name.get("fifo")
    out = {
        "backend": jax.devices()[0].platform,
        "model": args.model, "workload": "class-streams",
        "load": {"streams": streams, "slots": args.slots,
                 "kv_page_size": args.kv_page_size,
                 "mix": {k: sum(1 for s in specs if s[0] == k)
                         for k in ("best-effort", "batch",
                                   "interactive")},
                 "inject": args.inject},
        "results": results,
    }

    def _int_p99(row):
        if row is None:
            return None
        return (row.get("per_class_ttft", {})
                .get("interactive", {}).get("ttft_p99_s"))

    if klass_arm is not None:
        preempted = sum((klass_arm.get("preemptions") or {}).values())
        out["class_ab"] = {
            "interactive_ttft_p99_s_unloaded": _int_p99(unloaded),
            "interactive_ttft_p99_s_class": _int_p99(klass_arm),
            "interactive_ttft_p99_s_fifo": _int_p99(fifo),
            "preemptions": klass_arm.get("preemptions"),
            "readmit_suffix_tokens":
                klass_arm.get("readmit_suffix_tokens"),
            "tokens_per_sec_class": klass_arm.get("tokens_per_sec"),
            "tokens_per_sec_fifo":
                fifo.get("tokens_per_sec") if fifo else None,
            "throughput_ratio": (
                round(klass_arm["tokens_per_sec"]
                      / fifo["tokens_per_sec"], 4)
                if fifo and fifo.get("tokens_per_sec")
                and klass_arm.get("tokens_per_sec") else None),
        }
        print(f"class A/B: interactive ttft p99 "
              f"{_int_p99(klass_arm)}s class vs "
              f"{_int_p99(fifo)}s fifo "
              f"(unloaded {_int_p99(unloaded)}s), "
              f"{preempted} preemptions, throughput ratio "
              f"{out['class_ab']['throughput_ratio']}", flush=True)
    path = args.out or os.path.join(REPO, "bench_serve_results.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"wrote {path}")
    incomplete = [r["name"] for r in results
                  if r["completed"] < r["streams"]]
    if incomplete:
        print(f"ERROR: arms with failed requests: {incomplete}",
              file=sys.stderr)
        return 1
    if args.check_classes:
        if klass_arm is None:
            print("ERROR: --check-classes needs the class-admission "
                  "arm (drop --no-class-admission)", file=sys.stderr)
            return 1
        failures = []
        unl, cls = _int_p99(unloaded), _int_p99(klass_arm)
        # 1.5x, not parity: landing on a camped-full engine costs an
        # eviction tick plus a slot-drain ramp that the idle baseline
        # never pays. The gate catches admission failure (no
        # preemption → the natural-retirement wall blows well past
        # 1.5x), not the designed overhead.
        if unl is None or cls is None or cls > 1.5 * unl:
            failures.append(
                f"interactive ttft p99 {cls}s > 1.5x unloaded {unl}s "
                "— class admission is not protecting the interactive "
                "stream")
        preempted = (klass_arm.get("preemptions") or {})
        if not preempted.get("best-effort", 0) > 0:
            failures.append(
                f"preemptions {preempted} — no best-effort slot was "
                "evicted under full-slot pressure")
        for row in results:
            if row.get("kv_invariant_violations") not in (0, None):
                failures.append(
                    f"{row['name']}: {row['kv_invariant_violations']} "
                    "page refcount invariant violations")
        if fifo is not None:
            fifo_p99 = _int_p99(fifo)
            if cls is None or fifo_p99 is None or not cls < fifo_p99:
                failures.append(
                    f"interactive ttft p99 {cls}s class vs {fifo_p99}s "
                    "fifo — the policy did not beat the baseline")
            ratio = out["class_ab"]["throughput_ratio"]
            # 0.90, not parity: evictions discard the victim's private
            # tail-page decode work by design; the radix prefix makes
            # re-admission suffix-only, which is what keeps the waste
            # bounded. The gate catches eviction storms, not the
            # designed trade.
            if ratio is None or ratio < 0.90:
                failures.append(
                    f"throughput ratio {ratio} < 0.90 — preemption is "
                    "discarding more decode work than the class win "
                    "justifies")
        if args.streams >= 1000 and not args.quick:
            peak = max(r["streams_in_flight_peak"] for r in results)
            if peak < 1000:
                failures.append(
                    f"streams_in_flight_peak {peak} < 1000 — the load "
                    "harness never reached thousand-stream concurrency")
        if failures:
            for f in failures:
                print(f"ERROR: {f}", file=sys.stderr)
            return 1
        print(f"class check ok: interactive ttft p99 {cls}s "
              f"(unloaded {unl}s), preemptions {preempted}, "
              "invariants clean")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="llama3_1b")
    parser.add_argument("--clients", type=int, default=8)
    parser.add_argument("--requests", type=int, default=32)
    parser.add_argument("--max-new", type=int, default=64)
    parser.add_argument("--slots", type=int, default=8)
    parser.add_argument("--prompt-len", type=int, default=48)
    parser.add_argument("--workload", default="mixed",
                        choices=["mixed", "shared-prefix",
                                 "conversation-tree",
                                 "long-prompt-storm"],
                        help="prompt mix (see make_prompts); "
                             "long-prompt-storm switches to the lane "
                             "A/B: interleaved vs disaggregated "
                             "prefill/decode under concurrent long "
                             "prefills (see run_lanes)")
    parser.add_argument("--kv-page-size", type=int, default=16)
    parser.add_argument("--configs", default=None,
                        help="comma list to restrict the configs run, "
                             "e.g. 'paged,paged-nocache'")
    parser.add_argument("--draft", default=None,
                        help="also bench continuous speculative with "
                             "this draft model (vocab must match)")
    parser.add_argument("--spec-k", type=int, default=4)
    parser.add_argument("--fleet", type=int, default=0, metavar="N",
                        help="bench a ServingFleet of N replicas "
                             "instead of the single-engine configs: "
                             "prefix-affinity routing vs blind "
                             "round-robin over the same workload "
                             "(docs/serving.md 'Serving fleet')")
    parser.add_argument("--quick", action="store_true",
                        help="tiny load (CPU smoke of the harness)")
    parser.add_argument("--check-prefix", action="store_true",
                        help="CI gate: exit 1 unless the paged config "
                             "saw prefix_hit_rate > 0 with zero "
                             "refcount-invariant violations")
    parser.add_argument("--check-lanes", action="store_true",
                        help="(long-prompt-storm) CI gate: exit 1 "
                             "unless disaggregated decode gap p99 "
                             "stays within 1.15x of interleaved while "
                             "prefill throughput holds >= 0.95x, with "
                             "handoffs > 0 and invariants clean")
    parser.add_argument("--streams", type=int, default=0, metavar="N",
                        help="drive N concurrent mixed-class streams "
                             "through the class-admission A/B instead "
                             "of the config sweep (see run_streams; "
                             "the acceptance run uses N >= 1000)")
    parser.add_argument("--no-class-admission", action="store_true",
                        help="(--streams) run only the FIFO baseline "
                             "arm; the paired A/B runs it "
                             "automatically, this is the standalone "
                             "escape hatch")
    parser.add_argument("--check-classes", action="store_true",
                        help="(--streams) CI gate: exit 1 unless "
                             "interactive TTFT p99 stays within 1.5x "
                             "its unloaded value with best-effort "
                             "preemptions > 0, invariants clean, and "
                             "the FIFO pair beaten (p99 lower, tok/s "
                             ">= 0.9x)")
    parser.add_argument("--inject",
                        choices=["lane-starve", "no-preempt"],
                        default=None,
                        help="red-team arms: lane-starve "
                             "(long-prompt-storm) zeroes the decode "
                             "lane budget; no-preempt (--streams) "
                             "disables eviction so interactive TTFT "
                             "hits the natural-retirement wall — "
                             "either way the run MUST exit 1 (ci.sh "
                             "inverts this)")
    parser.add_argument("--out", default=None,
                        help="result path (default: repo-root "
                             "bench_serve_results.json)")
    args = parser.parse_args()
    if args.quick:
        args.clients, args.requests, args.max_new = 3, 6, 8

    if args.streams:
        return run_streams(args)

    if args.workload == "long-prompt-storm":
        return run_lanes(args)

    import random

    import jax

    rng = random.Random(0)
    prompts = make_prompts(args.workload, args.requests, args.prompt_len,
                           rng)

    if args.fleet:
        results = [run_fleet(args.model, prompts, args.max_new,
                             args.clients, replicas=args.fleet,
                             slots=args.slots,
                             page_size=args.kv_page_size, blind=blind)
                   for blind in (False, True)]
        out = {
            "backend": jax.devices()[0].platform,
            "model": args.model, "workload": args.workload,
            "load": {"clients": args.clients, "requests": args.requests,
                     "max_new": args.max_new, "slots": args.slots,
                     "replicas": args.fleet,
                     "prompt_len": args.prompt_len,
                     "kv_page_size": args.kv_page_size},
            "results": results,
        }
        for r in results:
            print(f"{r['name']}: hit_rate {r['prefix_hit_rate']}, "
                  f"p50 {r['latency_p50_ms']}ms, routed {r['routed']}",
                  flush=True)
        path = args.out or os.path.join(REPO, "bench_serve_results.json")
        with open(path, "w") as fh:
            json.dump(out, fh, indent=2)
        print(f"wrote {path}")
        incomplete = [r["name"] for r in results
                      if r["completed"] < args.requests]
        if incomplete:
            print(f"ERROR: configs with failed requests: {incomplete}",
                  file=sys.stderr)
            return 1
        return 0

    configs = [
        ("dense", dict(slots=args.slots)),
        ("paged", dict(slots=args.slots, kv="paged",
                       page_size=args.kv_page_size)),
        # The A/B baseline: same pool, radix sharing off — every
        # admission recomputes its full prefill.
        ("paged-nocache", dict(slots=args.slots, kv="paged",
                               page_size=args.kv_page_size,
                               prefix_cache=False)),
        ("paged-int8", dict(slots=args.slots, kv="paged",
                            page_size=args.kv_page_size,
                            quantize="int8")),
    ]
    if args.draft:
        # Continuous speculative (r4): ragged per-row acceptance over
        # the slot pool. Greedy-only engine; the drive() load is
        # already greedy (no temperature), so the same workload runs.
        configs.append(("dense-spec", dict(
            slots=args.slots, draft_model=args.draft, spec_k=args.spec_k)))
    if args.configs:
        wanted = {name.strip() for name in args.configs.split(",")}
        unknown = wanted - {name for name, _ in configs}
        if unknown:
            parser.error(f"unknown configs: {sorted(unknown)}")
        configs = [(n, kw) for n, kw in configs if n in wanted]
    results = [run_config(name, args.model, prompts, args.max_new,
                          args.clients, **kw)
               for name, kw in configs]
    by_name = {r["name"]: r for r in results}
    out = {
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "model": args.model,
        "workload": args.workload,
        "load": {"clients": args.clients, "requests": args.requests,
                 "max_new": args.max_new, "slots": args.slots,
                 "prompt_len": args.prompt_len,
                 "kv_page_size": args.kv_page_size},
        "results": results,
    }
    # The acceptance A/B: radix sharing on vs off, same pool, same
    # workload — skip fraction and the interactive-TTFT dividend.
    cached, nocache = by_name.get("paged"), by_name.get("paged-nocache")
    if cached is not None and nocache is not None:
        # Exact per-request TTFT from the span timelines; the bucketed
        # histogram percentiles ride along in each row's slo_by_class.
        t_on, t_off = cached.get("ttft_p50_ms"), nocache.get("ttft_p50_ms")
        out["prefix_ab"] = {
            "workload": args.workload,
            "prefix_hit_rate": cached.get("prefix_hit_rate"),
            "prefill_tokens_skipped": cached.get("prefill_tokens_skipped"),
            "ttft_p50_ms_cached": t_on,
            "ttft_p50_ms_nocache": t_off,
            "ttft_p50_improvement": (
                round(1.0 - t_on / t_off, 4)
                if t_on is not None and t_off else None),
        }
        print(f"prefix A/B ({args.workload}): hit_rate "
              f"{out['prefix_ab']['prefix_hit_rate']}, ttft p50 "
              f"{t_on}ms cached vs {t_off}ms nocache", flush=True)
    path = args.out or os.path.join(REPO, "bench_serve_results.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"wrote {path}")
    incomplete = [r["name"] for r in results
                  if r["completed"] < args.requests]
    if incomplete:
        print(f"ERROR: configs with failed requests: {incomplete} "
              "(see errors in the JSON)", file=sys.stderr)
        return 1
    if args.check_prefix:
        paged = by_name.get("paged")
        if paged is None:
            print("ERROR: --check-prefix needs the 'paged' config",
                  file=sys.stderr)
            return 1
        rate = paged.get("prefix_hit_rate") or 0.0
        violations = paged.get("kv_invariant_violations")
        if not rate > 0:
            print(f"ERROR: prefix_hit_rate {rate} — the radix cache "
                  "served nothing", file=sys.stderr)
            return 1
        if violations != 0:
            print(f"ERROR: {violations} page refcount invariant "
                  "violations after the run", file=sys.stderr)
            return 1
        print(f"prefix check ok: hit_rate {rate}, invariants clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
