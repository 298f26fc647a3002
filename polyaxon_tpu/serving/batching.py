"""Continuous batching for the serving runtime.

The static engine (server.py ``_Engine``) runs each request's whole
generation as one compiled program: a long request blocks the batch and
short ones pad to the longest. Continuous batching instead keeps a
fixed pool of KV-cache **slots** and advances all live requests one
token per loop iteration (the family's ``decode_step_ragged`` — each
slot at its own depth), admitting queued requests into freed slots
between iterations. Throughput scales with slot occupancy instead of
request alignment — the vLLM-style scheduling model, TPU-first:

- one jitted ragged decode step for the whole pool (static shapes:
  ``[slots]`` tokens/positions), so iteration never recompiles;
- admission = a jitted prefill per exact prompt length (LRU-bounded,
  same rule as the static engine) + an in-place cache-row insert;
- per-row sampling fused into the step program (greedy and
  temperature>0 rows coexist in one batch; per-row PRNG keys), so only
  ``[slots]`` token ids cross the host boundary per iteration.

Families exposing the continuous-batching surface are supported: llama
dense decoders, moe expert-FFN decoders, and t5 seq2seq (whose pool
cache carries per-slot encoder state — padded cross-attention K/V plus
a length mask — so requests with different encoder lengths share one
ragged decoder step).
"""

from __future__ import annotations

import collections
import contextlib
import logging
import math
import statistics
import threading
import time
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from polyaxon_tpu.obs import metrics as obs_metrics
from polyaxon_tpu.obs import reqtrace
from polyaxon_tpu.ops import mla_decode
from polyaxon_tpu.serving.quantize import held_transposed_bytes, weight_bytes
from polyaxon_tpu.serving.speculative import LaneView, SpeculationPolicy

logger = logging.getLogger(__name__)


class _FixedShapeProgram:
    """A jitted function whose argument shapes never change (the decode
    step: slots and the block-table width are fixed; a paged prefill
    program of one prompt length), compiled ahead of time on its first
    call. The owner keeps the executable, so it can say what the
    compiler set aside for its temporaries and, where it asks
    (``read_kernels``: the program's text is parsed for them), which
    Mosaic kernels are in it, and a drifting shape raises instead of
    compiling a second program. ``on_compile`` is told the temporaries'
    bytes once, when the program exists."""

    def __init__(self, jitted, read_kernels: bool = True, on_compile=None):
        self._jitted = jitted
        self._read_kernels = read_kernels
        self._on_compile = on_compile
        self._compiled = None
        self.kernels: dict[str, int] = {}
        self.temp_bytes: Optional[int] = None

    def __call__(self, *args):
        if self._compiled is None:
            self._compiled = self._jitted.lower(*args).compile()
            if self._read_kernels:
                from polyaxon_tpu.perf.hlo import pallas_kernels

                self.kernels = pallas_kernels(self._compiled.as_text())
            self.temp_bytes = getattr(self._compiled.memory_analysis(),
                                      "temp_size_in_bytes", None)
            if self._on_compile is not None:
                self._on_compile(self.temp_bytes)
        return self._compiled(*args)


# The engine's own histograms (a tick's length, a token's way from the
# readback to its handler's socket): bucket k holds [FIRST x 2^(k/4),
# FIRST x 2^((k+1)/4)), 19% a bucket from 0.25 ms to 16 s; what lies
# under the first edge is in bucket 0, what lies past the last in 63.
LOG_FIRST_EDGE_MS = 0.25
LOG_PER_OCTAVE = 4
LOG_BUCKETS = 64


def log_bucket(ns: int) -> int:
    """The bucket of a duration in nanoseconds."""
    if ns <= LOG_FIRST_EDGE_MS * 1e6:
        return 0
    octaves = math.log2(ns / (LOG_FIRST_EDGE_MS * 1e6))
    return min(int(LOG_PER_OCTAVE * octaves), LOG_BUCKETS - 1)


def _log_hist(**counts) -> dict:
    """Counts by `log_bucket` as `/v1/stats` carries them: copies, with
    the buckets' description beside them."""
    return {"first_edge_ms": LOG_FIRST_EDGE_MS,
            "per_octave": LOG_PER_OCTAVE,
            **{name: list(by_bucket) for name, by_bucket in counts.items()}}


class _PhaseClock:
    """Where an engine tick's host time goes. ``phase(name)`` is entered
    from the engine thread only: it opens a
    ``jax.profiler.TraceAnnotation("engine:<name>")``, which puts the
    span on the host plane of any running profile on the device
    operations' own clock (a flag test when none runs), and books the
    span's ``perf_counter_ns`` time, less what its children took, to a
    cumulative and a per-tick vector. Leaf phases never overlap, and
    what a tick spends under none of them (the lines between phases,
    this clock's own cost) is `tick.other`, so the vector sums to the
    tick. A tick longer than ``max(SLOW_FLOOR_S, SLOW_FACTOR x the
    median of the last 64)`` leaves a record in ``slow`` with the
    engine's state from ``snapshot()``: the black box of a stall that
    nobody was tracing. (An engine's first tick has no median to be
    held against, and compiles: it is never recorded.)

    One tick in ``CPU_EVERY`` is *sampled*: its spans also read the
    engine thread's own CPU time (``thread_time_ns``) and book it to
    ``cpu_ns``, and their wall time once more to ``sampled_ns``, both
    under the leaves' keys: ``sampled_ns`` less ``cpu_ns`` of a leaf is
    what the thread spent off the processor there, waiting for the
    interpreter lock or inside a call that blocked. (Not every tick:
    the CPU clock is a real system call, 0.5 us on a plain kernel and
    6-37 us under a sandbox that intercepts system calls, twenty of
    them a tick.) Every tick's length goes into ``tick_hist`` (the
    buckets of `log_bucket`), the ticks that ran a prefill into
    ``prefill_tick_hist`` as well; the loop's wait for work is no tick
    and no phase: `dry`."""

    LEAVES = ("sweep", "admit.pick", "admit.match", "admit.prefill",
              "admit.other", "prefill_chunk", "step.keys", "step.upload",
              "step.dispatch", "step.announce", "step.readback",
              "step.emit", "spec", "observe", "tick.other")
    SLOW_FLOOR_S = 1.0
    SLOW_FACTOR = 8.0
    SLOW_KEPT = 16
    CPU_EVERY = 8

    def __init__(self, snapshot, more_leaves: tuple = ()):
        self._snapshot = snapshot
        # An engine whose pool has a window space books its roll under a
        # leaf of its own (`step.window`); no other engine has the key.
        if more_leaves:
            self.LEAVES = self.LEAVES + more_leaves
        # Every key is there from the start: readers on other threads
        # copy these dicts while the engine thread adds to their values.
        self.total_ns = dict.fromkeys(self.LEAVES, 0)
        self.cpu_ns = dict.fromkeys(self.LEAVES, 0)
        self.sampled_ns = dict.fromkeys(self.LEAVES, 0)
        self._tick_ns = dict.fromkeys(self.LEAVES, 0)
        self.ticks = 0
        self.sampled_ticks = 0
        self._sampled = False
        self.tick_hist = [0] * LOG_BUCKETS
        self.prefill_tick_hist = [0] * LOG_BUCKETS
        self.dry_ns = 0
        self.dry_waits = 0
        # per open span: [start, children's ns, CPU at start, children's]
        self._open: list[list] = []
        self._recent: collections.deque = collections.deque(maxlen=64)
        self.slow: tuple = ()  # replaced whole, so a reader never races

    @contextlib.contextmanager
    def phase(self, name: str, book: Optional[str] = None):
        """A span `engine:<name>`; its time outside its children is
        booked under `book` (default: its name)."""
        # The CPU clock is read inside the wall clock's two readings, at
        # either end: a leaf's CPU time never exceeds its wall time.
        sampled = self._sampled
        frame = [time.perf_counter_ns(), 0,
                 time.thread_time_ns() if sampled else 0, 0]
        self._open.append(frame)
        try:
            with jax.profiler.TraceAnnotation("engine:" + name):
                yield
        finally:
            self._open.pop()
            cpu = time.thread_time_ns() - frame[2] if sampled else 0
            took = time.perf_counter_ns() - frame[0]
            if self._open:
                parent = self._open[-1]
                parent[1] += took
                parent[3] += cpu
            key = book or name
            self.total_ns[key] += took - frame[1]
            self._tick_ns[key] += took - frame[1]
            if sampled:
                self.sampled_ns[key] += took - frame[1]
                self.cpu_ns[key] += cpu - frame[3]

    @contextlib.contextmanager
    def dry(self):
        """The loop's wait for work, entered from the engine thread with
        nothing live, queued or asked: a span `engine:dry` beside the
        `engine:tick`s and a counter of its own, in no leaf."""
        start = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation("engine:dry"):
                yield
        finally:
            self.dry_ns += time.perf_counter_ns() - start
            self.dry_waits += 1

    @contextlib.contextmanager
    def tick(self):
        """One loop iteration: the parent span of every phase."""
        for key in self.LEAVES:
            self._tick_ns[key] = 0
        self._sampled = self.ticks % self.CPU_EVERY == 0
        try:
            with self.phase("tick", book="tick.other"):
                yield
        finally:
            self.sampled_ticks += self._sampled
            self._sampled = False
            took = sum(self._tick_ns.values())
            bucket = log_bucket(took)
            self.tick_hist[bucket] += 1
            if self._tick_ns["admit.prefill"]:
                self.prefill_tick_hist[bucket] += 1
            self.ticks += 1
            if (took > self.SLOW_FLOOR_S * 1e9 and self._recent
                    and took > self.SLOW_FACTOR
                    * statistics.median(self._recent)):
                record = {
                    "t_wall": time.time(), "duration_ms": took / 1e6,
                    "phases_ms": {k: v / 1e6
                                  for k, v in self._tick_ns.items() if v},
                    **self._snapshot()}
                self.slow = (self.slow + (record,))[-self.SLOW_KEPT:]
            self._recent.append(took)


class _Launch(NamedTuple):
    """A decode step launched and not read back: its token vector, still
    on the device, and whose token each row's is: (slot, request, whether
    this token is the request's last by its budget). The slot may have
    been freed and filled again before the tokens are read."""

    nxt: jax.Array
    rows: list


def step_keys(seeds, counts):
    """The decode step's sampling keys, derived inside the compiled
    program: row ``b``'s is ``fold_in(key(seeds[b]), counts[b])``, bit
    for bit the key the engine used to build on the host for every
    token. `seeds` is what ``jnp.asarray`` makes of the requests' int64
    seeds, `counts` the tokens each request has so far."""
    return jax.vmap(
        lambda seed, count: jax.random.fold_in(jax.random.key(seed), count)
    )(seeds, counts)


def _device_stats(params, cache) -> tuple:
    """(first device, what `/v1/stats` says of the devices): those the
    params live on, as jax reports them, and what each holds of the
    params and the KV cache. Computed once, where the cache is built:
    the counts never change, and the engine thread donates the cache's
    buffers to every step, so a later reader must not touch them."""
    from polyaxon_tpu.parallel.sharding import bytes_per_device, param_bytes

    devices = sorted(jax.tree.leaves(params)[0].devices(),
                     key=lambda d: d.id)
    return devices[0], {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "param_bytes": param_bytes(params),
        "param_bytes_per_device": bytes_per_device(params),
        "kv_bytes": param_bytes(cache),
        "kv_bytes_per_device": bytes_per_device(cache),
    }


class QueueFull(RuntimeError):
    """The continuous engine's pending queue is at its cap: the caller
    should shed load (HTTP 503 + Retry-After) instead of queueing
    unbounded work it will serve long after the client gave up."""

    def __init__(self, message: str, retry_after: int = 1):
        super().__init__(message)
        self.retry_after = max(int(retry_after), 1)


def bucket_suffix_len(n: int, floor: int = 8) -> int:
    """Padded length for a radix-suffix prefill of ``n`` novel tokens:
    the next power of two, floored at ``floor``. Suffix lengths are
    arbitrary (prompt length minus whatever prefix the radix cache
    matched), so compiling per exact length would accumulate one
    executable per distinct length; bucketing bounds the compile count
    to O(log max_suffix) per prefix-page count, and the padded tail is
    masked to the scratch page at insert (paged_insert_suffix)."""
    if n < 1:
        raise ValueError(f"suffix length must be >= 1, got {n}")
    return max(floor, 1 << (n - 1).bit_length())


@dataclass(frozen=True)
class RequestClass:
    """One named serving class — the per-request mirror of the PR 2
    queue/priority-class catalog (scheduling.catalog.V1Queue): a
    numeric priority orders admission across classes, a TTFT target
    anchors deadline urgency inside the rank tuple, and the
    preemption flags say who may evict whom under pressure.

    ``skip_cap`` is the PR 11 bounded-starvation barrier generalized
    per class: a request overtaken that many times becomes a barrier
    for younger requests OF ITS OWN CLASS (aging is within-class;
    across classes priority is strict — a saturated high class starves
    a lower one by design, and the per-class pending cap is the
    shed-load bound on that starvation)."""

    name: str
    priority: int          # higher admits first (catalog ordering)
    ttft_target: float     # seconds; past it the request is "overdue"
    preemptible: bool      # may be evicted from a live slot
    preempts: bool         # may trigger eviction when blocked
    skip_cap: int          # within-class starvation barrier


# Mirrors scheduling.catalog.PRIORITY_CLASSES (low=0, default=1,
# high=2): interactive rides the `high` rung with a tight TTFT target
# and is never evicted; `batch` is the default middle; `best-effort`
# is the only preemptible class — its slots and KV pages are the
# reserve an urgent interactive prefill draws down.
REQUEST_CLASSES: dict[str, RequestClass] = {
    "interactive": RequestClass("interactive", priority=2,
                                ttft_target=0.5, preemptible=False,
                                preempts=True, skip_cap=4),
    "batch": RequestClass("batch", priority=1, ttft_target=2.5,
                          preemptible=False, preempts=False,
                          skip_cap=16),
    "best-effort": RequestClass("best-effort", priority=0,
                                ttft_target=30.0, preemptible=True,
                                preempts=False, skip_cap=64),
}
DEFAULT_REQUEST_CLASS = "batch"


def resolve_request_class(name: str) -> RequestClass:
    """Catalog lookup; unknown class names fold to the default class
    (the HTTP layer already bounds the raw string) so an arbitrary
    label can never mint priority or preemption rights."""
    return REQUEST_CLASSES.get(name, REQUEST_CLASSES[DEFAULT_REQUEST_CLASS])


def validate_sampling(top_p: float, top_k: int) -> None:
    """Shared request-sampling validation (HTTP handler AND direct
    engine callers): out-of-range knobs must raise, not silently
    degenerate (top_p=0 would collapse to argmax via the all--inf
    categorical, top_k<0 would silently mean 'disabled')."""
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")


@dataclass
class _Request:
    tokens: list[int]
    max_new: int
    temperature: float
    seed: int
    top_p: float = 1.0
    top_k: int = 0
    # Early stop: generation retires at the first of these token ids
    # (the stop token IS included in the output — callers that want it
    # dropped slice it off; including it keeps losslessness trivially
    # comparable across engines).
    eos: frozenset = frozenset()
    out: list[int] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    # Set by the engine once tokens it appended to `out` (or the
    # request's end) are worth a streaming handler's waking for
    # (`ContinuousBatchingEngine._announce`); the handler clears it.
    fresh: threading.Event = field(default_factory=threading.Event)
    # `perf_counter_ns` at which the engine read the newest token of
    # `out` off the device (`_emit_step`); the streaming handler takes
    # the token's way out from it (`stats()["deliver_lag_hist"]`).
    read_ns: int = 0
    error: Optional[str] = None
    cancelled: bool = False
    # Stamped at submit; the retire path feeds submit→done wall time
    # into the unified registry's serving-latency histogram (ISSUE 5).
    submitted_at: float = field(default_factory=time.time)
    # Per-request observability (ISSUE 10): the id doubles as the trace
    # id; `klass` labels the SLO histograms and picks the admission
    # queue (REQUEST_CLASSES; unknown labels fold to `batch`);
    # `first_token_at` anchors TTFT at emission and TPOT at retirement.
    id: str = field(default_factory=reqtrace.new_request_id)
    klass: str = "batch"
    trace: Optional[reqtrace.RequestTrace] = None
    first_token_at: Optional[float] = None
    # Cache-aware admission bookkeeping (paged + radix prefix cache):
    # `admit_skips` counts how many times a younger request was
    # admitted past this one (the starvation bound); the cached-token
    # count lands on the request trace at finish.
    admit_skips: int = 0
    prefix_cached_tokens: int = 0
    # Class-aware admission (ISSUE 19): `seq` is the global arrival
    # order (assigned under the engine lock at enqueue) — the FIFO
    # tie-breaker now that pending work lives in per-class queues;
    # `preemptions` counts evictions this request survived, so the
    # re-admission path knows to account its suffix prefill.
    seq: int = 0
    preemptions: int = 0
    # `tokens` as the radix tree compares them while the request waits
    # (``serving/paged.py _common``): an int32 array made once at
    # submit, on the caller's thread, where the pool matches prefixes;
    # the list itself elsewhere.
    match_key: Any = None

    def __post_init__(self):
        if self.match_key is None:
            self.match_key = self.tokens

    def wait(self, timeout: Optional[float] = None) -> list[int]:
        if not self.done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error:
            raise RuntimeError(self.error)
        return self.out


class ContinuousBatchingEngine:
    """Slot-pool generation engine. API-compatible with ``_Engine``:
    ``generate(rows, max_new_tokens, temperature, seed)`` blocks; the
    lower-level ``submit()`` returns a waitable request for callers
    that want request-level interleaving (each HTTP thread does)."""

    def __init__(self, model: str, cfg, params, *, slots: int = 4,
                 max_len: Optional[int] = None, kv: str = "dense",
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 draft=None, prefill_chunk: Optional[int] = None,
                 prefill_slots: Optional[int] = None,
                 prefill_lane_budget: int = 1,
                 decode_lane_budget: int = 1,
                 spec_policy: Optional[SpeculationPolicy] = None,
                 max_pending: Optional[int] = None,
                 class_admission: bool = True,
                 class_max_pending: Optional[dict] = None,
                 preemption: bool = True,
                 request_tracing: bool = True,
                 trace_capacity: int = reqtrace.DEFAULT_RING_CAPACITY,
                 trace_dump_path: Optional[str] = None,
                 registry=None, mesh=None):
        from polyaxon_tpu.models import family_of

        family = family_of(model)
        # Disaggregated prefill/decode (ISSUE 18): `prefill_slots`
        # extra block-table rows form a prefill LANE — admissions land
        # there, stream their novel suffix in chunks via the radix
        # suffix path, and HAND their committed pages to a free decode
        # slot (PagePool.handoff — a block-table row move plus at most
        # the admission-time CoW fork, never a recompute). Per-lane
        # budgets bound interference: at most `prefill_lane_budget`
        # chunk programs run per tick while decode rows are live, and
        # the decode lane gets `decode_lane_budget` steps per tick
        # (0 = deliberately starved, the bench's lane-starve inject).
        if prefill_slots is not None:
            if prefill_slots < 1:
                raise ValueError(
                    f"prefill_slots must be >= 1, got {prefill_slots}")
            if kv != "paged":
                raise ValueError(
                    "disaggregated prefill/decode requires kv='paged' "
                    "(the handoff boundary is a block-table row move)")
            if draft is not None:
                raise ValueError(
                    "prefill_slots and draft are mutually exclusive: "
                    "the draft's verify chunk needs kv='dense' while "
                    "the page handoff needs kv='paged'")
            if not (hasattr(family, "paged_prefill_suffix_kv")
                    and hasattr(family, "paged_insert_suffix")):
                raise ValueError(
                    f"`{model}` ({family.__name__}) has no paged suffix-"
                    "prefill surface; the prefill lane streams chunks "
                    "through paged_prefill_suffix_kv")
        if prefill_lane_budget < 1:
            raise ValueError(
                f"prefill_lane_budget must be >= 1, got "
                f"{prefill_lane_budget}")
        if decode_lane_budget < 0:
            raise ValueError(
                f"decode_lane_budget must be >= 0, got "
                f"{decode_lane_budget}")
        # Chunked prefill (vLLM-style): a long prompt's admission no
        # longer blocks the pool for one monolithic prefill — the
        # prompt streams into a standalone row cache `prefill_chunk`
        # tokens per loop iteration (one fixed-shape decode_chunk
        # program, reused for EVERY prompt length — no per-length
        # compile cache), interleaved with the live slots' decode
        # steps; the finished row then inserts like any admission.
        # Rollback-free by the same slot==position argument as
        # speculative verify: the padded tail chunk's junk writes sit
        # at positions decode rewrites before anything attends them.
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if kv != "dense" and prefill_slots is None:
                raise ValueError(
                    "chunked prefill requires kv='dense' (the chunk "
                    "writer needs the slot==position row cache) — or "
                    "prefill_slots, where it sizes the lane's per-tick "
                    "suffix chunk instead")
            if kv == "dense":
                if not hasattr(family, "decode_chunk"):
                    raise ValueError(
                        f"`{model}` ({family.__name__}) has no "
                        "decode_chunk surface; chunked prefill supports "
                        "llama/moe-family decoders")
                if getattr(cfg, "sliding_window", None) is not None:
                    raise ValueError(
                        "chunked prefill requires a full-length cache "
                        "(no sliding_window): the padded tail chunk's "
                        "junk writes rely on slot == position")
        # Speculative decoding over the slot pool: ``draft`` =
        # (draft_model, draft_cfg, draft_params, k). Each loop
        # iteration becomes one draft→verify round — every live slot
        # proposes k tokens with its own draft-cache row and accepts
        # 1..k+1 of them raggedly (per-row acceptance counts, per-row
        # budget caps). Greedy-only: acceptance compares the target's
        # own argmax, so the pool serves temperature-0 requests while
        # a draft is configured (submit refuses sampled requests
        # loudly rather than silently starving speculation).
        if draft is not None:
            if kv != "dense":
                raise ValueError(
                    "speculative continuous batching requires kv='dense' "
                    "(the verify chunk needs the slot==position cache)")
            if getattr(cfg, "sliding_window", None) is not None:
                raise ValueError(
                    "speculative decoding requires a full-length cache "
                    "(no sliding_window) — rollback-free acceptance "
                    "depends on slot == position")
            if not hasattr(family, "decode_chunk"):
                raise ValueError(
                    f"`{model}` ({family.__name__}) has no decode_chunk "
                    "verify surface; speculative continuous batching "
                    "supports llama/moe-family decoders")
        # Family-generic: any family exposing the continuous-batching
        # surface (llama dense decoders, moe expert-FFN decoders, t5
        # seq2seq with per-slot encoder state) batches continuously.
        required = ("decode_step_ragged", "cb_init_cache", "cb_prefill",
                    "cb_admission", "cb_validate", "insert_cache_row")
        if kv == "paged":
            required += ("decode_step_paged", "paged_init_cache",
                         "paged_prefill_kv", "paged_insert_prefill")
        elif kv != "dense":
            raise ValueError(f"unknown kv mode `{kv}` "
                             "(expected 'dense' or 'paged')")
        missing = [name for name in required if not hasattr(family, name)]
        if missing:
            alt = "kv='dense'" if kv == "paged" else "the static engine"
            raise ValueError(
                f"continuous batching needs the ragged-decode surface; "
                f"`{model}` ({family.__name__}) lacks {missing} — use {alt}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.model = model
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len or cfg.max_seq_len
        self._family_mod = family
        # The mesh the params are sharded over (None = one device). The
        # KV cache shards its kv heads over `tp` with them, and the
        # loop thread enters the mesh so the kernels' shard_map wrappers
        # (parallel/compat.py) find it while tracing.
        self._mesh = mesh
        # Fleet-scoped telemetry (ISSUE 20): `registry` may be a
        # `REGISTRY.scoped(component=...)` view — every series this
        # engine records then carries the replica's identity, and its
        # trace spans name the replica instead of the generic
        # "serving". Standalone engines keep the unscoped global.
        self._obs = registry if registry is not None else obs_metrics.REGISTRY
        self._obs_component = (getattr(self._obs, "component", "")
                               or "serving")
        self.kv = kv
        self._pool = None
        # Prefill-lane rows sit AFTER the decode slots in the block
        # table (rows slots..slots+prefill_slots-1): the decode step's
        # [slots]-shaped tables slice never sees them, and a handoff is
        # a row move inside the same pool.
        self.prefill_slots = int(prefill_slots or 0)
        n_rows = slots + self.prefill_slots
        # A family with window layers (`paged_window`) is given a pool
        # with a window space beside the full one. Decided here, once:
        # with `_window_tables` None every line below runs as it did.
        self._window_tables: Optional[np.ndarray] = None
        window = getattr(family, "paged_window", None)
        if kv == "paged" and window is not None:
            from polyaxon_tpu.serving.paged import WindowedPagePool

            maxp = -(-self.max_len // page_size)
            self._pool = WindowedPagePool(
                n_rows, self.max_len, page_size,
                (n_rows * maxp if kv_pages is None else kv_pages) + 1,
                window=window(cfg), prefix_cache=prefix_cache)
            self._window_tables = self._pool.window_tables
        elif kv == "paged":
            from polyaxon_tpu.serving.paged import PagePool

            if kv_pages is None:
                # Sized to every row's dense reservation, lane rows
                # included — staged prefills hold pages concurrently
                # with the decode pool, by design.
                self._pool = PagePool.dense_equivalent(
                    n_rows, self.max_len, page_size,
                    prefix_cache=prefix_cache)
            else:
                # kv_pages counts USABLE pages (what /v1/stats reports
                # as kv_pages_total); the scratch page is internal —
                # validate in the user's units before adding it.
                if kv_pages < 1:
                    raise ValueError(
                        f"kv_pages must be >= 1, got {kv_pages}")
                self._pool = PagePool(n_rows, self.max_len, page_size,
                                      kv_pages + 1,
                                      prefix_cache=prefix_cache)
        self._cache = self._new_cache()
        self._device0, self._device_stats = _device_stats(
            self.params, self._cache)
        self._weight_bytes = weight_bytes(self.params)
        self._held_transposed_bytes = held_transposed_bytes(self.params)
        # What a page holds: page_size tokens of K and V and, for a
        # family whose cache has such a leaf, one fixed-size state. A
        # radix match that ends inside a page has no true state, so the
        # pool then matches whole pages only. What a row holds beside
        # its pages (``cache["rows"]``: a recurrent state a sequence)
        # no match can resume from, so the pool then matches nothing
        # (both decided here, from the cache's structure; there is no
        # option).
        self._page_bytes = (0, 0, 0)
        if self._pool is not None:
            from polyaxon_tpu.serving.paged import page_bytes

            self._page_bytes = page_bytes(
                self._cache, self._pool.n_pages, self._pool.page_size)
            if self._page_bytes[1]:
                self._pool.whole_page_matches = True
            if self._page_bytes[2]:
                self._pool.match_nothing()
            if self._window_tables is not None:
                from polyaxon_tpu.serving.paged import window_page_bytes

                # The window layers a suffix program walks behind a
                # match (`WindowedPagePool.suffix_start`), read off the
                # cache like the rest; where they make a match save
                # nothing, the pool matches nothing.
                self._pool.set_window_layers(
                    self._cache["window"]["k"].shape[0])
                self._window_page_bytes = window_page_bytes(self._cache)
        # A family may keep, with its cache, the (row, choice) pairs its
        # decode steps routed to each expert. The engine thread reads it
        # between ticks, when `stats()` has asked (`_serve_expert_tokens`).
        self._expert_counters = tuple(
            name for name in ("moe_expert_tokens", "moe_pairs_elsewhere",
                              "mla_decode_positions")
            if name in self._cache)
        self._expert_tokens: Optional[dict] = None
        self._expert_tokens_asking = threading.Lock()  # one asker at a time
        self._expert_tokens_wanted = threading.Event()
        self._expert_tokens_ready = threading.Event()
        self.draft = draft
        self._spec_rounds = 0
        self._spec_tokens = 0
        if draft is not None:
            draft_model, draft_cfg, draft_params, spec_k = draft
            if getattr(draft_cfg, "sliding_window", None) is not None:
                raise ValueError(
                    "draft model must not use sliding_window (its cache "
                    "needs slot == position too)")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self._draft_family = family_of(draft_model)
            if getattr(self._draft_family, "SEQ2SEQ", False):
                raise ValueError(
                    f"draft `{draft_model}` is seq2seq — a drafting "
                    "decoder must continue the same token stream the "
                    "target decodes (its proposals would be garbage "
                    "and acceptance would silently collapse)")
            draft_required = ("decode_step_ragged", "cb_init_cache",
                              "cb_prefill", "insert_cache_row")
            draft_missing = [name for name in draft_required
                             if not hasattr(self._draft_family, name)]
            if draft_missing:
                raise ValueError(
                    f"draft `{draft_model}` "
                    f"({self._draft_family.__name__}) lacks the ragged "
                    f"decode surface: {draft_missing}")
            self._draft_cfg = draft_cfg
            self._draft_params = draft_params
            self.spec_k = int(spec_k)
            self._draft_cache = self._draft_family.cb_init_cache(
                draft_cfg, slots, self.max_len)
        self.prefill_chunk = prefill_chunk
        # Lane scheduler state (paged disaggregation). `_lane` maps a
        # prefill ROW → [request, prefill tokens, progress, pos0,
        # tok0]; dict insertion order is the staging FIFO. A staged
        # reservation whose progress reached its prompt waits in place
        # for a free decode slot (natural backpressure — no page churn).
        self.prefill_lane_budget = int(prefill_lane_budget)
        self.decode_lane_budget = int(decode_lane_budget)
        self._lane: dict[int, list] = {}
        self._lane_chunk = (int(prefill_chunk) if prefill_chunk
                            else max(2 * page_size, 32))
        self._handoffs = 0
        self._handoff_pages = 0
        # Decode-lane cadence: wall time between CONSECUTIVE decode
        # steps (reset to None whenever the decode lane goes idle, so
        # quiet gaps never pollute the interference histogram).
        self._last_decode_at: Optional[float] = None
        # Per-slot chunked-prefill state: [request, prompt tokens to
        # write, progress, target row cache, draft row cache or None,
        # pos0, tok0]. A slot in this dict is RESERVED but not yet
        # live; dict insertion order IS the admission FIFO. Each
        # reservation holds a standalone full-length row cache (plus
        # the draft's when speculating) on top of the pool cache —
        # peak KV memory grows accordingly (documented at the flag).
        self._prefilling: dict[int, list] = {}
        self._pos = np.full(slots, -1, np.int32)  # -1 = free slot
        # The decode loop runs one step ahead of the host
        # (`_plain_step`): a step's tokens go to the next step on the
        # device (`_tok_dev`, None where the host's `_cur` is the whole
        # truth) and are read back after it is launched. `_cur` is the
        # host's word: a row's last token read, or the first token of
        # a row that went live since the last launch (`_fresh_rows`),
        # which overrides the device's. `_counts` is what the program
        # folds into each row's key: the tokens launched for its
        # request so far, the one in flight included.
        self._cur = np.zeros(slots, np.int32)
        self._counts = np.zeros(slots, np.int32)
        self._tok_dev: Optional[jax.Array] = None
        self._fresh_rows: set[int] = set()
        self._no_fresh = jnp.asarray(np.full(slots, -1, np.int32))
        # Launched, not read back, oldest first: one between two steps,
        # two for the moment after a launch.
        self._unread: collections.deque[_Launch] = collections.deque()
        self._steps_ahead = 0
        self._tokens_dropped = 0
        # Per-slot sampling state: written at admission and at retire
        # (`_set_sampling`), never per token. The decode step reads the
        # device copy, uploaded again only after a write. Seeds stay
        # int64 on the host: `jnp.asarray` narrows them exactly as
        # `jax.random.key(int)` narrows a Python int.
        self._seeds = np.zeros(slots, np.int64)
        self._temps = np.zeros(slots, np.float32)
        self._top_ps = np.ones(slots, np.float32)
        self._top_ks = np.zeros(slots, np.int32)
        self._sampling_dev: Optional[tuple] = None
        self._slot_req: list[Optional[_Request]] = [None] * slots

        # Graceful degradation: a bounded pending queue. None =
        # unbounded (library callers managing their own admission);
        # the HTTP layer maps QueueFull to 503 + Retry-After.
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.max_pending = max_pending
        # Class-aware admission (ISSUE 19): pending work lives in
        # PER-CLASS queues (FIFO within a class, arrival `seq` as the
        # cross-class tie-breaker) instead of one deque. With
        # `class_admission` off — the A/B baseline — every request
        # lands in one queue regardless of label and the pre-19
        # FIFO-with-cache-affinity scan runs unchanged.
        self.class_admission = bool(class_admission)
        self.preemption = bool(preemption)
        self._class_caps: dict[str, int] = {}
        for name, cap in (class_max_pending or {}).items():
            if cap is not None:
                cap = int(cap)
                if cap < 1:
                    raise ValueError(
                        f"class_max_pending[{name!r}] must be >= 1, "
                        f"got {cap}")
                self._class_caps[str(name)] = cap
        # Pre-created for every reachable key (unknown labels fold to
        # the default class) so the dict never grows after the ctor —
        # unlocked readers (health/stats/gauges) iterate it safely.
        self._queues: dict[str, collections.deque] = {
            name: collections.deque()
            for name in (REQUEST_CLASSES if self.class_admission
                         else (DEFAULT_REQUEST_CLASS,))}
        self._seq = 0
        # Preemption accounting (stats + the bench gate): evictions by
        # victim class, and the novel tokens re-admissions prefilled
        # (the real recompute cost of eviction — the committed prefix
        # rode the radix cache).
        self._preemptions: dict[str, int] = {}
        self._readmit_suffix_tokens = 0
        # Per-request observability (ISSUE 10): span trees in a bounded
        # ring behind GET /requests/{id}/timeline, shed-load accounting
        # for /v1/stats. Tracing defaults on — the parity check in
        # tests/test_serving.py holds its overhead within 5% — and
        # `request_tracing=False` turns span recording off while the
        # SLO histograms (TTFT/TPOT/queue-wait) keep flowing.
        self.request_tracing = bool(request_tracing)
        self._ring = reqtrace.TimelineRing(trace_capacity)
        # ISSUE 13: where to persist the ring at shutdown (None = the
        # ring dies with the process, the pre-13 behavior).
        self.trace_dump_path = trace_dump_path
        self._rejected: dict[str, int] = {}
        self._cv = threading.Condition()
        # Requests whose new tokens (or end) no handler has been woken
        # for yet (`_announce`).
        self._unannounced: list[_Request] = []
        # What the streaming handlers measured of a token's way out
        # (`merge_deliver_lags`), under a lock of its own.
        self._deliver_lags = [0] * LOG_BUCKETS
        self._deliver_lock = threading.Lock()
        self._stopped = False
        self._served = 0
        self._tokens_out = 0
        self._step_failures = 0  # lifetime counter (stats)
        self._consec_step_failures = 0
        # Occupancy accounting: continuous batching wins exactly when
        # slots stay busy — avg_occupancy is THE number that says so.
        self._steps_total = 0
        self._live_slot_steps = 0
        # What the paged decode kernel walks beside what it is handed:
        # pages the live rows hold, and table entries, summed over the
        # plain decode steps.
        self._paged_pages_live = 0
        self._paged_pages_table = 0
        self._queue_depth_peak = 0
        # Host time of the loop by phase (always on): `/v1/stats`
        # `tick_phase_ns`, the `engine:` spans of a profile, `slow_ticks`.
        self._clock = _PhaseClock(
            self._tick_snapshot,
            ("step.window",) if self._window_tables is not None else ())
        self._phase = self._clock.phase
        self._admissions = 0
        # A device that throws persistently (e.g. OOM) would otherwise
        # burn one rebuilt-cache step per queued request; after this
        # many consecutive failures the engine fails fast instead.
        self.max_step_failures = 3

        def step(params, cache, tokens, pos, seeds, counts, temps, top_ps,
                 top_ks, tables, fresh, *, filtered: bool):
            from polyaxon_tpu.models.common import sample_row

            # `tokens` is the step before's result, never seen by the
            # host; `fresh` holds the host's word for the rows that went
            # live since (their first decode token), -1 elsewhere.
            tokens = jnp.where(fresh >= 0, fresh, tokens)
            keys = step_keys(seeds, counts)
            # Quantized trees pass through whole — weights unwrap at
            # consumption inside the model (models/llama.py _w), so
            # int8 stays the HBM format in the per-step program.
            if tables is None:
                logits, cache = family.decode_step_ragged(
                    cfg, params, cache, tokens, pos)
            else:
                logits, cache = family.decode_step_paged(
                    cfg, params, cache, tokens, pos, tables)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if filtered:
                # Per-row temperature + top-p/top-k fused into the
                # step — greedy and filtered rows coexist in one
                # batch; only [slots] token ids cross the host.
                sampled = jax.vmap(sample_row)(logits, keys, temps,
                                               top_ps, top_ks)
            else:
                # The historical draw, bit-stable for existing seeds —
                # and no full-vocab sort in the hot loop when nothing
                # live uses the filters (the common case).
                scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
                sampled = jax.vmap(jax.random.categorical)(
                    keys, scaled).astype(jnp.int32)
            nxt = jnp.where(temps > 0, sampled, greedy)
            return nxt, cache

        # Two executables; the loop picks per iteration by whether any
        # live row actually uses top-p/top-k (same idea as the static
        # engine's `filtered` compile key).
        # Named functions, not partials: a trace then shows the program
        # as `jit_decode_step`, not `jit__unknown`.
        def decode_step(*args):
            return step(*args, filtered=False)

        def decode_step_filtered(*args):
            return step(*args, filtered=True)

        self._step_plain = _FixedShapeProgram(jax.jit(
            decode_step, donate_argnums=(1,)))
        self._step_filtered = _FixedShapeProgram(jax.jit(
            decode_step_filtered, donate_argnums=(1,)))
        self._decode_programs = (self._step_plain, self._step_filtered)

        # The most bytes of temporaries among the paged prefill
        # programs, whole-prompt and suffix, compiled so far (one that
        # its lru has dropped since counts): each is one shape, the
        # cache donated.
        self._prefill_temp_bytes: Optional[int] = None

        def prefill_program(run):
            return _FixedShapeProgram(
                jax.jit(run, donate_argnums=(2,)), read_kernels=False,
                on_compile=self._note_prefill_temp)

        # One lru-bounded executable per prompt length for BOTH kv
        # modes; a paged one writes the row's pages in the same program
        # (a separate jit of the [L, P, ...] insert would accumulate
        # an unbounded compile cache over prompt-length diversity).
        @lru_cache(maxsize=16)
        def compiled_prefill(plen: int):
            if self.kv == "paged":
                ps = page_size

                def run(params, prompt, cache, page_ids, *row):
                    # What the family's prefill returns (K and V; a
                    # hybrid family's state with them) goes to its
                    # insert as it is; `row` (`_row_arg`) says where a
                    # cache with per-row leaves keeps this one's.
                    return family.paged_insert_prefill(
                        cache, *family.paged_prefill_kv(
                            cfg, params, prompt), page_ids, ps, *row)

                return prefill_program(run)

            def run(params, prompt):
                return family.cb_prefill(cfg, params,
                                         prompt, self.max_len)

            return jax.jit(run)

        self._compiled_prefill = compiled_prefill
        self._insert = (None if kv == "paged" else
                        jax.jit(family.insert_cache_row,
                                donate_argnums=(0,)))

        # Radix prefix reuse (paged only): one jitted page duplicator
        # for copy-on-write forks (src/dst are traced scalars — every
        # fork shares ONE executable), and an lru-bounded suffix
        # prefill per (BUCKETED suffix length, prefix-page count) that
        # computes KV only for the tokens the radix cache did NOT
        # match. The cached-token count `m` and the real (pre-padding)
        # suffix length are traced, so requests with different match
        # depths but equal bucketed shapes share the program — at most
        # O(log max_suffix) compiles per prefix-page count instead of
        # one per distinct suffix length (bucket_suffix_len).
        self._copy_page = None
        self._suffix_prefill = None
        if kv == "paged":
            n_pages = self._pool.n_pages

            def copy_page(cache, src, dst):
                # The leaves with a page axis (`page_bytes`' rule); a
                # counter beside them is no page's content.
                return {name: arr.at[:, dst].set(arr[:, src])
                        if arr.ndim >= 3 and arr.shape[1] == n_pages
                        else arr for name, arr in cache.items()}

            self._copy_page = jax.jit(copy_page, donate_argnums=(0,))

            def copy_row(cache, src, dst):
                return {**cache, "rows": {
                    name: jax.lax.dynamic_update_slice_in_dim(
                        arr, jax.lax.dynamic_slice_in_dim(arr, src, 1, 1),
                        dst, 1)
                    for name, arr in cache["rows"].items()}}

            # What a lane row carries follows its pages to the decode
            # slot (`_lane_handoff`).
            self._copy_row = jax.jit(copy_row, donate_argnums=(0,))
            if (hasattr(family, "paged_prefill_suffix_kv")
                    and hasattr(family, "paged_gather_prefix")):
                ps = page_size

                # 32, not 16: the prefill LANE reuses this cache with
                # bucketed (chunk length, prefix-page) pairs on top of
                # the classic suffix shapes.
                @lru_cache(maxsize=32)
                def compiled_suffix_prefill(slen: int, n_pref: int):
                    def run(params, suffix, cache, page_ids, m, real_len,
                            *row):
                        pref = jnp.maximum(page_ids[:n_pref], 0)
                        # A row's state is carried past the padding by
                        # the pass itself, which is told where it ends.
                        extent = (m, real_len) if row else (m,)
                        novel = family.paged_prefill_suffix_kv(
                            cfg, params, suffix,
                            *family.paged_gather_prefix(cache, pref, *row),
                            *extent)
                        # Padded tail positions (>= real_len) carry
                        # garbage KV; the insert routes them to the
                        # scratch page. Real positions are unaffected:
                        # causality already masks padded KEYS from
                        # real queries (padding sits after every real
                        # position), so no extra attention mask.
                        return family.paged_insert_suffix(
                            cache, *novel, page_ids, m, ps, real_len, *row)

                    return prefill_program(run)

                if self._window_tables is not None:
                    compiled_suffix_prefill = self._windowed_suffix_prefill(
                        prefill_program)
                self._suffix_prefill = compiled_suffix_prefill
        # Cache-aware admission: scan a bounded window of the pending
        # queue and admit the admissible request with the hottest
        # matched prefix; a request overtaken `_admit_skip_cap` times
        # becomes a barrier (bounded starvation, same shape as the
        # scheduler's aging rule). Rolling per-admission hit window
        # feeds the polyaxon_serving_prefix_hit_rate gauge — unset
        # until it holds enough samples, so cold starts cannot page.
        self._admit_window = 32
        self._admit_skip_cap = 16
        self._prefill_tokens_total = 0
        self._prefill_tokens_skipped = 0
        # Under a window a suffix program starts below its match: of the
        # matched tokens, those it computed again (0 for any other pool).
        self._prefill_tokens_matched = 0
        self._prefill_tokens_recomputed = 0
        self._hit_window: collections.deque = collections.deque(maxlen=64)
        self._hit_window_min = 8

        if draft is not None:
            draft_family, draft_cfg = self._draft_family, self._draft_cfg

            @lru_cache(maxsize=16)
            def compiled_draft_prefill(plen: int):
                def run(draft_params, prompt):
                    return draft_family.cb_prefill(
                        draft_cfg, draft_params, prompt, self.max_len)

                return jax.jit(run)

            self._compiled_draft_prefill = compiled_draft_prefill
            self._draft_insert = jax.jit(draft_family.insert_cache_row,
                                         donate_argnums=(0,))

            # One executable PER DRAFT LENGTH (the scan length is
            # static): the speculation policy retunes k per tick, and
            # k only ever takes values in 1..spec_k, so the compile
            # count is bounded by spec_k. Greedy speculation is
            # lossless for ANY k — the target verifies — so varying k
            # across rounds (including k=0 plain-step rounds, which
            # leave draft-cache holes that degrade ACCEPTANCE, never
            # output) changes throughput only.
            @lru_cache(maxsize=16)
            def spec_round_for(k_spec: int):
                def spec_round(params, draft_params, cache_t, cache_d,
                               cur, pos, budget_left):
                    """One draft→verify round for the whole pool.
                    Returns (candidates [B, k+1], emit [B], next cur,
                    caches). Idle rows (pos < 0) run with clamped
                    positions and emit 0 — their cache rows are garbage
                    the next admission's insert replaces wholesale."""
                    B = cur.shape[0]
                    rows = jnp.arange(B)
                    live = pos >= 0
                    p0 = jnp.maximum(pos, 0)

                    def draft_step(carry, _):
                        cache_d, tok, p = carry
                        lg, cache_d = draft_family.decode_step_ragged(
                            draft_cfg, draft_params, cache_d, tok, p)
                        nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                        return (cache_d, nxt, p + 1), nxt

                    # k+1 draft steps for k proposals: the extra step
                    # writes the LAST proposal's draft KV (same
                    # hole-free invariant as speculative.py).
                    (cache_d, _, _), d = jax.lax.scan(
                        draft_step, (cache_d, cur, p0), None,
                        length=k_spec + 1)
                    d = d.T[:, :k_spec]  # [B, k]

                    chunk = jnp.concatenate([cur[:, None], d], axis=1)
                    logits, cache_t = family.decode_chunk(
                        cfg, params, cache_t, chunk, p0)
                    t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    match = (d == t[:, :k_spec]).astype(jnp.int32)
                    accepted = jnp.cumprod(match, axis=1).sum(axis=1)
                    emit = jnp.minimum(accepted + 1, budget_left)
                    emit = jnp.where(live, emit, 0)
                    cur_nxt = jnp.where(
                        emit > 0, t[rows, jnp.maximum(emit - 1, 0)], cur)
                    return t, emit, cur_nxt, cache_t, cache_d

                return jax.jit(spec_round, donate_argnums=(2, 3))

            self._spec_round_for = spec_round_for
        # Speculation as a POLICY OUTPUT (ISSUE 18), not a static
        # flag: each decode-lane tick asks the policy for the draft
        # length given live pressure (prefill backlog, decode
        # headroom, oldest queue wait). k=0 falls back to a plain
        # decode step. Injectable for tests; draft-less engines
        # carry no policy.
        self._spec_policy = None
        self._spec_proposed = 0
        self._spec_accepted = 0
        if draft is not None:
            self._spec_policy = (spec_policy if spec_policy is not None
                                 else SpeculationPolicy(self.spec_k))

        if prefill_chunk is not None:
            if draft is not None and not hasattr(self._draft_family,
                                                 "decode_chunk"):
                raise ValueError(
                    "chunked prefill with a draft needs the draft "
                    "family's decode_chunk too")

            def chunk_write(params, row_cache, tokens, pos0):
                """Write one [1, c] chunk of prompt KV into a
                standalone row cache; logits discarded. The padded
                tail's junk writes land at positions decode rewrites
                before anything attends them (slot == position)."""
                _, row_cache = family.decode_chunk(
                    cfg, params, row_cache, tokens, pos0)
                return row_cache

            self._chunk_write = jax.jit(chunk_write, donate_argnums=(1,))
            if draft is not None:
                def draft_chunk_write(draft_params, row_cache, tokens,
                                      pos0):
                    _, row_cache = self._draft_family.decode_chunk(
                        self._draft_cfg, draft_params, row_cache,
                        tokens, pos0)
                    return row_cache

                self._draft_chunk_write = jax.jit(
                    draft_chunk_write, donate_argnums=(1,))

        self._thread = threading.Thread(
            target=self._loop, name="plx-serving-batcher", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- public
    def _validate(self, tokens: list[int], max_new_tokens: int) -> None:
        if not tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        # Budget semantics are family-specific: decoder-only models
        # share one cache between prompt and generation; seq2seq bounds
        # encoder prompt and decode budget separately.
        self._family_mod.cb_validate(self.cfg, len(tokens), max_new_tokens,
                                     self.max_len)
        if self.draft is not None:
            # Verify rounds write KV up to k positions past the budget
            # (a nearly-done row still runs a full draft window): the
            # full-length cache must hold that headroom or the ring
            # wrap would scribble over the prompt start.
            need = len(tokens) + max_new_tokens + self.spec_k + 1
            if need > self.max_len:
                raise ValueError(
                    f"prompt {len(tokens)} + max_new {max_new_tokens} + "
                    f"draft window {self.spec_k}+1 exceeds the cache "
                    f"length {self.max_len} (speculative rounds need "
                    "the headroom)")
        if self._pool is not None:
            # A request that cannot fit the pool even when it is the
            # only tenant would wait at the FIFO head forever (and
            # block everyone behind it) — reject it up front. Written
            # positions span 0..len+max_new-2.
            need = self._pool.pages_for(len(tokens) + max_new_tokens - 1)
            capacity = self._pool.n_pages - 1
            if need > capacity:
                raise ValueError(
                    f"request needs {need} KV pages (prompt {len(tokens)} "
                    f"+ {max_new_tokens} new) but the pool holds "
                    f"{capacity}; raise --kv-pages or shorten the request")

    def _reject(self, reason: str) -> None:
        """Shed-load accounting: QueueFull 503s and post-stop submits
        must not vanish — the counter is THE load-shedding signal on
        /metrics and the dashboard (ISSUE 10 satellite)."""
        self._rejected[reason] = self._rejected.get(reason, 0) + 1
        obs_metrics.serving_rejected_total(self._obs).inc(reason=reason)

    def submit(self, tokens: list[int], max_new_tokens: int,
               temperature: float = 0.0, seed: int = 0,
               top_p: float = 1.0, top_k: int = 0,
               eos_tokens=None, klass: str = "batch",
               request_id: Optional[str] = None,
               trace_parent: Optional[str] = None,
               route_record: Optional[dict] = None) -> _Request:
        """`request_id`/`trace_parent`/`route_record` carry a
        propagated trace context (ISSUE 20): the fleet front door
        pre-generates the id, opens a `route` span, and the engine's
        `request` root nests under it — one trace id, one cross-
        component timeline."""
        self._validate(tokens, max_new_tokens)
        validate_sampling(top_p, top_k)
        eos = frozenset(int(t) for t in (eos_tokens or ()))
        if self.draft is not None and temperature > 0:
            raise ValueError(
                "this engine speculates with a draft model, which is "
                "greedy-only (acceptance compares the target's own "
                "argmax); send temperature=0 or serve without "
                "--draft-model for sampling")
        req = _Request(list(tokens), max_new_tokens, float(temperature),
                       int(seed), float(top_p), int(top_k), eos,
                       klass=str(klass) or "batch")
        if request_id:
            req.id = str(request_id)
        if self._pool is not None and self._pool.prefix_cache:
            req.match_key = np.asarray(req.tokens, np.int32)
        if self.request_tracing:
            # Built BEFORE the lock (span allocation off the critical
            # section); ringed only AFTER a successful enqueue so
            # rejected requests never occupy ring capacity.
            req.trace = reqtrace.RequestTrace(
                req.id, req.klass,
                component=self._obs_component,
                parent_id=trace_parent,
                extra_records=[route_record] if route_record else None,
                prompt_len=len(req.tokens),
                max_new=int(max_new_tokens))
            req.trace.start_phase("queue_wait")
        with self._cv:
            if self._stopped:
                self._reject("shutdown")
                raise RuntimeError("engine stopped")
            depth = self._queue_depth()
            if self.max_pending is not None and depth >= self.max_pending:
                self._reject("queue_full")
                # Retry-After scales with how much decode work sits
                # ahead of the caller: ~one hint-second per queued
                # request per slot, floored at 1.
                raise QueueFull(
                    f"pending queue is full ({depth}/"
                    f"{self.max_pending}); retry later",
                    retry_after=max(1, depth // max(self.slots, 1)))
            key = self._queue_key(req.klass)
            q = self._queues[key]
            cap = (self._class_caps.get(key)
                   if self.class_admission else None)
            if cap is not None and len(q) >= cap:
                self._reject("class_queue_full")
                raise QueueFull(
                    f"`{key}` pending queue is full ({len(q)}/{cap}); "
                    f"retry later",
                    retry_after=max(1, len(q) // max(self.slots, 1)))
            req.seq = self._seq
            self._seq += 1
            q.append(req)
            self._publish_queue_depth()
            self._cv.notify()
        if req.trace is not None:
            self._ring.add(req.trace)
        return req

    def cancel(self, req: _Request) -> None:
        """Drop a request: dequeued if still waiting, retired at the
        next loop iteration if live. Waiters see error='cancelled'."""
        req.cancelled = True
        with self._cv:
            try:
                self._queue_for(req).remove(req)
                if not req.done.is_set():
                    req.error = "cancelled"
                    self._finish_trace(req)
                    req.done.set()
            except ValueError:
                pass  # live in a slot (or done): the loop retires it

    def submit_all(self, token_rows: list[list[int]], max_new_tokens: int,
                   temperature: float = 0.0, seed: int = 0,
                   top_p: float = 1.0, top_k: int = 0,
                   eos_tokens=None, klass: str = "batch") -> list[_Request]:
        """Submit a batch atomically-ish: validate every row before
        submitting ANY (same no-wasted-work contract as the static
        engine — a bad row must not leave its siblings generating
        discarded output), and if a mid-batch submit is shed
        (QueueFull/stop) cancel the rows already queued before
        re-raising — the caller sees all-or-nothing."""
        for row in token_rows:
            self._validate(row, max_new_tokens)
        reqs: list[_Request] = []
        try:
            for i, row in enumerate(token_rows):
                reqs.append(self.submit(
                    row, max_new_tokens, temperature, seed + i,
                    top_p, top_k, eos_tokens=eos_tokens, klass=klass))
        except Exception:
            for r in reqs:
                self.cancel(r)
            raise
        return reqs

    def generate(self, token_rows: list[list[int]], max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 top_p: float = 1.0, top_k: int = 0,
                 timeout: Optional[float] = None,
                 eos_tokens=None, klass: str = "batch") -> list[list[int]]:
        if not token_rows:
            return []
        reqs = self.submit_all(token_rows, max_new_tokens, temperature,
                               seed, top_p, top_k, eos_tokens=eos_tokens,
                               klass=klass)
        try:
            return [r.wait(timeout=timeout) for r in reqs]
        except TimeoutError:
            for r in reqs:  # don't keep burning slots on abandoned work
                if not r.done.is_set():
                    self.cancel(r)
            raise

    def _finalize_stop(self) -> None:
        """After the loop thread has really exited, unblock every waiter
        it will never serve. Runs post-join, so it cannot race the
        loop's own done.set() calls."""
        self._thread.join()
        with self._cv:
            pending = [state[0] for state in self._prefilling.values()]
            pending += [state[0] for state in self._lane.values()]
            # A loop that died mid-step leaves its launches unread.
            pending += [row[1] for launch in self._unread
                        for row in launch.rows]
            for req in self._pending_requests() + self._slot_req + pending:
                if req is not None and not req.done.is_set():
                    req.error = "engine stopped"
                    self._finish_trace(req)
                    req.done.set()
        if self._hit_window:
            # This engine fed the shared prefix-hit-rate gauge; its
            # rolling window dies with it. Unset rather than leave the
            # last value parked: instant threshold rules read the live
            # registry, so a stopped engine's stale low watermark would
            # hold serving-prefix-hit-collapse in a breach that no
            # amount of clock fast-forward can ever resolve. A live
            # engine re-sets the gauge on its next admission.
            obs_metrics.serving_prefix_hit_rate(self._obs).unset()
        self._dump_ring()

    def _dump_ring(self) -> None:
        """Persist the request-timeline ring at shutdown (ISSUE 13):
        the serving mirror of the flight recorder's postmortem, so
        request evidence survives process exit and sim.replay can turn
        it into an arrival trace. Fail-open — a dump failure must not
        turn a clean stop into a crash; both outcomes are counted."""
        if not self.trace_dump_path or not self.request_tracing:
            return
        # The dump path must work on a skeleton engine (no __init__ —
        # postmortem tooling builds one around a recovered ring), so the
        # scoped view is optional here.
        obs = getattr(self, "_obs", None) or obs_metrics.REGISTRY
        clock = getattr(self, "_clock", None)
        try:
            path = reqtrace.dump_ring(
                self._ring, self.trace_dump_path,
                slow_ticks=list(clock.slow) if clock else [])
            obs_metrics.serving_trace_dumps_total(obs).inc(outcome="ok")
            logger.info("request-timeline ring dumped to %s", path)
        except Exception:
            obs_metrics.serving_trace_dumps_total(obs).inc(outcome="error")
            logger.warning("request-timeline ring dump to %s failed",
                           self.trace_dump_path, exc_info=True)

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            # A long compile/step is still in flight; the loop exits at
            # its next iteration top. Hand the final bookkeeping to a
            # watcher so waiters are guaranteed to unblock eventually
            # without stop() hanging on a wedged device.
            logger.warning("batching loop still draining at stop(); "
                           "waiters will be released when it exits")
            # polycheck: ignore[invariant-daemon-drain] -- deliberately unjoined: the watcher exists so stop() does NOT hang on a wedged device; it only releases waiters
            threading.Thread(target=self._finalize_stop,
                             name="plx-batcher-finalize",
                             daemon=True).start()
            return
        self._finalize_stop()

    # -------------------------------------------------------------- loop
    def _fail_fast(self, err: str) -> None:
        """Persistent device breakage (e.g. OOM): admitting the queue
        against it would fail serially, one compiled program per
        request. Fail live slots AND drain the queue, then stop the
        engine; submit() refuses new work. Live slots must be retired
        here — the loop thread exits right after, and nothing else
        would ever set their done events (their waiters would hang)."""
        logger.error(
            "%d consecutive device-program failures; draining queue and "
            "stopping engine", self._consec_step_failures)
        with self._cv:
            # First, so that a waiter released below finds the engine
            # stopped whenever it looks.
            self._stopped = True
        self._fail_live(f"engine failed: {err}")
        for b, state in list(self._prefilling.items()):
            req = state[0]
            del self._prefilling[b]
            if not req.done.is_set():
                req.error = f"engine failed: {err}"
                self._finish_trace(req)
                req.done.set()
        for p in list(self._lane):
            self._drop_lane_reservation(p, f"engine failed: {err}")
        with self._cv:
            for q in self._queues.values():
                while q:
                    req = q.popleft()
                    if not req.done.is_set():
                        req.error = f"engine failed: {err}"
                        self._finish_trace(req)
                        req.done.set()

    # --------------------------------------------------- pending queues
    def _queue_key(self, klass: str) -> str:
        """Which pending queue a request class lands in. FIFO mode (the
        A/B baseline) merges everything into one queue — the pre-19
        scan semantics depend on global arrival order."""
        if not self.class_admission or klass not in REQUEST_CLASSES:
            return DEFAULT_REQUEST_CLASS
        return klass

    def _queue_for(self, req: _Request) -> collections.deque:
        return self._queues[self._queue_key(req.klass)]

    def _queue_depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _queue_head(self) -> Optional[_Request]:
        """Oldest pending request across every class queue."""
        heads = [q[0] for q in self._queues.values() if q]
        return min(heads, key=lambda r: r.seq) if heads else None

    def _pending_requests(self) -> list[_Request]:
        return [r for q in self._queues.values() for r in q]

    def _publish_queue_depth(self) -> None:
        obs_metrics.serving_queue_depth(self._obs).set(self._queue_depth())
        if self.class_admission:
            gauge = obs_metrics.serving_class_pending(self._obs)
            for name, q in self._queues.items():
                gauge.set(len(q), **{"class": name})

    def _pick_next_locked(self) -> Optional[_Request]:
        """Choose the next request to admit (caller holds ``_cv``).

        FIFO mode (``class_admission=False``): the pre-19 policy,
        unchanged — dense pops strict FIFO; paged scans a bounded
        window of the one queue and picks the admissible request whose
        radix-matched prefix is hottest (most cached tokens), strict
        `>` keeping FIFO among ties, with the skip-cap barrier
        bounding starvation.

        Class mode: every class queue's window is scanned and the
        rank tuple ``(class priority, TTFT-deadline urgency, matched-
        token hotness, age)`` picks the winner. Urgency is a bucket —
        a request past its class TTFT target outranks a hotter fresh
        one of the same class; below target, hotness keeps the radix
        dividend (the PR 11 behavior within a class). The starvation
        barrier is per class: a request at its class skip cap stops
        younger SAME-CLASS requests from passing (if it fits, its
        infinite hotness wins its tier outright); across classes
        priority stays strict, and the per-class pending cap is the
        shed-load bound on that starvation. None = nothing admissible
        right now (backpressure)."""
        if self._pool is None:
            if not self.class_admission:
                return self._queues[DEFAULT_REQUEST_CLASS].popleft()
            best = None
            for name, q in self._queues.items():
                if not q:
                    continue
                key = (resolve_request_class(name).priority, -q[0].seq)
                if best is None or key > best[0]:
                    best = (key, q)
            return best[1].popleft() if best is not None else None
        if not self.class_admission:
            q = self._queues[DEFAULT_REQUEST_CLASS]
            best_i, best_score = None, -1.0
            for i in range(min(len(q), self._admit_window)):
                req = q[i]
                barrier = req.admit_skips >= self._admit_skip_cap
                matched = self._pool.admissible_match(
                    len(req.tokens), req.match_key)
                if matched is not None:
                    score = float("inf") if barrier else float(matched)
                    if score > best_score:
                        best_i, best_score = i, score
                if barrier:
                    break
            if best_i is None:
                return None
            for i in range(best_i):
                q[i].admit_skips += 1
            req = q[best_i]
            del q[best_i]
            return req
        now = time.time()
        best = None  # ((priority, overdue, hotness, -seq), queue, index)
        for name, q in self._queues.items():
            if not q:
                continue
            rc = resolve_request_class(name)
            for i in range(min(len(q), self._admit_window)):
                req = q[i]
                barrier = req.admit_skips >= rc.skip_cap
                matched = self._pool.admissible_match(
                    len(req.tokens), req.match_key)
                if matched is not None:
                    hot = float("inf") if barrier else float(matched)
                    overdue = int(now - req.submitted_at > rc.ttft_target)
                    key = (rc.priority, overdue, hot, -req.seq)
                    if best is None or key > best[0]:
                        best = (key, q, i)
                if barrier:
                    break
        if best is None:
            return None
        _, q, best_i = best
        for i in range(best_i):
            q[i].admit_skips += 1  # within-class aging only
        req = q[best_i]
        del q[best_i]
        return req

    def _note_prefix_outcome(self, req: _Request, res,
                             prefill_len: int) -> int:
        """Per-admission radix-reuse accounting: counters, the rolling
        hit-rate gauge, and the request's cached-token stamp. Returns
        the prefill tokens to skip: those matched, or under a window
        those below the suffix program's start."""
        skip = matched = min(res.matched_tokens, prefill_len)
        if matched and self._window_tables is not None:
            skip = self._pool.suffix_start(matched)
            self._prefill_tokens_recomputed += matched - skip
        self._prefill_tokens_matched += matched
        req.prefix_cached_tokens = skip
        outcome = ("full" if skip >= prefill_len
                   else "partial" if skip > 0 else "miss")
        obs_metrics.serving_prefix_hits_total(self._obs).inc(outcome=outcome)
        if skip:
            obs_metrics.serving_prefix_cached_tokens(self._obs).inc(skip)
        self._prefill_tokens_total += prefill_len
        self._prefill_tokens_skipped += skip
        self._hit_window.append((skip, prefill_len))
        if len(self._hit_window) >= self._hit_window_min:
            denom = sum(p for _, p in self._hit_window)
            if denom:
                obs_metrics.serving_prefix_hit_rate(self._obs).set(
                    sum(s for s, _ in self._hit_window) / denom)
        if res.cow is not None and req.trace is not None:
            req.trace.event("cow_fork", src=int(res.cow[0]),
                            dst=int(res.cow[1]))
        if req.preemptions:
            # Re-admission after eviction: the novel suffix is the real
            # recompute cost of preempting this request — the committed
            # prefix came back from the radix tree for free.
            novel = max(prefill_len - skip, 0)
            if novel:
                self._readmit_suffix_tokens += novel
                obs_metrics.serving_readmit_suffix_tokens_total(self._obs).inc(
                    novel)
        return skip

    def _admit(self) -> None:
        for b in range(self.slots):
            if self._slot_req[b] is not None or b in self._prefilling:
                continue
            # Pick under the lock: cancel() mutates the queue from HTTP
            # threads, and an unsynchronized pop can race it empty.
            with self._phase("admit.pick"), self._cv:
                if not self._queue_depth():
                    break
                req = self._pick_next_locked()
                if req is None:
                    # Paged backpressure: nothing in the scan window
                    # fits the pool right now — wait for retirements
                    # to free pages. One head annotation per engine
                    # tick while blocked (the per-span event cap
                    # bounds a long wait): answers "why is my request
                    # stuck in queue_wait" from the timeline alone.
                    head = self._queue_head()
                    if head is not None and head.trace is not None:
                        head.trace.event("kv_backpressure",
                                         pages_free=self._pool.free_pages)
                    break
                self._publish_queue_depth()
            admit_res = None
            if self._pool is not None:
                with self._phase("admit.match"):
                    admit_res = self._pool.admit(b, len(req.tokens),
                                                 req.tokens)
                if not admit_res:
                    # can_admit raced/drifted: put the request back at
                    # the head (FIFO preserved) and wait for
                    # retirements — running without pages would stream
                    # scratch-page garbage.
                    obs_metrics.serving_admissions_total(self._obs).inc(
                        outcome="deferred")
                    if req.trace is not None:
                        req.trace.event("requeue", reason="kv_pages")
                    with self._cv:
                        self._queue_for(req).appendleft(req)
                    break
            # Dequeued for real: close the queue_wait phase and feed
            # the SLO histogram (submit → admission dequeue).
            self._admissions += 1
            obs_metrics.serving_queue_wait_hist(self._obs).observe(
                time.time() - req.submitted_at, **{"class": req.klass})
            if req.trace is not None:
                req.trace.end_phase(slot=b)
            try:
                pos0, tok0, prefill_tokens = self._family_mod.cb_admission(
                    req.tokens)
                skip = 0
                if admit_res is not None:
                    skip = self._note_prefix_outcome(
                        req, admit_res, len(prefill_tokens or ()))
                with self._phase("admit.prefill"):
                    self._admit_prefill(b, req, admit_res, skip, pos0,
                                        tok0, prefill_tokens)
            except Exception as exc:  # noqa: BLE001 — request-scoped
                if self._pool is not None:
                    # Failed admission frees pages AND forgets any
                    # prefix keys registered for content the prefill
                    # never wrote.
                    self._pool.release(b, invalidate_prefix=True)
                obs_metrics.serving_admissions_total(self._obs).inc(
                    outcome="failed")
                req.error = f"{type(exc).__name__}: {exc}"
                self._finish_trace(req)
                req.done.set()
                # Persistent device breakage surfaces in the admission
                # prefill just as readily as in the decode step — count
                # it toward the same fail-fast budget so a broken
                # device doesn't burn one prefill per queued request
                # (_count_request_failure has the counting rules).
                if not self._count_request_failure(exc):
                    return

    def _admit_prefill(self, b: int, req: _Request, admit_res, skip: int,
                       pos0: int, tok0: int, prefill_tokens) -> None:
        """What an admission runs on the device: the CoW fork, then the
        prefill program for what the radix cache did not serve, and the
        slot goes live (or, for a long prompt under `prefill_chunk`,
        is reserved for `_advance_prefill`)."""
        if admit_res is not None and admit_res.cow is not None:
            # Fork the partially-shared page ONCE on device; the suffix
            # prefill then writes only the divergent tokens into the
            # private copy.
            src, dst = admit_res.cow
            self._cache = self._copy_page(
                self._cache, jnp.int32(src), jnp.int32(dst))
        if (prefill_tokens and self.prefill_chunk is not None
                and len(prefill_tokens) > self.prefill_chunk):
            # Long prompt: reserve the slot and stream the
            # prompt in chunks across loop iterations instead
            # of blocking the pool on one monolithic prefill.
            if req.trace is not None:
                req.trace.start_phase(
                    "prefill", mode="chunked",
                    prompt_tokens=len(prefill_tokens),
                    chunk=self.prefill_chunk)
            row_t = self._family_mod.cb_init_cache(
                self.cfg, 1, self.max_len)
            row_d = (self._draft_family.cb_init_cache(
                self._draft_cfg, 1, self.max_len)
                if self.draft is not None else None)
            self._prefilling[b] = [
                req, np.asarray(prefill_tokens, np.int32), 0,
                row_t, row_d, pos0, tok0]
            return
        if prefill_tokens:
            # What the tree matched; `skip` is where the computation
            # starts, which only a pool with a window space puts lower.
            matched = (min(admit_res.matched_tokens, len(prefill_tokens))
                       if admit_res else 0)
            if skip >= len(prefill_tokens):
                # Whole prefill served from the radix cache:
                # every page is already written — no program
                # runs at all, decode starts immediately.
                if req.trace is not None:
                    req.trace.start_phase(
                        "prefill", mode="cached",
                        prompt_tokens=len(prefill_tokens),
                        cached_tokens=skip)
            elif matched > 0 and self._suffix_prefill is not None:
                # Partial hit: compute KV only for the novel
                # suffix, attending the matched prefix pages
                # gathered from the pool — O(S·P) instead of
                # the full O(P²) recompute. Under a window the
                # suffix starts below the match (`skip` < `matched`).
                if req.trace is not None:
                    req.trace.start_phase(
                        "prefill", mode="suffix",
                        prompt_tokens=len(prefill_tokens),
                        cached_tokens=skip,
                        recomputed_tokens=matched - skip,
                        state_pages_written=self._state_pages(
                            skip, len(prefill_tokens)))
                suffix = prefill_tokens[skip:]
                n_pref = -(-matched // self._pool.page_size)
                bucket = self._suffix_bucket(len(suffix), matched - skip)
                padded = np.zeros(bucket, np.int32)
                padded[:len(suffix)] = suffix
                fn = self._suffix_prefill(bucket, n_pref)
                self._cache = fn(
                    self.params,
                    jnp.asarray([padded], jnp.int32),
                    self._cache,
                    jnp.asarray(self._pool.padded_row(b)),
                    jnp.int32(matched),
                    jnp.int32(len(suffix)), *self._row_arg(b))
            else:
                if req.trace is not None:
                    req.trace.start_phase(
                        "prefill", mode="monolithic",
                        prompt_tokens=len(prefill_tokens),
                        state_pages_written=self._state_pages(
                            0, len(prefill_tokens)),
                        **({"window_pages": int(np.count_nonzero(
                            self._window_tables[b] >= 0))}
                           if self._window_tables is not None else {}))
                row = jnp.asarray([prefill_tokens], jnp.int32)
                fn = self._compiled_prefill(len(prefill_tokens))
                if self._pool is not None:
                    self._cache = fn(
                        self.params, row, self._cache,
                        jnp.asarray(self._pool.padded_row(b)),
                        *self._row_arg(b))
                else:
                    row_cache = fn(self.params, row)
                    self._cache = self._insert(
                        self._cache, row_cache, jnp.int32(b))
        if prefill_tokens and self.draft is not None:
            # The draft's cache row prefills the same prompt
            # prefix; its first query (cur at pos) writes
            # position pos inside the round. (Drafts require
            # kv='dense', so the radix skip never applies —
            # `row` was built by the monolithic branch.)
            draft_row = self._compiled_draft_prefill(
                len(prefill_tokens))(self._draft_params, row)
            self._draft_cache = self._draft_insert(
                self._draft_cache, draft_row, jnp.int32(b))
        if self._pool is not None:
            # The prefill (or full cache hit) really wrote the
            # pages this admission registered: the fresh radix
            # leaf survives the slot from here on.
            self._pool.commit_prefix(b)
        self._go_live(b, req, pos0, tok0)

    def _note_prefill_temp(self, temp_bytes: Optional[int]) -> None:
        if temp_bytes is not None:
            self._prefill_temp_bytes = max(
                self._prefill_temp_bytes or 0, temp_bytes)

    def _suffix_bucket(self, n: int, recomputed: int) -> int:
        """The padded length of a suffix program over ``n`` tokens, the
        first ``recomputed`` of them matched ones computed again (under
        a window; 0 elsewhere): that stretch as it is, `bucket_suffix_len`
        of the novel ones behind it, and under a window the sum rounded
        up to the family's whole flash tiles, which its sequence passes
        pad to anyway: fewer programs for the same work."""
        bucket = recomputed + bucket_suffix_len(max(n - recomputed, 1))
        if self._window_tables is not None:
            tile = self._family_mod.PREFILL_TILE
            bucket = -(-bucket // tile) * tile
        return bucket

    def _windowed_suffix_prefill(self, prefill_program):
        """`_suffix_prefill` for a pool with a window space: the program
        over a run that starts below the match (`WindowedPagePool.
        suffix_start`; both are plain numbers, such a pool matches whole
        pages). Its full layers read the matched pages and write from
        the match on, its window layers start empty and write the row's
        own window pages (the family's suffix surface, ``models/
        smallthinker.py``)."""
        family, cfg, ps = self._family_mod, self.cfg, self._pool.page_size

        @lru_cache(maxsize=32)
        def compiled_suffix_prefill(slen: int, n_pref: int):
            matched = n_pref * ps
            start = self._pool.suffix_start(matched)

            def run(params, suffix, cache, page_ids, m, real_len):
                del m  # `matched`, told by the count of pages
                pref = jnp.maximum(page_ids[0, :n_pref], 0)
                novel = family.paged_prefill_suffix_kv(
                    cfg, params, suffix,
                    *family.paged_gather_prefix(cache, pref), start)
                return family.paged_insert_suffix(
                    cache, *novel, page_ids, start, matched, real_len)

            # A profile tells this program from a whole-prompt one by
            # its module's name, `jit_run.suffix`; `jit_run` finds both.
            run.__name__ = "run.suffix"
            return prefill_program(run)

        return compiled_suffix_prefill

    def _row_arg(self, row: int) -> tuple:
        """What a prefill program is told beside the block table: the
        row itself, for a cache with per-row leaves; nothing else."""
        return (jnp.int32(row),) if self._page_bytes[2] else ()

    def _state_pages(self, start: int, stop: int) -> int:
        """Pages a prefill of positions start..stop-1 leaves a per-page
        state in (0 for a cache of K and V alone)."""
        if not self._page_bytes[1] or stop <= start:
            return 0
        ps = self._pool.page_size
        return (stop - 1) // ps - start // ps + 1

    # ------------------------------------------------------ prefill lane
    def _admit_lane(self) -> None:
        """Disaggregated admission: queued requests land on free
        prefill-lane ROWS (never directly on a decode slot). The pool
        admission is identical to the classic path — radix match,
        page adoption, CoW fork, fresh-leaf registration — but no
        prefill program runs here; the lane tick streams the novel
        suffix in chunks and the handoff moves the finished row."""
        for p in range(self.slots, self.slots + self.prefill_slots):
            if p in self._lane:
                continue
            with self._phase("admit.pick"), self._cv:
                if not self._queue_depth():
                    break
                req = self._pick_next_locked()
                if req is None:
                    head = self._queue_head()
                    if head is not None and head.trace is not None:
                        head.trace.event(
                            "kv_backpressure",
                            pages_free=self._pool.free_pages)
                    break
                self._publish_queue_depth()
            with self._phase("admit.match"):
                admit_res = self._pool.admit(p, len(req.tokens), req.tokens)
            if not admit_res:
                obs_metrics.serving_admissions_total(self._obs).inc(
                    outcome="deferred")
                if req.trace is not None:
                    req.trace.event("requeue", reason="kv_pages")
                with self._cv:
                    self._queue_for(req).appendleft(req)
                break
            self._admissions += 1
            obs_metrics.serving_queue_wait_hist(self._obs).observe(
                time.time() - req.submitted_at, **{"class": req.klass})
            if req.trace is not None:
                req.trace.end_phase(slot=p)
            try:
                pos0, tok0, prefill_tokens = self._family_mod.cb_admission(
                    req.tokens)
                skip = self._note_prefix_outcome(
                    req, admit_res, len(prefill_tokens or ()))
                if admit_res.cow is not None:
                    # The lane's only device work at admission; the
                    # chunks run under `prefill_chunk`.
                    with self._phase("admit.prefill"):
                        src, dst = admit_res.cow
                        self._cache = self._copy_page(
                            self._cache, jnp.int32(src), jnp.int32(dst))
                toks = np.asarray(prefill_tokens or [], np.int32)
                skip = min(skip, len(toks))
                if req.trace is not None:
                    req.trace.start_phase(
                        "prefill",
                        mode="cached" if skip >= len(toks) else "lane",
                        prompt_tokens=int(len(toks)), cached_tokens=skip,
                        slot=p)
                self._lane[p] = [req, toks, skip, pos0, tok0]
            except Exception as exc:  # noqa: BLE001 — request-scoped
                self._pool.release(p, invalidate_prefix=True)
                obs_metrics.serving_admissions_total(self._obs).inc(
                    outcome="failed")
                req.error = f"{type(exc).__name__}: {exc}"
                self._finish_trace(req)
                req.done.set()
                if not self._count_request_failure(exc):
                    return

    def _drop_lane_reservation(self, p: int, error: str) -> None:
        """Abort one staged reservation: pages freed AND the fresh
        radix leaf detached (its content was never fully written —
        exactly the failed-prefill contract `release` documents)."""
        req = self._lane.pop(p)[0]
        self._pool.release(p, invalidate_prefix=True)
        if not req.done.is_set():
            if error != "cancelled" or not req.error:
                req.error = error
            self._finish_trace(req)
            req.done.set()

    def _lane_tick(self, decode_live: int) -> bool:
        """Advance the prefill lane. While decode rows are live, at
        most ``prefill_lane_budget`` chunk programs run — a prefill
        storm can inflate its OWN latency but never occupy more than
        the budgeted share of a tick the decode batch needed. With the
        decode lane idle, every staged reservation advances (the
        cold-start argument from _advance_prefill). Returns False when
        fail-fast stopped the engine."""
        budget = (len(self._lane) if decode_live == 0
                  else self.prefill_lane_budget)
        ran = 0
        for p in list(self._lane):
            if ran >= budget:
                break
            state = self._lane[p]
            req, toks, i, pos0, tok0 = state
            if req.cancelled:
                self._drop_lane_reservation(p, "cancelled")
                continue
            if i >= len(toks):
                continue  # staged, waiting for a free decode slot
            chunk = toks[i:i + self._lane_chunk]
            bucket = bucket_suffix_len(len(chunk))
            padded = np.zeros(bucket, np.int32)
            padded[:len(chunk)] = chunk
            n_pref = self._bucket_pages(-(-i // self._pool.page_size))
            try:
                fn = self._suffix_prefill(bucket, n_pref)
                self._cache = fn(
                    self.params, jnp.asarray([padded], jnp.int32),
                    self._cache,
                    jnp.asarray(self._pool.padded_row(p)),
                    jnp.int32(i), jnp.int32(len(chunk)),
                    *self._row_arg(p))
            except Exception as exc:  # noqa: BLE001 — request-scoped
                self._drop_lane_reservation(
                    p, f"{type(exc).__name__}: {exc}")
                obs_metrics.serving_admissions_total(self._obs).inc(
                    outcome="failed")
                if not self._count_request_failure(exc):
                    return False
                continue
            ran += 1
            state[2] = i + len(chunk)
            if req.trace is not None:
                req.trace.event("chunk", pos=int(i), of=int(len(toks)))
        if ran:
            obs_metrics.serving_lane_ticks_total(self._obs).inc(lane="prefill")
        return True

    def _bucket_pages(self, n: int) -> int:
        """Bucket a prefix-page count to the next power of two (capped
        at the row width) so lane chunks share suffix executables
        across progress depths. Safe over-read: table entries past the
        real prefix gather the scratch page and _suffix_mask hides
        every prefix column >= the traced match depth m."""
        if n <= 0:
            return 0
        return min(1 << (n - 1).bit_length(),
                   self._pool.max_pages_per_row)

    def _lane_handoff(self) -> None:
        """Move finished reservations to free decode slots: commit the
        fresh radix leaf (the lane really wrote its pages), transfer
        row ownership (PagePool.handoff — refcounts conserved), and go
        live. Staging order is FIFO among finished rows; an unfinished
        head does not block a finished sibling (per-iteration
        scheduling: the decode lane should never idle on ceremony)."""
        for p in list(self._lane):
            state = self._lane[p]
            req, toks, i, pos0, tok0 = state
            if req.cancelled:
                self._drop_lane_reservation(p, "cancelled")
                continue
            if i < len(toks):
                continue
            b = next((s for s in range(self.slots)
                      if self._slot_req[s] is None), None)
            if b is None:
                return  # decode pool full: staged rows wait in place
            self._pool.commit_prefix(p)
            moved = self._pool.handoff(p, b)
            if self._page_bytes[2]:
                with self._phase("admit.prefill"):
                    self._cache = self._copy_row(
                        self._cache, jnp.int32(p), jnp.int32(b))
            del self._lane[p]
            self._handoffs += 1
            self._handoff_pages += moved
            obs_metrics.serving_handoff_pages_total(self._obs).inc(moved)
            if req.trace is not None:
                req.trace.event("handoff", src_row=p, dst_slot=b,
                                pages=moved)
            self._go_live(b, req, pos0, tok0)

    def _lane_view(self) -> LaneView:
        """Pressure snapshot for the speculation policy (and the
        health surface): prefill backlog counts everything that still
        owes prefill work — queued, dense chunked reservations, lane
        reservations."""
        with self._cv:
            backlog = (self._queue_depth() + len(self._prefilling)
                       + len(self._lane))
            head = self._queue_head()
            oldest = (time.time() - head.submitted_at
                      if head is not None else 0.0)
        free = sum(1 for b in range(self.slots)
                   if self._slot_req[b] is None
                   and b not in self._prefilling)
        return LaneView(prefill_backlog=backlog, decode_free=free,
                        oldest_wait=oldest)

    def request_timeline(self, request_id: str) -> Optional[dict]:
        """Assembled span tree for one recent request (None = unknown
        id or already evicted from the ring) — the payload behind
        ``GET /requests/{id}/timeline``."""
        return self._ring.timeline(request_id)

    def recent_requests(self) -> list[dict]:
        """Ring summaries, most recent first — ``GET /requests``."""
        return self._ring.summaries()

    def health(self) -> dict:
        """Liveness + load view for /healthz: queue depth, slot
        occupancy, radix hit rate, and paged-KV headroom — ONE polled
        surface, so a balancer (serving.router.FleetRouter) can route
        on affinity and shed on pressure without stitching /metrics
        and /v1/stats by hand."""
        denom = sum(p for _, p in self._hit_window)
        return {
            "status": "stopped" if self._stopped else "ok",
            "model": self.model,
            "engine": "continuous",
            "queued": self._queue_depth(),
            "active": sum(1 for r in self._slot_req if r is not None),
            "slots": self.slots,
            "max_pending": self.max_pending,
            # Per-class admission view (ISSUE 19): the router's
            # pressure guard reads interactive pending against its cap
            # — aggregate prefill_pending can look fine while one class
            # queue is saturated.
            "class_admission": self.class_admission,
            "class_pending": {name: len(q)
                              for name, q in self._queues.items()},
            "class_caps": dict(self._class_caps),
            "preemptions": dict(self._preemptions),
            # Per-lane depths (ISSUE 18): the router spills on PREFILL
            # pressure (work not yet decoding — queued plus staged
            # reservations) instead of total queue depth, so a replica
            # that is merely decode-busy no longer looks crowded; the
            # autoscaler reads both sides separately.
            "prefill_pending": (self._queue_depth()
                                + len(self._prefilling)
                                + len(self._lane)),
            "decode_active": sum(1 for r in self._slot_req
                                 if r is not None),
            # Rolling draft-acceptance rate (None until a draft engine
            # has proposed something): accepted draft tokens over
            # proposed — the policy's throughput dividend observable.
            "spec_tokens_accepted_rate": (
                round(self._spec_accepted / self._spec_proposed, 4)
                if self._spec_proposed else None),
            # Rolling radix prefix hit rate (same admission window as
            # the polyaxon_serving_prefix_hit_rate gauge); None until
            # the window has samples, so cold starts read as unknown,
            # not as a collapse.
            "radix_hit_rate": (
                round(sum(s for s, _ in self._hit_window) / denom, 4)
                if len(self._hit_window) >= self._hit_window_min and denom
                else None),
            # Paged-KV headroom (None on dense engines): the router
            # treats free == 0 as not-routable.
            "kv_headroom": (self._pool.utilization()
                            if self._pool is not None else None),
        }

    def stats(self) -> dict:
        """Live engine counters + occupancy gauges for /v1/stats."""
        from polyaxon_tpu.runtime import compile_cache

        return {
            "engine": "continuous",
            "slots": self.slots,
            "active": sum(1 for r in self._slot_req if r is not None),
            "prefilling": len(self._prefilling),
            "queued": self._queue_depth(),
            "queue_depth_peak": self._queue_depth_peak,
            # Class-aware admission accounting (ISSUE 19): evictions by
            # victim class, and the real recompute cost of them — novel
            # suffix tokens prefilled at re-admission (the committed
            # radix prefix served the rest).
            "class_admission": self.class_admission,
            "preemptions": dict(self._preemptions),
            "readmit_suffix_tokens": self._readmit_suffix_tokens,
            "decode_steps": self._steps_total,
            # How often the loop ran ahead of the host: plain steps
            # launched while the one before was still unread, and rows
            # computed whose token was dropped (a row that ends at a
            # stop token is seen one step late).
            "decode_steps_ahead": self._steps_ahead,
            "decode_tokens_dropped": self._tokens_dropped,
            # Host time of the loop by phase (the `engine:` spans of a
            # profile, docs/observability.md): cumulative ns per leaf
            # phase, which sum to the ticks' own time; the ticks longer
            # than max(1 s, 8 x the running median), each with its
            # phase split.
            "ticks_total": self._clock.ticks,
            "tick_phase_ns": dict(self._clock.total_ns),
            # One tick in eight also reads the engine thread's CPU
            # clock: those ticks' CPU and wall time under the same
            # keys. Wall less CPU is what the thread spent off the
            # processor there (the interpreter lock, or a call that
            # blocked). (CPU copied first: never ahead of its wall.)
            "tick_phase_cpu_ns": dict(self._clock.cpu_ns),
            "tick_phase_sampled_ns": dict(self._clock.sampled_ns),
            "ticks_sampled": self._clock.sampled_ticks,
            "slow_ticks": list(self._clock.slow),
            # A tick's length (every tick; those that ran a prefill),
            # and a token's way from its readback to the end of its
            # handler's write: counts by bucket, bucket k from
            # first_edge_ms x 2^(k / per_octave).
            "tick_ms_hist": _log_hist(
                all=self._clock.tick_hist,
                with_prefill=self._clock.prefill_tick_hist),
            "deliver_lag_hist": _log_hist(
                counts=self._deliver_lags_copy()),
            # The loop's waits with nothing live, queued or asked: in
            # no tick, so in no key of `tick_phase_ns`.
            "dry_ns": self._clock.dry_ns,
            "dry_waits": self._clock.dry_waits,
            "admissions_total": self._admissions,
            # Mean fraction of slots live per decode step: ~1.0 means
            # continuous batching is actually winning; low values with
            # a deep queue mean admission (prefill) is the bottleneck.
            "avg_occupancy": (
                round(self._live_slot_steps
                      / (self._steps_total * self.slots), 4)
                if self._steps_total else None),
            "requests_served": self._served,
            "tokens_generated": self._tokens_out,
            "step_failures": self._step_failures,
            # Shed-load accounting (ISSUE 10): per-reason totals of
            # requests refused before admission.
            "rejected": dict(self._rejected),
            "request_tracing": self.request_tracing,
            "traced_requests": len(self._ring),
            "stopped": self._stopped,
            "kv": self.kv,
            # Bytes of the weights by the dtype they are held in, read
            # once at start-up (docs/observability.md).
            "weight_bytes": dict(self._weight_bytes),
            # Of those, the projections held [N, D] as the decode dot
            # reads them (0: a plain tree), so a run says which tree it
            # measured.
            "weights_held_transposed_bytes": self._held_transposed_bytes,
            # Mosaic kernels in the compiled decode step (empty until
            # the first step compiles, and wherever attention runs a
            # reference path: the CPU mesh, or the gather formulation).
            "decode_kernels": {
                name: count for program in self._decode_programs
                for name, count in program.kernels.items()},
            # What the compiler set aside for the decode program's
            # temporaries (the larger of the two programs; None until
            # one compiles). Megabytes where the program updates the
            # page pool in place; a copy of the pool shows here whole.
            "decode_program_temp_bytes": max(
                (program.temp_bytes for program in self._decode_programs
                 if program.temp_bytes is not None), default=None),
            # The same of the paged prefill programs, whole-prompt and
            # suffix (the largest compiled so far; None until one is):
            # the prompt's own K and V and its activations where the
            # insert writes by whole pages (llama.paged_write_span).
            "prefill_program_temp_bytes": self._prefill_temp_bytes,
            "device": {**self._device_stats, "peak_hbm_bytes": (
                self._device0.memory_stats() or {}).get(
                    "peak_bytes_in_use")},
            "compile_cache": compile_cache.stats(),
            # `moe_expert_tokens` [expert layer][held expert]: (row,
            # choice) pairs of live rows the decode steps routed there;
            # `moe_pairs_elsewhere` [expert layer]: those routed to
            # experts another chip holds (families with routed experts
            # that count them; absent otherwise).
            # `mla_decode_positions`: the positions the decode steps'
            # latent attention read, over live rows and steps (a
            # family that caches a latent a token; absent otherwise),
            # and beside it the shape its kernel's loop runs at here:
            # `mla_decode_keys_per_turn`, `mla_decode_turns_in_flight`.
            **(self._read_expert_tokens() if self._expert_counters else {}),
            **(mla_decode.schedule_stats(self._pool.max_pages_per_row,
                                         self._pool.page_size)
               if "mla_decode_positions" in self._expert_counters else {}),
            **({"draft_model": self.draft[0],
                "spec_k": self.spec_k,
                "spec_rounds": self._spec_rounds,
                # Mean tokens emitted per verify round (1..k+1): THE
                # speculation-efficiency number — near 1 means the
                # draft buys nothing, near k+1 means near-full
                # acceptance.
                "spec_tokens_per_round": (
                    round(self._spec_tokens / self._spec_rounds, 3)
                    if self._spec_rounds else None),
                "spec_policy_state": self._spec_policy.state,
                "spec_tokens_accepted_rate": (
                    round(self._spec_accepted / self._spec_proposed, 4)
                    if self._spec_proposed else None)}
               if self.draft is not None else {}),
            **({"prefill_slots": self.prefill_slots,
                "lane_staging": len(self._lane),
                "handoffs": self._handoffs,
                "handoff_pages": self._handoff_pages,
                "decode_lane_budget": self.decode_lane_budget}
               if self.prefill_slots else {}),
            **({"kv_pages_total": self._pool.n_pages - 1,
                "kv_pages_free": self._pool.free_pages,
                "kv_page_size": self._pool.page_size,
                # Their ratio is the part of the block tables the paged
                # decode kernel has pages to fetch for.
                "paged_pages_live": self._paged_pages_live,
                "paged_pages_table": self._paged_pages_table,
                # Bytes one page holds over all layers (K, V and any
                # per-page state), and the state's part of it.
                "kv_page_bytes": sum(self._page_bytes[:2]),
                # Bytes a token costs over all layers in the pool's
                # per-token leaves (K and V, or a latent with its
                # padding).
                "kv_token_bytes": (self._page_bytes[0]
                                   // self._pool.page_size),
                "kv_state_bytes_per_page": self._page_bytes[1],
                # What a slot holds beside its pages, whatever its
                # length: a family's per-row recurrent state.
                "kv_state_bytes_per_slot": self._page_bytes[2],
                "kv_prefix_hits": self._pool.prefix_hits,
                "kv_prefix_misses": self._pool.prefix_misses,
                # Radix prefix-reuse dividend: prefill tokens the
                # engine did NOT recompute, plus the tree's live shape
                # and the chaos-path invariant check (non-zero means a
                # refcount/CoW accounting bug — bench and CI fail it).
                "prefill_tokens_total": self._prefill_tokens_total,
                "prefill_tokens_skipped": self._prefill_tokens_skipped,
                # Tokens the tree matched, and those of them a suffix
                # program computed again (under a window; 0 elsewhere):
                # skipped = matched - recomputed.
                "prefill_tokens_matched": self._prefill_tokens_matched,
                "prefill_tokens_recomputed": self._prefill_tokens_recomputed,
                "kv_prefix_hit_rate": (
                    round(self._prefill_tokens_skipped
                          / self._prefill_tokens_total, 4)
                    if self._prefill_tokens_total else None),
                "kv_cow_forks": self._pool.cow_forks,
                "kv_prefix_evictions": self._pool.prefix_evictions,
                "kv_radix": self._pool.radix_stats(),
                "kv_invariant_violations": len(
                    self._pool.check_invariants())}
               if self._pool is not None else {}),
            **(self._window_stats()
               if self._window_tables is not None else {}),
        }

    def _window_stats(self) -> dict:
        """The window space of a `WindowedPagePool` (`kv_pages_*` are
        the full space's, which is the one admission waits for)."""
        seen = self._pool.window_stats()
        return {"kv_window": self._pool.window,
                "kv_window_pages_total": seen["total"],
                "kv_window_pages_free": seen["free"],
                "kv_window_pages_live": seen["live"],
                "kv_window_pages_released": seen["released"],
                "kv_window_row_pages_max": seen["row_max"],
                "kv_window_page_bytes": self._window_page_bytes}

    def _go_live(self, b: int, req: _Request, pos0: int, tok0: int) -> None:
        """Mark a slot live for decode — the ONE place slot state is
        initialized (monolithic admission and chunked-prefill
        completion both land here)."""
        obs_metrics.serving_admissions_total(self._obs).inc(outcome="admitted")
        if req.trace is not None:
            # Closes the prefill phase when one ran (1-token prompts
            # go straight from queue_wait to decode).
            req.trace.start_phase("decode", slot=b, pos0=int(pos0))
        self._slot_req[b] = req
        self._pos[b] = pos0
        self._cur[b] = tok0
        self._fresh_rows.add(b)
        self._counts[b] = len(req.out)
        self._set_sampling(b, req.temperature, req.top_p, req.top_k,
                           req.seed)

    def _set_sampling(self, b: int, temperature: float = 0.0,
                      top_p: float = 1.0, top_k: int = 0,
                      seed: int = 0) -> None:
        """Slot ``b``'s sampling state (the defaults: a free slot's).
        The next decode step uploads the four vectors again."""
        self._seeds[b] = seed
        self._temps[b] = temperature
        self._top_ps[b] = top_p
        self._top_ks[b] = top_k
        self._sampling_dev = None

    def _count_request_failure(self, exc: Exception) -> bool:
        """Request-scoped device-failure accounting, shared by the
        admission prefill and the chunk writer: only RuntimeErrors
        (XLA device errors) count toward fail-fast — a ValueError is a
        bad REQUEST, and bad requests must not stop a healthy engine —
        and only a successful step resets the counter. Returns False
        when fail-fast stopped the engine."""
        if isinstance(exc, RuntimeError):
            self._step_failures += 1
            self._consec_step_failures += 1
            if self._consec_step_failures >= self.max_step_failures:
                self._fail_fast(f"{type(exc).__name__}: {exc}")
                return False
        return True

    def _advance_prefill(self, all_slots: bool = False) -> bool:
        """Advance prefilling slots by one chunk each: the OLDEST
        reservation only while live rows are decoding (bounded added
        latency per decode step, strict admission FIFO — dict
        insertion order), or every reservation when the pool is
        otherwise idle (``all_slots`` — serializing a cold-start burst
        behind one-slot-at-a-time would beat monolithic prefill at
        nothing). Returns False when fail-fast stopped the engine."""
        c = self.prefill_chunk
        advanced = False
        for b in list(self._prefilling):
            state = self._prefilling[b]
            req = state[0]
            if req.cancelled:
                del self._prefilling[b]
                if not req.done.is_set():
                    req.error = "cancelled"
                    self._finish_trace(req)
                    req.done.set()
                continue
            if advanced and not all_slots:
                break
            req, pending, i, row_t, row_d, pos0, tok0 = state
            chunk = pending[i:i + c]
            if len(chunk) < c:  # padded tail: junk writes land at
                chunk = np.concatenate(  # positions decode rewrites 1st
                    [chunk, np.zeros(c - len(chunk), np.int32)])
            tokens = jnp.asarray(chunk[None, :], jnp.int32)
            p0 = jnp.asarray([i], jnp.int32)
            try:
                state[3] = row_t = self._chunk_write(
                    self.params, row_t, tokens, p0)
                if row_d is not None:
                    state[4] = row_d = self._draft_chunk_write(
                        self._draft_params, row_d, tokens, p0)
            except Exception as exc:  # noqa: BLE001 — request-scoped
                del self._prefilling[b]
                obs_metrics.serving_admissions_total(self._obs).inc(
                    outcome="failed")
                req.error = f"{type(exc).__name__}: {exc}"
                self._finish_trace(req)
                req.done.set()
                if not self._count_request_failure(exc):
                    return False
                continue
            advanced = True
            state[2] = i + c
            if req.trace is not None:
                req.trace.event("chunk", pos=i,
                                of=int(len(pending)))
            if state[2] >= len(pending):
                # Caught up: insert the finished row(s) and go live.
                del self._prefilling[b]
                self._cache = self._insert(self._cache, row_t,
                                           jnp.int32(b))
                if row_d is not None:
                    self._draft_cache = self._draft_insert(
                        self._draft_cache, row_d, jnp.int32(b))
                self._go_live(b, req, pos0, tok0)
        return True

    def _new_cache(self) -> dict:
        """A zeroed KV cache (the paged pool, or the dense slot cache).
        Under a mesh its kv-head dim shards over `tp` by the kernels'
        own divisibility rule, and is born sharded — never whole on the
        first device."""
        family, cfg = self._family_mod, self.cfg
        if self._pool is not None:
            rows = self.slots + self.prefill_slots

            spaces = (self._pool.n_pages,)
            if self._window_tables is not None:
                spaces += (self._pool.window_n_pages,)

            def build():
                cache = family.paged_init_cache(
                    cfg, spaces[0], self._pool.page_size, *spaces[1:])
                if hasattr(family, "paged_init_rows"):
                    # What a sequence carries whatever its length, by
                    # the engine's row (lane rows behind the slots).
                    cache["rows"] = family.paged_init_rows(cfg, rows)
                return cache
        else:
            def build():
                return family.cb_init_cache(cfg, self.slots, self.max_len)
        if self._mesh is None:
            return build()
        from jax.sharding import NamedSharding, PartitionSpec

        from polyaxon_tpu.parallel import compat

        with self._mesh:
            _, head_axis = compat.kernel_axes(1, cfg.n_kv_heads)
        # kv heads are dim 2 of the paged pool [L, P, KV, page, Hd] and
        # dim 3 of the dense cache [L, B, C, KV, Hd].
        kv_dim = 2 if self._pool is not None else 3

        def spec(path, aval):
            dims = [None] * aval.ndim
            # K and V; any other leaf (per-row ones too) is replicated.
            if aval.ndim == 5 and path[0].key != "rows":
                dims[kv_dim] = head_axis
            return NamedSharding(self._mesh, PartitionSpec(*dims))

        shapes = jax.eval_shape(build)

        return jax.jit(build, out_shardings=jax.tree_util.tree_map_with_path(
            spec, shapes))()

    def _handle_step_failure(self, exc: Exception, what: str) -> bool:
        """Shared device-failure recovery for the plain step AND the
        speculative round: fail every live request with the error,
        count toward the fail-fast budget, and rebuild the donated
        cache(s) so a transient failure doesn't kill the engine.
        Returns False when fail-fast stopped the engine. Must be
        called from an ``except`` block (logger.exception)."""
        logger.exception("%s failed", what)
        self._step_failures += 1
        self._consec_step_failures += 1
        err = f"{type(exc).__name__}: {exc}"
        # Every program queued behind the failed one took its cache: one
        # failure, and none of their tokens is read.
        self._fail_live(err)
        # Lane reservations die with the cache: their staged pages
        # were in the donated buffer, so the KV they hold is gone —
        # failing them is the only honest option (pages freed, fresh
        # leaves detached).
        for p in list(self._lane):
            self._drop_lane_reservation(p, err)
        if self._consec_step_failures >= self.max_step_failures:
            self._fail_fast(err)
            return False
        # The old cache was donated to the failed program — its buffer
        # is gone (or poisoned). Rebuild. (Every live row was retired
        # above, so a paged pool is fully free.)
        self._cache = self._new_cache()
        if self._pool is not None:
            # The rebuilt cache is zeros: resident prefix pages no
            # longer hold the content their keys promise.
            self._pool.invalidate_prefix_cache()
        if self.draft is not None:
            self._draft_cache = self._draft_family.cb_init_cache(
                self._draft_cfg, self.slots, self.max_len)
        return True

    def _fail_live(self, err: str) -> None:
        """Every request in a slot ends with the error, and the steps
        launched and unread are forgotten: the requests that left their
        slot at a launch and wait for their last token there end with
        it too."""
        for b in range(self.slots):
            if self._slot_req[b] is not None:
                self._slot_req[b].error = err
                self._retire(b)
        while self._unread:
            for _, req, _ in self._unread.popleft().rows:
                if not req.done.is_set():
                    req.error = err
                    self._complete(req)
        self._tok_dev = None

    def _spec_iteration(self, k: Optional[int] = None) -> bool:
        """One draft→verify round for the pool: every live slot emits
        1..k+1 tokens (ragged acceptance, per-row budget caps). ``k``
        is the POLICY's draft length for this round (default: the
        configured spec_k); each distinct k compiles once. Returns
        False when a persistent failure stopped the engine. Mirrors the
        plain step's failure semantics, rebuilding BOTH caches on a
        transient device error (they were donated to the failed round).
        """
        k = self.spec_k if k is None else k
        # The round takes its tokens and budgets from the host: a plain
        # step the policy fell through to is read back first.
        if not self._drain():
            return False
        budget = np.zeros(self.slots, np.int32)
        for b in range(self.slots):
            req = self._slot_req[b]
            if req is not None:
                budget[b] = req.max_new - len(req.out)
        try:
            t, emit, cur_nxt, self._cache, self._draft_cache = (
                self._spec_round_for(k)(
                    self.params, self._draft_params,
                    self._cache, self._draft_cache,
                    jnp.asarray(self._cur), jnp.asarray(self._pos),
                    jnp.asarray(budget)))
            t = np.asarray(t)
            emit = np.asarray(emit)
            cur_nxt = np.asarray(cur_nxt)
        except Exception as exc:  # noqa: BLE001 — fail live requests
            return self._handle_step_failure(exc, "speculative round")
        self._consec_step_failures = 0
        self._spec_rounds += 1
        read_ns = time.perf_counter_ns()
        for b in range(self.slots):
            req = self._slot_req[b]
            if req is None:
                continue
            n = int(emit[b])
            self._spec_tokens += n
            # Acceptance accounting for the policy observable: of the
            # k proposals this row verified, emit-1 were the draft's
            # (the last emitted token is always the target's own).
            self._spec_proposed += k
            self._spec_accepted += max(n - 1, 0)
            fresh = [int(tok) for tok in t[b, :n]]
            hit = next((j for j, tok in enumerate(fresh)
                        if tok in req.eos), None)
            if hit is not None:
                # Stop at the eos (inclusive): the accepted tokens past
                # it are the target's real greedy continuation, but the
                # request asked to stop — drop them. Cache/pos state
                # past the retire point is irrelevant (the row is
                # replaced wholesale at the next admission).
                fresh = fresh[:hit + 1]
            req.read_ns = read_ns
            req.out.extend(fresh)
            self._unannounced.append(req)
            if fresh:
                if req.first_token_at is None:
                    self._observe_first_token(req)
                if req.trace is not None:
                    req.trace.event("spec_round", accepted=n,
                                    emitted=len(fresh))
            self._pos[b] += n
            self._cur[b] = int(cur_nxt[b])
            self._counts[b] = len(req.out)
            if len(req.out) >= req.max_new or hit is not None:
                self._retire(b)
        self._tok_dev = None  # the round's `_cur` is the whole truth
        return True

    def _observe_first_token(self, req: _Request) -> None:
        """Stamp first-token emission: TTFT (submit → first token, so
        queue wait and prefill both count — that is the number a client
        feels) plus the timeline annotation."""
        req.first_token_at = time.time()
        obs_metrics.serving_ttft_hist(self._obs).observe(
            req.first_token_at - req.submitted_at,
            **{"class": req.klass})
        if req.trace is not None:
            req.trace.event("first_token")

    def _finish_trace(self, req: _Request) -> None:
        """Close a request's span tree (idempotent — retire and the
        failure paths may both reach it)."""
        if req.trace is None:
            return
        if req.error:
            req.trace.finish(status="error", error=req.error,
                             tokens_out=len(req.out),
                             prefix_cached_tokens=req.prefix_cached_tokens)
        else:
            req.trace.finish(tokens_out=len(req.out),
                             prefix_cached_tokens=req.prefix_cached_tokens)

    def _retire(self, b: int) -> None:
        """Free slot ``b`` and end its request, whose tokens the host
        holds whole (nothing of it is in a step still unread)."""
        req = self._slot_req[b]
        self._free_slot(b)
        if req is not None:
            self._complete(req)

    def _free_slot(self, b: int) -> None:
        """Slot ``b`` and its pages go back. A step in flight may still
        read and write those pages: whatever takes them next is launched
        after it, and the device runs programs in launch order."""
        self._slot_req[b] = None
        self._pos[b] = -1
        self._counts[b] = 0
        self._fresh_rows.discard(b)
        if self._pool is not None:
            self._pool.release(b)
        self._set_sampling(b)

    def _complete(self, req: _Request) -> None:
        """The request has its last token (or its error)."""
        if req.cancelled and not req.error:
            req.error = "cancelled"
        if not req.error:  # count only successfully-served requests
            self._served += 1
            self._tokens_out += len(req.out)
        now = time.time()
        obs_metrics.serving_request_hist(self._obs).observe(
            now - req.submitted_at)
        if (not req.error and req.first_token_at is not None
                and len(req.out) >= 2):
            # TPOT = steady-state decode cadence: the first token
            # (prefill-dominated, already TTFT's job) is excluded.
            obs_metrics.serving_tpot_hist(self._obs).observe(
                (now - req.first_token_at) / (len(req.out) - 1),
                **{"class": req.klass})
        self._publish_queue_depth()
        self._finish_trace(req)
        req.done.set()
        self._unannounced.append(req)

    def _announce(self) -> None:
        """Wake the streaming handlers of the requests that got tokens
        (or ended) since the last call. A handler takes the interpreter
        lock to write its token out, and with a hundred rows live a
        hundred of them do; woken at once where the tokens are
        appended, they would hold the engine thread off the lock just
        when it uploads and launches the next step, with the device
        idle. So the tokens read since the last launch are announced
        after the *next* one (`_plain_step`), and the handlers write
        while the engine thread waits for the device; an engine with
        nothing live announces at the end of its tick (`_run_loop`)."""
        with self._phase("step.announce"):
            for req in self._unannounced:
                req.fresh.set()
            self._unannounced.clear()

    def merge_deliver_lags(self, counts: list) -> None:
        """A streaming handler's own histogram of `perf_counter_ns() -
        req.read_ns` after its writes (`log_bucket`'s buckets), added
        to the engine's: every 32 tokens or so and at a stream's end,
        never a token."""
        with self._deliver_lock:
            for k, n in enumerate(counts):
                if n:
                    self._deliver_lags[k] += n

    def _deliver_lags_copy(self) -> list:
        with self._deliver_lock:
            return list(self._deliver_lags)

    # ------------------------------------------------------- preemption
    def _maybe_preempt(self) -> None:
        """Make room for a blocked urgent prefill by evicting one live
        lower-priority slot (ISSUE 19). Runs at the top of every tick,
        at most one eviction per tick (each eviction frees a slot AND
        pages, so re-checking next tick is cheap and avoids cascades).

        Trigger: pending ``preempts``-class demand (interactive)
        exceeds what free capacity can absorb — more urgent requests
        queued than free slot/lane entries, or the pool can't admit
        the oldest one's prompt. Demand-vs-capacity, not
        zero-capacity: under a storm, retirements free one slot per
        tick and a zero-capacity trigger would stall eviction there,
        capping the interactive lane at half width while best-effort
        camps the rest. Victim: a live decode slot
        of a ``preemptible`` class with strictly lower priority,
        preferring the one holding the most KV pages ("most
        over-budget"), fewest emitted tokens as tiebreak. Eviction
        releases the slot's pages through the normal retire path — the
        committed radix prefix stays resident, so the victim's
        re-admission is a suffix-only prefill (pages, not recompute)."""
        if (self._pool is None or not self.class_admission
                or not self.preemption):
            return
        with self._cv:
            cand = None
            demand = 0
            for name, q in self._queues.items():
                if not q or not resolve_request_class(name).preempts:
                    continue
                demand += len(q)
                if cand is None or q[0].seq < cand[0].seq:
                    cand = (q[0], resolve_request_class(name))
        if cand is None:
            return
        req, rc = cand
        if self.prefill_slots:
            free = sum(
                1 for p in range(self.slots,
                                 self.slots + self.prefill_slots)
                if p not in self._lane)
        else:
            free = sum(
                1 for b in range(self.slots)
                if self._slot_req[b] is None
                and b not in self._prefilling)
        fits = self._pool.can_admit(len(req.tokens), req.tokens)
        if free >= demand and fits:
            return  # capacity absorbs every urgent pending request
        # A victim is requeued with what it has: the step in flight is
        # read first (it may end the row that would have been chosen).
        self._drain()
        victim = self._pick_victim(rc.priority)
        if victim is None:
            return  # nothing evictable (never touch peers/superiors)
        self._evict_slot(victim,
                         reason="kv_pages" if free else "slots")

    def _pick_victim(self, min_priority: int) -> Optional[int]:
        """Best decode slot to evict for a blocked class of
        ``min_priority``: preemptible, strictly lower priority, most
        pages held first. Lane reservations are never victims — their
        fresh leaves are uncommitted, so releasing them would need a
        prefix invalidate and cost full recompute."""
        best = None  # ((priority asc, pages desc, emitted asc), slot)
        for b in range(self.slots):
            victim = self._slot_req[b]
            if victim is None or b in self._prefilling:
                continue
            vrc = resolve_request_class(victim.klass)
            if not vrc.preemptible or vrc.priority >= min_priority:
                continue
            key = (-vrc.priority, self._pool.slot_pages(b),
                   -len(victim.out))
            if best is None or key > best[0]:
                best = (key, b)
        return best[1] if best is not None else None

    def _evict_slot(self, b: int, reason: str) -> None:
        """Preemptively evict slot ``b`` and requeue its request at the
        head of its class queue. Pages release through the same
        fresh-leaf path _retire uses — the committed prompt prefix
        stays resident in the radix tree (reclaimable, and a free
        suffix-only re-admission), while decode-extension pages return
        to the free list. Emitted tokens are discarded and regenerated
        deterministically on resume (greedy argmax / seed folded by
        position), so streaming clients see a consistent prefix; TTFT
        re-observes at the retry's first token — degraded service is
        measured, not hidden."""
        req = self._slot_req[b]
        rc = resolve_request_class(req.klass)
        held = self._pool.slot_pages(b)
        discarded = len(req.out)
        self._free_slot(b)
        req.preemptions += 1
        req.out.clear()
        req.first_token_at = None
        self._preemptions[rc.name] = self._preemptions.get(rc.name, 0) + 1
        obs_metrics.serving_preemptions_total(self._obs).inc(
            **{"class": rc.name, "reason": reason})
        if req.trace is not None:
            req.trace.event("preempted", reason=reason, slot=b,
                            pages_held=held, tokens_discarded=discarded)
            req.trace.start_phase("queue_wait", requeued=True)
        with self._cv:
            self._queue_for(req).appendleft(req)
            self._publish_queue_depth()

    def _loop(self) -> None:
        if self._mesh is None:
            return self._run_loop()
        with self._mesh:
            return self._run_loop()

    def _run_loop(self) -> None:
        while True:
            with self._cv:
                while (not self._stopped and not self._queue_depth()
                       and not self._prefilling and not self._lane
                       and all(r is None for r in self._slot_req)
                       and not self._expert_tokens_wanted.is_set()):
                    with self._clock.dry():
                        self._cv.wait()
                stopped = self._stopped
            if stopped:
                # The requests that wait only for the token of the step
                # in flight get it.
                self._drain()
                return
            self._serve_expert_tokens()
            # Idle waiting above is excluded from the tick duration
            # (it is `engine:dry`): the histograms measure work per
            # iteration (admission + prefill chunk + decode step), not
            # queue quiet time.
            with self._clock.tick():
                t0 = time.time()
                alive = self._tick()
                if alive:
                    with self._phase("observe"):
                        self._observe_tick(time.time() - t0)
                else:
                    # A `stop()` seen inside the tick: as above (after
                    # a fail-fast nothing is left unread).
                    self._drain()
                if not alive or self.draft is not None or all(
                        r is None for r in self._slot_req):
                    # No plain step is known to follow at once: the
                    # tick's tokens and endings are announced here.
                    self._announce()
            if not alive:
                return

    def _serve_expert_tokens(self) -> None:
        """Between ticks, on the engine thread (the only one that may
        touch the cache: every step donates it): copy the family's
        routed-pairs counter to the host when `stats()` has asked."""
        if self._expert_tokens_wanted.is_set():
            self._expert_tokens_wanted.clear()
            got = {name: np.asarray(self._cache[name]).tolist()
                   for name in self._expert_counters}
            if "mla_decode_positions" in got:
                # Kept on the device as (multiples of 2^30, remainder).
                high, low = got["mla_decode_positions"]
                got["mla_decode_positions"] = (high << 30) + low
            self._expert_tokens = got
            self._expert_tokens_ready.set()

    def _read_expert_tokens(self) -> dict:
        """The counters as of the next tick boundary (the last copy
        where the engine thread does not answer: stopped, or
        mid-compile; None before any)."""
        with self._expert_tokens_asking:
            self._expert_tokens_ready.clear()
            self._expert_tokens_wanted.set()
            with self._cv:
                self._cv.notify_all()
            self._expert_tokens_ready.wait(timeout=2.0)
            got = self._expert_tokens
        return got or dict.fromkeys(self._expert_counters)

    def _tick_snapshot(self) -> dict:
        """The engine's state beside a slow tick's phase split."""
        return {
            "live": sum(1 for r in self._slot_req if r is not None),
            "prefilling": len(self._prefilling) + len(self._lane),
            "queued": self._queue_depth(),
            "decode_steps": self._steps_total,
            "kv_pages_free": (self._pool.free_pages
                              if self._pool is not None else None)}

    def _observe_tick(self, dt: float) -> None:
        """Engine-tick telemetry: iteration duration plus the batch
        composition and KV-page gauges a dashboard needs to say WHY
        throughput looks the way it does (decode-bound vs
        prefill-bound vs page-starved)."""
        obs_metrics.serving_tick_hist(self._obs).observe(dt)
        decode = sum(1 for r in self._slot_req if r is not None)
        prefill = len(self._prefilling) + len(self._lane)
        slots = obs_metrics.serving_batch_slots(self._obs)
        slots.set(decode, state="decode")
        slots.set(prefill, state="prefill")
        # Lane rows are capacity ON TOP of the decode slots, so free
        # counts only unreserved decode-pool slots.
        slots.set(max(self.slots - decode - len(self._prefilling), 0),
                  state="free")
        if self._pool is not None:
            util = self._pool.utilization()
            pages = obs_metrics.serving_kv_pages(self._obs)
            pages.set(util["used"], state="used")
            pages.set(util["free"], state="free")
            radix = self._pool.radix_stats()
            obs_metrics.serving_radix_nodes(self._obs).set(radix["nodes"])
            rpages = obs_metrics.serving_radix_pages(self._obs)
            rpages.set(radix["referenced"], state="referenced")
            rpages.set(radix["resident"], state="resident")

    def _tick(self) -> bool:
        """One engine iteration. Classic: drop cancellations, admit,
        advance chunked prefills, one decode step or speculative
        round. Disaggregated (``prefill_slots``): handoff finished
        lane rows, admit into lane rows, run the budgeted lane chunk
        programs, handoff again (a prefill that finished this tick
        goes live this tick), then give the decode lane its budgeted
        steps. Returns False when fail-fast stopped the engine (the
        loop exits); True otherwise — including idle iterations."""
        with self._phase("sweep"):
            if any(r is not None and r.cancelled for r in self._slot_req):
                # Drop cancelled live requests, with the tokens of the
                # step in flight (a failed readback retires them all).
                self._drain()
                for b in range(self.slots):
                    req = self._slot_req[b]
                    if req is not None and req.cancelled:
                        self._retire(b)
            self._maybe_preempt()
        if self._stopped:  # a drain may fail-fast
            return False
        with self._phase("admit", book="admit.other"):
            if self.prefill_slots:
                self._lane_handoff()  # free lane rows before admission
                self._admit_lane()
            else:
                self._admit()
        if self._stopped:  # admission may fail-fast mid-pass
            return False
        self._queue_depth_peak = max(self._queue_depth_peak,
                                     self._queue_depth())
        live = sum(1 for r in self._slot_req if r is not None)
        if self._lane:
            with self._phase("prefill_chunk"):
                alive = self._lane_tick(live)
            if not alive:
                return False  # fail-fast stopped the engine
            with self._phase("admit", book="admit.other"):
                self._lane_handoff()
            live = sum(1 for r in self._slot_req if r is not None)
        elif self._prefilling:
            # Idle pool → advance every reservation (a cold-start
            # burst must not serialize one slot at a time).
            with self._phase("prefill_chunk"):
                alive = self._advance_prefill(all_slots=(live == 0))
            if not alive:
                return False  # fail-fast stopped the engine
            live = sum(1 for r in self._slot_req if r is not None)
        if live == 0:
            self._last_decode_at = None
            # Nothing to launch behind it: the step in flight is read.
            return self._drain()
        if self.prefill_slots and self.decode_lane_budget < 1:
            # Red-team knob (bench --inject lane-starve): a zeroed
            # decode budget means staged work goes live and then sits
            # emitting nothing — the lane gate must catch this, so the
            # engine honors it rather than quietly clamping to 1.
            self._last_decode_at = None
            if not self._drain():
                return False
            time.sleep(0.005)  # don't spin hot while starved
            return True
        obs_metrics.serving_lane_ticks_total(self._obs).inc(lane="decode")
        steps = self.decode_lane_budget if self.prefill_slots else 1
        for _ in range(max(steps, 1)):
            live = sum(1 for r in self._slot_req if r is not None)
            if live == 0:
                break
            self._steps_total += 1
            self._live_slot_steps += live
            if self.draft is not None:
                k = max(0, min(
                    self._spec_policy.draft_len(self._lane_view()),
                    self.spec_k))
                obs_metrics.serving_spec_draft_len(self._obs).set(k)
                if k > 0:
                    with self._phase("spec"):
                        alive = self._spec_iteration(k)
                    if not alive:
                        return False
                    self._note_decode_step()
                    continue
                # Policy says no headroom: fall through to a plain
                # step (lossless either way — the draft cache just
                # accrues holes that degrade later acceptance).
            if not self._plain_step():
                return False
            self._note_decode_step()
        if all(r is None for r in self._slot_req):
            # The last rows left their slots at the launch: no step
            # follows for theirs to be read behind.
            return self._drain()
        return True

    def _note_decode_step(self) -> None:
        """Decode-lane cadence: the wall gap between CONSECUTIVE
        decode-lane steps, including whatever prefill work the
        scheduler let land in between — THE interference observable
        the decode-tpot-interference rule and the storm-window oracle
        invariant judge. Idle gaps never count (_last_decode_at resets
        whenever the lane goes quiet)."""
        now = time.monotonic()
        if self._last_decode_at is not None:
            obs_metrics.serving_decode_tpot_hist(self._obs).observe(
                now - self._last_decode_at)
        self._last_decode_at = now

    def _plain_step(self) -> bool:
        """One ragged decode step for the decode pool, launched before
        the step before it is read back: the tokens go from step to
        step on the device, and what the host does with them (the
        readback, `_emit_step`, and the next tick's sweep, admission
        and uploads) runs while the device has a program queued.
        Returns False when fail-fast stopped the engine."""
        if self._unread:
            # Counted where `_tick` counts the step, so that a reader
            # of both finds neither a step ahead of the other.
            self._steps_ahead += 1
        try:
            rows = self._launch()
            # The step before this one's tokens: the device is busy now.
            self._announce()
            before = self._read_oldest() if len(self._unread) > 1 else None
        except Exception as exc:  # noqa: BLE001 — fail live requests
            return self._handle_step_failure(exc, "decode step")
        with self._phase("step.emit"):
            if before is not None:
                self._emit_step(*before)
            return self._advance(rows)

    def _launch(self) -> list:
        """Upload what the host knows without the tokens in flight and
        dispatch the step; returns the launch's rows."""
        with self._phase("step.keys"):
            # What the program folds into each slot's base key: the
            # tokens launched for its request so far (0 for a free slot).
            counts = self._counts.copy()
            rows = [(b, req, counts[b] + 1 >= req.max_new)
                    for b, req in enumerate(self._slot_req)
                    if req is not None]
        filtered = any(req.top_p < 1.0 or req.top_k > 0
                       for _, req, _ in rows)
        step_fn = self._step_filtered if filtered else self._step_plain
        with self._phase("step.upload"):
            # Copies, made on the host: the host writes these vectors
            # while the program that reads them runs (and the CPU
            # backend aliases what it is handed).
            # Decode sees ONLY the decode-pool rows: lane rows sit past
            # self.slots and belong to staged prefills.
            tables = (jnp.asarray(self._pool.tables[:self.slots].copy())
                      if self._pool is not None else None)
            pos = jnp.asarray(self._pos.copy())
            if tables is not None:
                live = self._pos[self._pos >= 0]
                self._paged_pages_live += int(
                    (live // self._pool.page_size + 1).sum())
                self._paged_pages_table += tables.size
                if self._window_tables is not None:
                    tables = (tables, jnp.asarray(
                        self._window_tables[:self.slots].copy()))
            counts = jnp.asarray(counts)
            tokens, fresh = self._tok_dev, self._no_fresh
            if tokens is None:
                tokens = jnp.asarray(self._cur.copy())
            elif self._fresh_rows:
                went_live = list(self._fresh_rows)
                word = np.full(self.slots, -1, np.int32)
                word[went_live] = self._cur[went_live]
                fresh = jnp.asarray(word)
            self._fresh_rows.clear()
            if self._sampling_dev is None:
                # (`jnp.array` would run a device program for each.)
                self._sampling_dev = tuple(
                    jnp.asarray(a.copy())
                    for a in (self._seeds, self._temps, self._top_ps,
                              self._top_ks))
            seeds, temps, top_ps, top_ks = self._sampling_dev
        with self._phase("step.dispatch"):
            nxt, self._cache = step_fn(
                self.params, self._cache, tokens, pos, seeds, counts,
                temps, top_ps, top_ks, tables, fresh)
        self._tok_dev = nxt
        self._unread.append(_Launch(nxt, rows))
        return rows

    def _read_oldest(self) -> tuple:
        """(tokens, rows) of the oldest unread step. A device error of
        that step (or of one before it) surfaces here."""
        launch = self._unread[0]
        with self._phase("step.readback"):
            nxt = np.asarray(launch.nxt)
        self._unread.popleft()
        self._consec_step_failures = 0
        return nxt, launch.rows

    def _drain(self) -> bool:
        """Read back and hand out every step launched and unread:
        whatever needs the host's tokens whole calls this first.
        Returns False when fail-fast stopped the engine."""
        while self._unread:
            try:
                before = self._read_oldest()
            except Exception as exc:  # noqa: BLE001 — fail live requests
                return self._handle_step_failure(exc, "decode step")
            with self._phase("step.emit"):
                self._emit_step(*before)
        return True

    def _emit_step(self, nxt: np.ndarray, rows: list) -> None:
        """Hand each row of a launch its token, by the request it was
        launched for: append, end the request at its budget or at a
        stop token. The slot may be another request's by now."""
        read_ns = time.perf_counter_ns()
        for b, req, last in rows:
            if req.done.is_set():
                # Ended by a stop token in the step before, which was
                # read after this one was launched: computed, dropped.
                self._tokens_dropped += 1
                continue
            tok = int(nxt[b])
            req.read_ns = read_ns
            req.out.append(tok)
            self._unannounced.append(req)
            if req.first_token_at is None:
                self._observe_first_token(req)
            if last:
                self._complete(req)  # its slot went at the launch
            elif tok in req.eos:
                if self._slot_req[b] is req:
                    self._free_slot(b)
                self._complete(req)
            elif self._slot_req[b] is req:
                self._cur[b] = tok

    def _advance(self, rows: list) -> bool:
        """What the host knows of the step just launched without its
        tokens: each row moves one position on; a row whose budget this
        step reaches leaves its slot and pages at once (only its
        request's end waits for the token); any other takes the page of
        its next position. Returns False when fail-fast stopped the
        engine."""
        for b, req, last in rows:
            if self._slot_req[b] is not req:
                continue  # ended by a stop token in the step just read
            self._pos[b] += 1
            self._counts[b] += 1
            if last:
                self._free_slot(b)
            elif (self._pool is not None
                  and not self._pool.ensure(b, int(self._pos[b]))):
                # An oversubscribed pool ran dry mid-generation. Whether
                # the row ends at its token anyway is decided with the
                # token; if not, fail THIS row loudly (its output so
                # far is surfaced in the error path) rather than let it
                # scribble over a neighbour's pages.
                if not self._drain():
                    return False
                if self._slot_req[b] is not req:
                    continue
                obs_metrics.serving_evictions_total(self._obs).inc(
                    reason="pool_exhausted")
                if req.trace is not None:
                    req.trace.event("evicted", reason="pool_exhausted",
                                    pos=int(self._pos[b]))
                req.error = (
                    "kv page pool exhausted mid-generation "
                    f"(pos {int(self._pos[b])}); raise --kv-pages "
                    "or lower concurrency")
                self._retire(b)
        if self._window_tables is not None:
            with self._phase("step.window"):
                for b, req, _ in rows:
                    if self._slot_req[b] is req:
                        self._pool.roll(b, int(self._pos[b]))
        return True
