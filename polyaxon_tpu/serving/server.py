"""Built-in model-serving runtime for ``V1Service`` runs.

The reference's service kind just exposes a user container's port
(SURVEY.md §2 "Operator": Deployment+Service) — serving *content* is the
user's problem. Here the framework owns a TPU-native serving path too:
KV-cache generation (llama-family decoders: prefill + ring-buffer
decode; t5-family seq2seq: encode once + decoder cache from BOS) behind
a stdlib HTTP endpoint, so a Polyaxonfile service can run
``python -m polyaxon_tpu.serving --model llama3_8b --checkpoint <dir>``
with no user code. Decoders bound prompt+budget by max_seq_len;
seq2seq bounds encoder prompt and decode budget separately.

TPU-first details:
- prompt lengths and generation budgets are bucketed to powers of two so
  the jitted prefill/decode pair compiles a handful of shapes, not one
  per request;
- decode runs the whole budget under ``lax.scan`` (one compiled program
  per bucket), then the host truncates;
- weights load from an Orbax checkpoint (params tree) or fall back to
  random init for smoke serving.

API (JSON over HTTP):
    GET  /healthz              → {"status": "ok", "model": name}
    GET  /v1/models            → {"models": [name]}
    GET  /v1/fleet             → per-replica telemetry breakdown
                               (ServingFleet front ends only; 404
                               behind a single engine)
    GET  /requests/{id}        → one request's summary row (behind a
                               fleet: fan-out over every replica's
                               ring, stamped with the serving replica)
    POST /v1/generate          {"tokens": [[...]], "max_new_tokens": N,
                                "temperature": T?, "seed": S?,
                                "stream": bool?}
                               → {"tokens": [[...]] }, or with
                               stream=true an SSE stream of per-token
                               events {"index": row, "token": id}
                               followed by event:done {"tokens": ...}.
                               Under --batching continuous tokens
                               arrive as they decode; the static
                               engine emits one burst per batch.
"""

from __future__ import annotations

import functools
import json
import logging
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from polyaxon_tpu.models.common import hold_transposed, served_params
from polyaxon_tpu.serving.batching import (LOG_BUCKETS, QueueFull, log_bucket,
                                           validate_sampling)
from polyaxon_tpu.serving.quantize import (held_transposed_bytes,
                                           quantize_tree, tree_bytes,
                                           weight_bytes)

logger = logging.getLogger(__name__)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _family(model: str):
    """``models.family_of`` held to what can be served: a family module
    with ``generate`` beside CONFIGS/init, and a SEQ2SEQ flag (llama-style
    decoders, Mixtral-style MoE decoders, lfm2-style hybrid decoders:
    short convolutions beside attention, sigmoid-routed experts; and
    t5-style encoder-decoders)."""
    from polyaxon_tpu import models

    try:
        family = models.family_of(model)
    except ValueError:
        family = None
    if hasattr(family, "generate"):
        return family

    def servable(seq2seq: bool) -> list:
        return [name for mod in models.FAMILIES
                if hasattr(mod, "generate")
                and getattr(mod, "SEQ2SEQ", False) == seq2seq
                for name in sorted(mod.CONFIGS)]

    raise ValueError(
        f"model `{model}` is not servable; decoders: {servable(False)}, "
        f"seq2seq: {servable(True)}")


def load_params(model: str, checkpoint: Optional[str] = None, seed: int = 0,
                mesh=None, lora_alpha: float = 16.0,
                quantize: Optional[str] = None):
    """Model params: latest step of an Orbax checkpoint dir (a saved
    JAXJob train state or a bare params tree), else random init.

    A server holds its weights in the precision it computes in: every
    leaf that its family reads at ``cfg.dtype`` is cast to it here,
    once (``models/common.py served_params``; the family's
    ``READ_AT_FLOAT32`` names what stays float32), so no decode or
    prefill program repeats the cast. The values are drawn or restored
    in float32 first and then rounded: the very values the programs'
    own casts gave. The projections a family names in its
    ``HELD_TRANSPOSED`` (those its walks split into heads) are then held
    ``[.., N, D]`` under ``name_t``, which is how the chip's dot reads
    them: the same values, swapped after the cast (inside the jitted
    draw; on the host for a restored leaf, whose checkpoint is
    ``[D, N]`` and is validated so), and the walks read whichever they
    are handed (``models/common.py project``). A family that states
    nothing keeps float32 (t5). ``quantize`` ("int8"): the float32 tree
    goes to ``quantize_tree`` instead and nothing is cast or swapped,
    which is the tree it always gave.

    ``mesh``: shard the weights over it using the model's logical axes
    and the mesh's rule table (the same tables training uses) — serving
    an 8B-class model then runs tensor/fsdp-parallel across the mesh
    with GSPMD inserting the decode collectives. The full weight tree
    is never materialized unsharded on one device: random init is
    jitted with sharded out_shardings, and checkpoint tensors move
    host → their own device shards directly.
    """
    from polyaxon_tpu.runtime import compile_cache

    # Every serving entry loads params before it compiles anything (the
    # server, the fleet's replica factory, the serving bench scripts),
    # so this is where they all get the one compile-cache rule.
    compile_cache.enable()
    family = _family(model)
    cfg = family.CONFIGS[model]

    read_at_float32 = getattr(family, "READ_AT_FLOAT32", None)
    plain = bool(quantize) or read_at_float32 is None
    held = () if plain else getattr(family, "HELD_TRANSPOSED", ())

    shardings = None
    if mesh is not None:
        from polyaxon_tpu.parallel import rules_for_mesh
        from polyaxon_tpu.parallel.sharding import tree_shardings

        # A held-transposed leaf's logical axes are the plain leaf's
        # with the last two swapped: the same rule table shards it.
        shardings = tree_shardings(
            hold_transposed(family.logical_axes(cfg)["params"], held,
                            lambda axes: (*axes[:-2], axes[-1], axes[-2])),
            mesh, rules_for_mesh(mesh))

    def init_params(key, held=held):
        params = family.init(cfg, key)["params"]
        if plain:
            return params
        return served_params(params, cfg.dtype, read_at_float32, held)

    # Shape/dtype template of the tree as a checkpoint holds it (every
    # projection ``[D, N]``): no memory, used for structure validation
    # and dtype casts (the served dtypes: a restored leaf is rounded on
    # the host, after any LoRA merge in float32, and swapped there).
    template = jax.eval_shape(
        functools.partial(init_params, held=()), jax.random.key(0))

    if checkpoint:
        import orbax.checkpoint as ocp

        with ocp.CheckpointManager(checkpoint) as mgr:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {checkpoint}")
            # Restore with the on-disk topology (no abstract): the saved
            # tree is either a full JAXJob train state ({params,
            # opt_state, step, state} — runtime.checkpoint layout) or a
            # bare {params: ...}; slice out the params either way and
            # validate against the model before serving.
            restored = mgr.restore(step, args=ocp.args.StandardRestore())
            loaded = restored.get("params", restored)
            if isinstance(loaded, dict) and set(loaded) == {"base", "lora"}:
                # A LoRA fine-tune's train state: fold the adapters
                # into dense weights at load — zero serving overhead.
                # Alpha/rank come from the checkpoint's own _meta
                # (--lora-alpha is only a fallback for pre-meta saves);
                # the merge runs on the HOST so an 8B's stacked leaves
                # never materialize unsharded on one device.
                from polyaxon_tpu.models.lora import merge_saved

                loaded = merge_saved(loaded["base"], loaded["lora"],
                                     alpha=lora_alpha, host=True)
                logger.info("merged LoRA adapters into %s", model)
            if jax.tree.structure(template) != jax.tree.structure(loaded):
                raise ValueError(
                    f"checkpoint {checkpoint} step {step} does not match "
                    f"model `{model}`: params tree structure differs")
            # Leaf by leaf, so that no rounded copy of the whole tree
            # sits on the host beside the restored one: round, swap a
            # held leaf, place.
            def rounded(ref, x):
                return lambda: np.asarray(x, ref.dtype)

            def swapped(leaf):
                return lambda: np.swapaxes(leaf(), -1, -2)

            staged = hold_transposed(
                jax.tree.map(rounded, template, loaded), held, swapped)
            if shardings is not None:
                params = jax.tree.map(
                    lambda leaf, sh: jax.device_put(leaf(), sh),
                    staged, shardings)
            else:
                params = jax.tree.map(lambda leaf: jnp.asarray(leaf()),
                                      staged)
            logger.info("restored %s step=%s", checkpoint, step)
    else:
        # One program, sharded or not: op-by-op init compiles a program
        # per tensor shape, which is minutes of start-up at real widths.
        # The cast is part of it, so the float32 tree never sits whole
        # in device memory beside its copy.
        init_fn = jax.jit(init_params, out_shardings=shardings)
        params = init_fn(jax.random.key(seed))

    if quantize:
        full = tree_bytes(params)
        params = quantize_tree(params, mode=quantize)
        logger.info("quantized %s weights %s: %.1f MiB -> %.1f MiB",
                    model, quantize, full / 2**20,
                    tree_bytes(params) / 2**20)

    if mesh is not None:
        logger.info("sharded %s over mesh %s", model,
                    dict(zip(mesh.axis_names, mesh.devices.shape)))
    return cfg, params


class _Engine:
    """Bucketed, jitted generation around the family's generate().

    ``draft``: optional ``(draft_model, draft_cfg, draft_params, k)``
    enables speculative decoding for GREEDY requests — lossless (the
    output is the target's own greedy sequence), the draft just buys
    back sequential decode steps. Sampled requests and requests without
    cache headroom for the k+1 verify window fall back to the plain
    path silently.
    """

    def __init__(self, model: str, cfg, params, draft=None):
        self.model = model
        self.cfg = cfg
        self.params = params
        self.draft = draft
        self._weight_bytes = weight_bytes(params)
        self._held_transposed_bytes = held_transposed_bytes(params)
        self._served = 0
        self._tokens_out = 0
        self._lock = threading.Lock()  # one TPU program at a time
        family = _family(model)
        # seq2seq families decode into their own cache; the prompt is
        # the encoder input, so prompt and budget are bounded separately.
        self.seq2seq = bool(getattr(family, "SEQ2SEQ", False))
        if draft is not None:
            if not hasattr(family, "decode_chunk"):
                raise ValueError(
                    f"speculative decoding needs the target family to "
                    f"expose decode_chunk; `{model}` does not — serve "
                    "without --draft-model")
            if getattr(cfg, "sliding_window", None) is not None:
                raise ValueError(
                    "speculative decoding requires a full-length cache "
                    "(no sliding_window)")
            draft_family = _family(draft[0])
            missing = [name for name in ("prefill", "decode_step_ragged")
                       if not hasattr(draft_family, name)]
            if missing:
                raise ValueError(
                    f"draft `{draft[0]}` cannot speculate: its family "
                    f"lacks {missing}")

        @functools.lru_cache(maxsize=16)
        def compiled(prompt_len: int, max_new: int, sampling: bool,
                     filtered: bool, spec: bool = False):
            # Temperature/top_p/top_k are traced scalars, NOT part of
            # the compile key — only the greedy/sampling/filtered mode
            # switches programs, so a client sweeping knobs reuses one
            # executable. `filtered` keeps plain-sampling requests on
            # the historical categorical draw (bit-stable seeds); only
            # requests that actually set top_p/top_k pay the sorted
            # nucleus path.
            if spec:
                from polyaxon_tpu.serving.speculative import (
                    generate_speculative,
                )

                draft_name, draft_cfg, _, spec_k = self.draft

                # Draft params are a traced ARGUMENT (passed at the
                # call site), not a closure capture: captured weights
                # would be baked as constants into every compiled
                # (plen, budget) executable — constant-folding the
                # int8 dequant back to full precision and duplicating
                # the draft per program.
                def run_spec(params, draft_params, prompt):
                    # Quantized trees pass through WHOLE: the model
                    # unwraps each weight at its consumption site
                    # (models/llama.py _w), inside the decode scan —
                    # a tree-level dequant here would be hoisted out
                    # of the loop, materializing a bf16 copy that
                    # every step re-reads.
                    return generate_speculative(
                        self.cfg, params,
                        draft_cfg, draft_params,
                        prompt, max_new_tokens=max_new, k=spec_k,
                        family=family,
                        draft_family=_family(draft_name))

                return jax.jit(run_spec)

            def run(params, prompt, rng, temperature, top_p, top_k):
                # Quantized trees pass through whole; weights unwrap at
                # their consumption sites INSIDE the decode scan
                # (models/llama.py _w) so int8 stays the HBM-resident
                # format per step. A dequantize_tree here is loop-
                # invariant — XLA hoists it, and decode then re-reads a
                # materialized bf16 copy every step (the round-3 0.88x
                # int8 anomaly).
                # llama: prompt continues; t5: prompt is the encoder
                # input and generation starts from BOS.
                return family.generate(
                    self.cfg, params, prompt, max_new_tokens=max_new,
                    temperature=temperature if sampling else 0.0,
                    top_p=top_p if filtered else 1.0,
                    top_k=top_k if filtered else 0,
                    rng=rng)

            return jax.jit(run)

        self._compiled = compiled

    def _spec_usable(self, plen: int, n_bucket: int) -> bool:
        if self.draft is None:
            return False
        _, draft_cfg, _, spec_k = self.draft
        need = plen + n_bucket + spec_k + 1
        return (need <= self.cfg.max_seq_len
                and need <= draft_cfg.max_seq_len)

    def _validate(self, tokens: list[int], max_new_tokens: int) -> None:
        """Request-level checks, shared with the streaming handler so a
        bad request is rejected before any work (or any SSE header)."""
        if len(tokens) < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        plen, n_bucket = len(tokens), _bucket(max_new_tokens, lo=16)
        if self.seq2seq:
            if max(plen, n_bucket) > self.cfg.max_seq_len:
                raise ValueError(
                    f"prompt {plen} or generation budget {n_bucket} "
                    f"exceeds max_seq_len {self.cfg.max_seq_len}")
        elif plen + n_bucket > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt {plen} + generation budget {n_bucket} exceeds "
                f"max_seq_len {self.cfg.max_seq_len}")

    def generate(self, token_rows: list[list[int]], max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 top_p: float = 1.0, top_k: int = 0,
                 eos_tokens=None) -> list[list[int]]:
        if not token_rows:
            return []
        eos = frozenset(int(t) for t in (eos_tokens or ()))
        # Validate every row before running any (no TPU work is spent
        # on a batch that will be rejected).
        for row in token_rows:
            self._validate(row, max_new_tokens)
        validate_sampling(top_p, top_k)
        sampling = temperature > 0
        filtered = sampling and (top_p < 1.0 or top_k > 0)
        n_bucket = _bucket(max_new_tokens, lo=16)
        # Rows are grouped by EXACT prompt length — padding a causal
        # prompt (either side) changes what the real tokens attend to,
        # so correctness wins over a shared bucket; the generation
        # budget is still bucketed, so the compile count is
        # O(distinct prompt lengths × budgets), LRU-bounded.
        groups: dict[int, list[int]] = {}
        for i, row in enumerate(token_rows):
            groups.setdefault(len(row), []).append(i)
        results: list[Optional[list[int]]] = [None] * len(token_rows)
        for plen, idxs in groups.items():
            batch = np.asarray([token_rows[i] for i in idxs], np.int32)
            spec = not sampling and self._spec_usable(plen, n_bucket)
            fn = self._compiled(plen, n_bucket, sampling, filtered, spec)
            with self._lock:
                if spec:
                    out = np.asarray(fn(self.params, self.draft[2],
                                        jnp.asarray(batch)))
                else:
                    out = np.asarray(fn(self.params, jnp.asarray(batch),
                                        jax.random.key(seed),
                                        jnp.float32(temperature),
                                        jnp.float32(top_p),
                                        jnp.int32(top_k)))
            for j, i in enumerate(idxs):
                row_out = out[j, :max_new_tokens].tolist()
                if eos:
                    # Whole-budget program, host truncation: stop at
                    # the first eos (inclusive — same convention as the
                    # continuous engine's early retire).
                    hit = next((jj for jj, tok in enumerate(row_out)
                                if tok in eos), None)
                    if hit is not None:
                        row_out = row_out[:hit + 1]
                results[i] = row_out
        with self._lock:  # ThreadingHTTPServer: += on ints is not atomic
            self._served += len(token_rows)
            self._tokens_out += sum(
                len(r) for r in results if r is not None)
        return results  # type: ignore[return-value]

    def stats(self) -> dict:
        """Live engine counters for /v1/stats."""
        return {
            "engine": "static",
            "requests_served": self._served,
            "tokens_generated": self._tokens_out,
            "weight_bytes": dict(self._weight_bytes),
            "weights_held_transposed_bytes": self._held_transposed_bytes,
        }


# Operator stats page at GET / — live tiles over /v1/stats (same
# design tokens as the runs dashboard, api/ui.py: status never color
# alone, ink/muted text roles, light+dark).
STATS_PAGE = r"""<!doctype html>
<html>
<head>
<meta charset="utf-8">
<meta name="viewport" content="width=device-width, initial-scale=1">
<title>polyaxon_tpu — serving</title>
<style>
  :root {
    color-scheme: light dark;
    --page: #f9f9f7; --surface: #fcfcfb;
    --ink: #0b0b0b; --ink-2: #52514e; --muted: #898781;
    --ring: rgba(11,11,11,0.10); --good: #0ca30c; --bad: #d03b3b;
  }
  @media (prefers-color-scheme: dark) {
    :root { --page: #0d0d0d; --surface: #1a1a19; --ink: #fff;
            --ink-2: #c3c2b7; --ring: rgba(255,255,255,0.10); }
  }
  body { margin: 0; background: var(--page); color: var(--ink);
         font: 14px/1.45 system-ui, sans-serif; }
  header { padding: 14px 20px; border-bottom: 1px solid var(--ring);
           display: flex; gap: 10px; align-items: baseline; }
  h1 { font-size: 16px; margin: 0; font-weight: 650; }
  #state { color: var(--ink-2); font-size: 12px; }
  main { padding: 16px 20px; max-width: 900px; margin: 0 auto;
         display: flex; gap: 12px; flex-wrap: wrap; }
  .tile { background: var(--surface); border: 1px solid var(--ring);
          border-radius: 8px; padding: 10px 16px; min-width: 130px; }
  .tile .v { font-size: 22px; font-weight: 650;
             font-variant-numeric: tabular-nums; }
  .tile .k { color: var(--ink-2); font-size: 12px; }
</style>
</head>
<body>
<header><h1>polyaxon_tpu serving</h1><span id="state">…</span></header>
<main id="tiles"></main>
<script>
"use strict";
const esc = (s) => String(s ?? "").replace(/[&<>"']/g,
  c => ({"&":"&amp;","<":"&lt;",">":"&gt;",'"':"&quot;","'":"&#39;"}[c]));
function tile(k, v) {
  return `<div class="tile"><div class="v">${esc(v)}</div>` +
         `<div class="k">${esc(k)}</div></div>`;
}
let lastTokens = null, lastT = null;
async function refresh() {
  let s;
  try { s = await (await fetch("/v1/stats")).json(); }
  catch (e) {
    document.getElementById("state").textContent = "unreachable";
    return;
  }
  let fleet = null;
  try {
    const fr = await fetch("/v1/fleet");
    if (fr.ok) fleet = await fr.json();
  } catch (e) { /* single-engine server: no fleet surface */ }
  const now = performance.now();
  let rate = "";
  if (lastTokens != null && s.tokens_generated >= lastTokens && now > lastT) {
    rate = ((s.tokens_generated - lastTokens) / ((now - lastT) / 1000))
      .toFixed(1);
  }
  lastTokens = s.tokens_generated; lastT = now;
  document.getElementById("state").textContent =
    `engine ${s.engine}` + (s.kv ? ` · kv ${s.kv}` : "") +
    (s.stopped ? " · ✕ stopped" : " · ✓ live");
  const tiles = [
    tile("requests served", s.requests_served),
    tile("tokens generated", s.tokens_generated),
    rate !== "" ? tile("tokens/sec (page-window)", rate) : "",
    s.slots != null ? tile("slots active", `${s.active} / ${s.slots}`) : "",
    s.avg_occupancy != null ? tile("avg occupancy", s.avg_occupancy) : "",
    s.queued != null ? tile("queued", s.queued) : "",
    s.decode_steps != null ? tile("decode steps", s.decode_steps) : "",
    s.step_failures ? tile("step failures", s.step_failures) : "",
    s.rejected && Object.keys(s.rejected).length
      ? tile("rejected (shed)", Object.values(s.rejected)
          .reduce((a, b) => a + b, 0)) : "",
    s.traced_requests != null
      ? tile("traced requests", s.traced_requests) : "",
    s.kv_pages_total != null
      ? tile("kv pages free", `${s.kv_pages_free} / ${s.kv_pages_total}`) : "",
    s.kv_prefix_hits != null
      ? tile("prefix hit rate", (s.kv_prefix_hits + s.kv_prefix_misses)
          ? (s.kv_prefix_hits / (s.kv_prefix_hits + s.kv_prefix_misses))
              .toFixed(2)
          : "–") : "",
    s.prefill_tokens_skipped != null
      ? tile("prefill tokens cached",
          `${s.prefill_tokens_skipped} / ${s.prefill_tokens_total}`) : "",
    s.kv_radix != null
      ? tile("radix pages (ref/resident)",
          `${s.kv_radix.referenced} / ${s.kv_radix.resident}`) : "",
  ];
  if (fleet && fleet.per_replica) {
    for (const [rid, t] of Object.entries(fleet.per_replica)) {
      tiles.push(tile(`${rid} · ttft p50/p99 ms`,
        `${t.ttft_p50_ms ?? "–"} / ${t.ttft_p99_ms ?? "–"}`));
      if (t.preemptions)
        tiles.push(tile(`${rid} · preemptions`, t.preemptions));
    }
    if (fleet.ttft_skew != null)
      tiles.push(tile("ttft skew (max/median p99)",
        Number(fleet.ttft_skew).toFixed(2)));
  }
  document.getElementById("tiles").innerHTML = tiles.join("");
}
refresh();
setInterval(refresh, 2000);
</script>
</body>
</html>
"""


class _Handler(BaseHTTPRequestHandler):
    engine: _Engine
    protocol_version = "HTTP/1.1"
    # A streaming handler hands its delivery lags to the engine once it
    # holds this many (`_stream_generate`).
    LAG_MERGE_EVERY = 32

    def log_message(self, *args):
        pass

    def _json(self, payload: Any, status: int = 200,
              headers: Optional[dict] = None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for name, value in (headers or {}).items():
            self.send_header(name, value)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802
        if self.path == "/healthz":
            # The continuous engine reports queue depth + slot
            # occupancy; the static engine has no queue to report.
            if hasattr(self.engine, "health"):
                return self._json(self.engine.health())
            return self._json({"status": "ok", "model": self.engine.model})
        if self.path == "/metrics":
            # Prometheus scrape backed by the unified registry
            # (obs.metrics): the full serving SLO schema (TTFT/TPOT/
            # queue-wait, shed-load and admission counters, engine-tick
            # gauges) is pre-registered so scrapers see every family
            # before traffic lands, plus whatever else this process
            # recorded.
            from polyaxon_tpu.obs import metrics as obs_metrics

            obs_metrics.ensure_serving_metrics()
            body = obs_metrics.REGISTRY.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if self.path == "/alerts":
            # The serving process evaluates the same committed ruleset
            # (obs.rules) against ITS registry — the request-p99 and
            # queue-saturation rules live where those series do.
            from polyaxon_tpu.obs import rules as obs_rules

            alert_engine = obs_rules.default_engine()
            alert_engine.evaluate()
            return self._json(alert_engine.to_json())
        if self.path == "/v1/models":
            return self._json({"models": [self.engine.model]})
        if self.path == "/v1/stats":
            return self._json(self.engine.stats())
        if self.path == "/v1/fleet":
            # Fleet telemetry (ISSUE 20): aggregate stats plus the
            # per-replica breakdown read from the component-scoped
            # series. Only a ServingFleet front end carries it; a
            # single engine 404s and the stats page silently skips.
            if not hasattr(self.engine, "fleet_snapshot"):
                return self._json(
                    {"error": "fleet telemetry requires a "
                              "ServingFleet front end"}, status=404)
            return self._json(self.engine.fleet_snapshot())
        if self.path == "/requests":
            # Ring summaries, most recent first. Only the continuous
            # engine traces requests; the static engine 404s rather
            # than pretending an empty ring is a real answer.
            if not hasattr(self.engine, "recent_requests"):
                return self._json(
                    {"error": "request timelines require "
                              "--batching continuous"}, status=404)
            return self._json({"requests": self.engine.recent_requests()})
        m = re.match(r"^/requests/([0-9a-f]{1,64})/timeline$", self.path)
        if m is not None:
            if not hasattr(self.engine, "request_timeline"):
                return self._json(
                    {"error": "request timelines require "
                              "--batching continuous"}, status=404)
            timeline = self.engine.request_timeline(m.group(1))
            if timeline is None:
                return self._json(
                    {"error": f"unknown or evicted request "
                              f"`{m.group(1)}` (the trace ring keeps "
                              "the most recent requests only)"},
                    status=404)
            from polyaxon_tpu.obs.analyze import request_phases

            # Phase decomposition (queue-wait/prefill/decode ms, TTFT,
            # tokens) rides along so `plx ops request-timeline` and
            # humans with curl both get the numbers without walking
            # the span tree themselves.
            timeline["summary"] = request_phases(timeline)
            return self._json(timeline)
        m = re.match(r"^/requests/([0-9a-f]{1,64})$", self.path)
        if m is not None:
            # One request's summary row. Behind a fleet front end the
            # lookup fans out over every replica's ring and the row
            # carries the serving replica's id.
            if not hasattr(self.engine, "recent_requests"):
                return self._json(
                    {"error": "request timelines require "
                              "--batching continuous"}, status=404)
            rows = [r for r in self.engine.recent_requests()
                    if r.get("request_id") == m.group(1)]
            if not rows:
                return self._json(
                    {"error": f"unknown or evicted request "
                              f"`{m.group(1)}` (the trace ring keeps "
                              "the most recent requests only)"},
                    status=404)
            return self._json(rows[0])
        if self.path in ("/", "/ui"):
            body = STATS_PAGE.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        return self._json({"error": f"no route {self.path}"}, status=404)

    def do_POST(self):  # noqa: N802
        if self.path != "/v1/generate":
            return self._json({"error": f"no route {self.path}"}, status=404)
        try:
            length = int(self.headers.get("Content-Length") or 0)
            req = json.loads(self.rfile.read(length).decode() or "{}")
            tokens = req["tokens"]
            if (not isinstance(tokens, list)
                    or not all(isinstance(r, list) and r for r in tokens)):
                raise ValueError("`tokens` must be a non-empty list of "
                                 "non-empty token-id lists")
            max_new = int(req.get("max_new_tokens", 32))
            temperature = float(req.get("temperature", 0.0))
            seed = int(req.get("seed", 0))
            top_p = float(req.get("top_p", 1.0))
            top_k = int(req.get("top_k", 0))
            validate_sampling(top_p, top_k)
            eos_tokens = req.get("eos_tokens")
            if eos_tokens is None and "eos_token" in req:
                eos_tokens = [req["eos_token"]]
            if eos_tokens is not None:
                if (not isinstance(eos_tokens, list)
                        or not all(isinstance(t, int)
                                   and not isinstance(t, bool)
                                   for t in eos_tokens)):
                    raise ValueError(
                        "`eos_tokens` must be a list of token ids")
            # Request class picks the admission queue (`interactive` /
            # `batch` / `best-effort` — unknown labels fold to `batch`,
            # no minted priority) and labels the per-class SLO
            # histograms. Bounded so a client can't mint unbounded
            # label cardinality.
            klass = req.get("class", "batch")
            if (not isinstance(klass, str) or not klass
                    or len(klass) > 64):
                raise ValueError(
                    "`class` must be a non-empty string of at most "
                    "64 chars")
            if req.get("stream"):
                return self._stream_generate(tokens, max_new, temperature,
                                             seed, top_p, top_k,
                                             eos_tokens=eos_tokens,
                                             klass=klass)
            if hasattr(self.engine, "submit_all"):
                # Continuous engine: keep the request handles so the
                # response carries ids the caller can feed straight to
                # GET /requests/{id}/timeline.
                reqs = self.engine.submit_all(
                    tokens, max_new, temperature, seed, top_p, top_k,
                    eos_tokens=eos_tokens, klass=klass)
                out = [r.wait() for r in reqs]
                return self._json({"tokens": out,
                                   "request_ids": [r.id for r in reqs]})
            out = self.engine.generate(
                tokens, max_new_tokens=max_new,
                temperature=temperature, seed=seed,
                top_p=top_p, top_k=top_k, eos_tokens=eos_tokens)
            return self._json({"tokens": out})
        except QueueFull as exc:
            # Saturated: shed load honestly instead of queueing work
            # the client will have abandoned by decode time.
            return self._json({"error": str(exc)}, status=503,
                              headers={"Retry-After": str(exc.retry_after)})
        except (KeyError, ValueError, TypeError) as exc:
            return self._json({"error": str(exc)}, status=400)
        except Exception as exc:  # pragma: no cover
            return self._json({"error": f"{type(exc).__name__}: {exc}"},
                              status=500)

    def _sse(self, payload: Any, event: Optional[str] = None) -> None:
        msg = ""
        if event:
            msg += f"event: {event}\n"
        msg += f"data: {json.dumps(payload)}\n\n"
        self.wfile.write(msg.encode())
        self.wfile.flush()

    def _stream_generate(self, token_rows, max_new: int, temperature: float,
                         seed: int, top_p: float = 1.0,
                         top_k: int = 0, eos_tokens=None,
                         klass: str = "batch") -> None:
        """SSE token streaming. With the continuous engine, per-token
        events flow as rows decode: the handler reads each request's
        growing output (appends are GIL-atomic) and sleeps on the
        request's ``fresh`` event, which the engine sets once the step
        after the one that made the tokens is on the device
        (``batching.py _announce``), so that a hundred handlers write
        while the device computes and not while the engine thread needs
        the interpreter lock to launch. The wait's timeout is the net
        under endings that set no event (a rejection, a failure). The
        static engine emits the whole batch as a burst after its
        compiled run.

        After the write that brings a request level with its output the
        handler takes the time since the engine read that token off the
        device (``req.read_ns``) into a histogram of its own, and hands
        the counts to the engine every `LAG_MERGE_EVERY` of them and at
        the stream's end (``/v1/stats`` ``deliver_lag_hist``). A token
        written together with a later one is not measured: its reading
        time was overwritten."""
        # Validate before any header goes out, so bad requests are real
        # HTTP 400s (the caller catches ValueError) rather than error
        # events on an already-open stream. Both engines expose
        # _validate.
        for row in token_rows:
            self.engine._validate(row, max_new)

        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()
        reqs = []
        try:
            if hasattr(self.engine, "submit_all"):
                reqs = self.engine.submit_all(
                    token_rows, max_new, temperature, seed, top_p, top_k,
                    eos_tokens=eos_tokens, klass=klass)
                emitted = [0] * len(reqs)
                lags, unmerged = [0] * LOG_BUCKETS, 0
                while True:
                    progressed = False
                    for r in reqs:
                        r.fresh.clear()
                    for i, r in enumerate(reqs):
                        if emitted[i] >= len(r.out):
                            continue
                        while emitted[i] < len(r.out):
                            self._sse({"index": i,
                                       "token": r.out[emitted[i]]})
                            emitted[i] += 1
                        progressed = True
                        # (read first: the engine may stamp the next)
                        read_ns = r.read_ns
                        lags[log_bucket(time.perf_counter_ns()
                                        - read_ns)] += 1
                        unmerged += 1
                    if unmerged >= self.LAG_MERGE_EVERY:
                        self.engine.merge_deliver_lags(lags)
                        lags, unmerged = [0] * LOG_BUCKETS, 0
                    if all(r.done.is_set() and emitted[i] == len(r.out)
                           for i, r in enumerate(reqs)):
                        if unmerged:
                            self.engine.merge_deliver_lags(lags)
                        break
                    if not progressed:
                        waiting = next((r for r in reqs
                                        if not r.done.is_set()), reqs[0])
                        waiting.fresh.wait(0.1)
                failed = [r.error for r in reqs if r.error]
                if failed:
                    return self._sse({"error": failed[0]}, event="error")
                out = [r.out for r in reqs]
                return self._sse(
                    {"tokens": out,
                     "request_ids": [r.id for r in reqs]}, event="done")
            out = self.engine.generate(
                token_rows, max_new_tokens=max_new,
                temperature=temperature, seed=seed,
                top_p=top_p, top_k=top_k, eos_tokens=eos_tokens)
            for i, row in enumerate(out):
                for tok in row:
                    self._sse({"index": i, "token": tok})
            self._sse({"tokens": out}, event="done")
        except (BrokenPipeError, ConnectionResetError):
            # Client went away mid-stream: stop burning slots on output
            # nobody will read (same invariant as generate()'s timeout
            # cancellation).
            for r in reqs:
                if not r.done.is_set():
                    self.engine.cancel(r)
        except Exception as exc:  # noqa: BLE001 — headers already sent
            try:
                self._sse({"error": f"{type(exc).__name__}: {exc}"},
                          event="error")
            except OSError:
                pass


class ServingServer:
    """``with ServingServer("llama_tiny") as s: requests → s.url``

    ``batching="continuous"`` swaps the static whole-budget engine for
    the slot-pool continuous batcher (serving/batching.py): concurrent
    HTTP requests interleave token-by-token instead of queueing behind
    each other's full generations. Decoder-only models only.
    """

    def __init__(self, model: str, checkpoint: Optional[str] = None,
                 host: str = "127.0.0.1", port: int = 0, seed: int = 0,
                 batching: str = "static", slots: int = 4,
                 mesh_axes: Optional[dict] = None,
                 quantize: Optional[str] = None, kv: str = "dense",
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 draft_model: Optional[str] = None,
                 draft_checkpoint: Optional[str] = None, spec_k: int = 4,
                 lora_alpha: float = 16.0,
                 prefill_chunk: Optional[int] = None,
                 prefill_slots: Optional[int] = None,
                 prefill_lane_budget: int = 1,
                 decode_lane_budget: int = 1,
                 max_pending: Optional[int] = None,
                 class_admission: bool = True,
                 class_max_pending: Optional[dict] = None,
                 preemption: bool = True,
                 request_tracing: bool = True,
                 trace_dump_path: Optional[str] = None):
        self.mesh = None
        if mesh_axes:
            from polyaxon_tpu.parallel import build_mesh
            from polyaxon_tpu.polyflow.runs import V1MeshSpec

            if any(v == -1 for v in mesh_axes.values()):
                devices = jax.devices()  # -1 axis absorbs all devices
            else:
                n = 1
                for v in mesh_axes.values():
                    n *= v
                devices = jax.devices()[:n]
            self.mesh = build_mesh(V1MeshSpec(axes=mesh_axes),
                                   devices=devices)
        cfg, params = load_params(model, checkpoint, seed=seed,
                                  mesh=self.mesh, lora_alpha=lora_alpha,
                                  quantize=quantize)
        draft = None
        if draft_model is not None:
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            # Validate the pairing from the CONFIG before materializing
            # a single draft weight (a mispaired real-size draft would
            # otherwise load GBs just to be refused).
            draft_vocab = _family(draft_model).CONFIGS[draft_model].vocab_size
            if draft_vocab != cfg.vocab_size:
                raise ValueError(
                    f"draft `{draft_model}` (vocab {draft_vocab}) "
                    f"and target `{model}` (vocab {cfg.vocab_size}) must "
                    "share a token space — mismatched drafts propose "
                    "garbage and silently collapse acceptance")
            # mesh= so the draft shards like the target: left off, an
            # unsharded real-size draft sits whole on device 0 (OOM
            # risk) or gets replicated by GSPMD on every call.
            draft_cfg, draft_params = load_params(
                draft_model, draft_checkpoint, seed=seed, mesh=self.mesh,
                quantize=quantize)
            draft = (draft_model, draft_cfg, draft_params, spec_k)
            logger.info("speculative decoding: draft=%s k=%d",
                        draft_model, spec_k)
        if batching == "continuous":
            from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

            self.engine = ContinuousBatchingEngine(
                model, cfg, params, slots=slots, kv=kv,
                page_size=page_size, kv_pages=kv_pages,
                prefix_cache=prefix_cache, draft=draft,
                prefill_chunk=prefill_chunk,
                prefill_slots=prefill_slots,
                prefill_lane_budget=prefill_lane_budget,
                decode_lane_budget=decode_lane_budget,
                max_pending=max_pending,
                class_admission=class_admission,
                class_max_pending=class_max_pending,
                preemption=preemption,
                request_tracing=request_tracing,
                trace_dump_path=trace_dump_path, mesh=self.mesh)
        elif batching == "static":
            if prefill_chunk is not None:
                raise ValueError(
                    "--prefill-chunk requires --batching continuous "
                    "(the static engine compiles whole generations)")
            if prefill_slots is not None:
                raise ValueError(
                    "--prefill-slots requires --batching continuous "
                    "with kv='paged' (the disaggregated lane scheduler "
                    "lives in the continuous engine)")
            if max_pending is not None:
                raise ValueError(
                    "--max-pending requires --batching continuous (the "
                    "static engine has no pending queue to bound)")
            if class_max_pending:
                raise ValueError(
                    "--class-max-pending requires --batching continuous "
                    "(the static engine has no pending queue to bound)")
            if kv != "dense":
                raise ValueError(
                    "kv='paged' requires --batching continuous (the "
                    "static engine compiles whole generations, not "
                    "pooled steps)")
            self.engine = _Engine(model, cfg, params, draft=draft)
        else:
            raise ValueError(
                f"unknown batching mode `{batching}` "
                "(expected 'static' or 'continuous')")
        handler = type("BoundHandler", (_Handler,), {"engine": self.engine})
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.host = host
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "ServingServer":
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self._thread.start()
        logger.info("serving %s at %s", self.engine.model, self.url)
        return self

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            # Drain the serve loop so in-flight handlers finish before
            # the engine (their backend) is stopped underneath them.
            self._thread.join(timeout=5)
            self._thread = None
        if hasattr(self.engine, "stop"):
            self.engine.stop()

    def __enter__(self) -> "ServingServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
