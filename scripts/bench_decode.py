#!/usr/bin/env python
"""Serving decode throughput: bf16 vs --quantize int8, on the current
backend (its result names the platform it ran on).

Decode is HBM-bandwidth-bound — each generated token re-reads the whole
weight tree — so int8 weight-only quantization (serving/quantize.py)
should approach 2x tokens/sec on large models. This measures the real
number plus the quantization noise (greedy-token agreement vs bf16) so
`plx serve --quantize int8` ships with a recorded quality/throughput
tradeoff (VERDICT r2 item 10).

Usage: python scripts/bench_decode.py [--model llama3_1b] [--slots 8]
       [--steps 256] [--prompt-len 32]
Writes bench_decode_results.json at the repo root.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(model: str, quantize: bool, slots: int, steps: int,
            prompt_len: int, seed: int = 0,
            lm_chunk: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.models import family_of
    from polyaxon_tpu.serving.quantize import tree_bytes
    from polyaxon_tpu.serving.server import load_params

    family = family_of(model)
    cfg, params = load_params(model, seed=seed)
    if lm_chunk is not None:
        # Sweepable lever: the quantized decode-logits vocab chunk
        # (models/common.py lm_logits) — fewer/larger matmuls per step
        # at bigger chunks, with the int8-on-carry guarantee unchanged.
        import dataclasses

        cfg = dataclasses.replace(cfg, lm_logits_chunk=lm_chunk)
    full_bytes = tree_bytes(params)
    if quantize:
        # From float32, as the server does (load_params quantizes the
        # tree before any cast to the compute dtype).
        _, params = load_params(model, seed=seed, quantize="int8")
    max_len = min(cfg.max_seq_len, prompt_len + steps + 8)

    # The continuous engine's exact step program, driven synchronously:
    # one ragged decode step for the whole slot pool, greedy rows.
    # Quantized trees pass through whole — weights unwrap at their
    # consumption sites inside the model (models/common.py _w), the
    # same contract the engines use.
    def step(params, cache, tokens, pos):
        logits, cache = family.decode_step_ragged(
            cfg, params, cache, tokens, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    step = jax.jit(step, donate_argnums=(1,))

    cache = family.cb_init_cache(cfg, slots, max_len)
    prompt = jax.random.randint(jax.random.key(1), (1, prompt_len), 0,
                                cfg.vocab_size, jnp.int32)
    row = jax.jit(
        lambda p, t: family.cb_prefill(cfg, p, t, max_len)
    )(params, prompt)
    for b in range(slots):
        cache = family.insert_cache_row(cache, row, jnp.int32(b))
    pos = jnp.full((slots,), prompt_len - 1, jnp.int32)
    cur = jnp.full((slots,), int(prompt[0, -1]), jnp.int32)

    # Warm (compile) + timed run.
    cur, cache = step(params, cache, cur, pos)
    pos = pos + 1
    jax.block_until_ready(cur)
    emitted = []
    t0 = time.perf_counter()
    for _ in range(steps):
        cur, cache = step(params, cache, cur, pos)
        pos = pos + 1
        emitted.append(cur)
    jax.block_until_ready(cur)
    dt = time.perf_counter() - t0
    tokens = np.asarray(jnp.stack(emitted))  # [steps, slots]
    # The EFFECTIVE vocab chunk, not just the request: _lm_chunk_len
    # floors to a power of two capped at V//2, so distinct --lm-chunk
    # values can compile the SAME program — the sweep record must show
    # that, or a no-op delta reads as a lever effect.
    from polyaxon_tpu.models.common import _lm_chunk_len

    effective_chunk = (_lm_chunk_len(cfg.vocab_size, cfg.lm_logits_chunk)
                       if quantize else None)
    return {
        "model": model,
        "quantize": "int8" if quantize else None,
        **({"lm_chunk_effective": effective_chunk}
           if effective_chunk is not None else {}),
        "slots": slots,
        "decode_steps": steps,
        "weight_bytes": tree_bytes(params),
        "weight_bytes_bf16": full_bytes,
        "tokens_per_sec": round(steps * slots / dt, 2),
        "step_ms": round(dt / steps * 1e3, 3),
        "tokens": tokens,
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="llama3_1b")
    parser.add_argument("--slots", type=int, default=8)
    parser.add_argument("--steps", type=int, default=256)
    parser.add_argument("--prompt-len", type=int, default=32)
    def _positive(v):
        v = int(v)
        if v < 1:
            raise argparse.ArgumentTypeError(
                "lm-chunk must be >= 1 (chunk<=0 would silently fall "
                "back to the monolithic dequant this bench exists to "
                "avoid)")
        return v

    parser.add_argument("--lm-chunk", type=_positive, default=None,
                        help="quantized decode-logits vocab chunk "
                             "(default: the model config's 4096)")
    args = parser.parse_args()

    import jax

    rows = []
    for quantize in (False, True):
        r = measure(args.model, quantize, args.slots, args.steps,
                    args.prompt_len, lm_chunk=args.lm_chunk)
        print(f"{args.model} quantize={r['quantize']}: "
              f"{r['tokens_per_sec']} tok/s ({r['step_ms']} ms/step, "
              f"weights {r['weight_bytes'] / 2**20:.0f} MiB)", flush=True)
        rows.append(r)

    bf16, int8 = rows
    agree = float((bf16.pop("tokens") == int8.pop("tokens")).mean())
    # Bandwidth roofline context: each decode step re-reads the whole
    # weight tree, so implied bandwidth = weight_bytes / step_time. On
    # a v5e (~819 GB/s HBM) a bandwidth-bound step cannot beat
    # weight_bytes/819e9 — if the bf16 step is near that bound, int8
    # SHOULD approach 2x; if far below it, decode is latency/compute
    # bound there and int8's ceiling shrinks accordingly.
    V5E_HBM_GBPS = 819.0
    for r in rows:
        gb = r["weight_bytes"] / 1e9
        # 3 SIGNIFICANT digits, not 3 decimals: tiny-model bounds are
        # sub-microsecond and fixed rounding would record 0.0.
        r["implied_gbps"] = float(f"{gb / (r['step_ms'] / 1e3):.3g}")
        r["hbm_bound_step_ms_v5e"] = float(f"{gb / V5E_HBM_GBPS * 1e3:.3g}")
    out = {
        "backend": jax.devices()[0].platform,
        **({"lm_chunk": args.lm_chunk}
           if args.lm_chunk is not None else {}),
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "results": rows,
        "int8_speedup": round(int8["tokens_per_sec"]
                              / bf16["tokens_per_sec"], 3),
        # Greedy-token agreement over the whole run: the end-to-end
        # quality signal (argmax flips compound once sequences diverge,
        # so this is a conservative lower bound on per-step agreement).
        "greedy_token_agreement": round(agree, 4),
    }
    path = os.path.join(REPO, "bench_decode_results.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"int8 speedup {out['int8_speedup']}x, greedy agreement "
          f"{out['greedy_token_agreement']}; wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
