#!/usr/bin/env python
"""Speculative-decoding benchmark: wall time of plain greedy vs
draft-accelerated greedy on the same target, plus the acceptance
observable (verify rounds). Lossless is asserted, not assumed.

The interesting on-chip pairing is a small draft for a big target
(e.g. --model llama3_1b --draft llama3_draft_200m — drafts must share
the target's vocab): each verify round costs
one target chunk forward instead of (accepted+1) sequential target
decode steps, so speedup ~= mean_accepted+1 divided by the relative
cost of draft steps + chunk. Writes bench_spec_results.json.

Usage: python scripts/bench_spec.py [--model llama3_1b]
       [--draft llama3_draft_200m] [--max-new 128] [--k 4]
       [--prompt-len 64]
CPU smoke: JAX_PLATFORMS=cpu ... --model llama_tiny --draft llama_tiny --quick
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="llama3_1b")
    parser.add_argument("--draft", default="llama3_draft_200m")
    parser.add_argument("--max-new", type=int, default=128)
    parser.add_argument("--k", type=int, default=4)
    parser.add_argument("--prompt-len", type=int, default=64)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()
    if args.quick:
        args.max_new, args.prompt_len, args.reps = 16, 8, 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.models import family_of
    from polyaxon_tpu.serving.server import load_params
    from polyaxon_tpu.serving.speculative import generate_speculative

    cfg, params = load_params(args.model, seed=0)
    draft_cfg, draft_params = load_params(args.draft, seed=0)
    if draft_cfg.vocab_size != cfg.vocab_size:
        print(f"draft vocab {draft_cfg.vocab_size} != target vocab "
              f"{cfg.vocab_size}: a mismatched draft proposes garbage — "
              "pick a same-vocab pair", file=sys.stderr)
        return 2
    family, draft_family = family_of(args.model), family_of(args.draft)
    prompt = jax.random.randint(jax.random.key(1), (1, args.prompt_len),
                                0, min(cfg.vocab_size,
                                       draft_cfg.vocab_size), jnp.int32)

    plain = jax.jit(lambda p, pr: family.generate(
        cfg, p, pr, max_new_tokens=args.max_new))
    spec = jax.jit(lambda p, dp, pr: generate_speculative(
        cfg, p, draft_cfg, dp, pr, max_new_tokens=args.max_new,
        k=args.k, family=family, draft_family=draft_family,
        return_rounds=True))

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))  # compile + warm
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return out, sorted(times)[len(times) // 2]

    want, t_plain = timed(plain, params, prompt)
    (got, rounds), t_spec = timed(spec, params, draft_params, prompt)
    lossless = bool((np.asarray(got) == np.asarray(want)).all())
    assert lossless, "speculative output diverged from plain greedy"

    out = {
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "model": args.model, "draft": args.draft, "k": args.k,
        "max_new": args.max_new, "prompt_len": args.prompt_len,
        "plain_s": round(t_plain, 3),
        "spec_s": round(t_spec, 3),
        "speedup": round(t_plain / t_spec, 3) if t_spec else None,
        "verify_rounds": int(rounds),
        "mean_emitted_per_round": round(args.max_new / max(int(rounds), 1),
                                        2),
        "lossless": lossless,
    }
    path = os.path.join(REPO, "bench_spec_results.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
