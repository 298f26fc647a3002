"""The flash kernels alone on the chip: ms a call, best of n.

    python scripts/bench_flash.py [--tree DIR] \
        --shape "3,4096,32,8,128;1,12288,28,4,128@4096" [--n 3] \
        [--tiles "fwd=512x4096x512,dkdv=...,dq=...;fwd=..."] \
        [--part copies|all-masked]
    python scripts/bench_flash.py [--tree DIR] --startup 12288 [--sites 8]

``--shape`` is B,S,H,KV,D (bfloat16, causal; ``@W`` a sliding window;
several shapes apart by ``;``). ``--tiles`` gives a kernel's resident
rows, copied positions and sub-block (several sets apart by ``;``);
what is left out is what ``ops/flash.py auto_blocks`` gives. ``--part`` cuts the
kernels the ways they can be cut from outside: ``copies`` (the grid and
its copies, no turn: what a grid step costs empty), ``all-masked``
(every visible sub-block takes the masked turn). ``--tree`` times
another checkout's kernels (the parent's, unpacked beside this one):
the seed's signatures (one 512 x 512 tile a step, 256 x 256 backward)
are told by the module lacking ``KERNELS``.

``--startup S`` is what a server's start pays for the kernels: a
program of ``--sites`` forward calls at 28 / 4 heads of 128 over S
tokens (a quarter of them full, the rest under a window of 4,096, as
SmallThinker's prefill has them), traced, lowered, compiled and run
once; run it twice with one ``JAX_COMPILATION_CACHE_DIR`` and the
second is a warm start. PERF.md §5-6 (PRs 50-51) were counted with
this; no benchmark cell runs it. One JSON line a run on stdout. Needs a
TPU: a time from the CPU's interpreter says nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

KERNELS = ("fwd", "dkdv", "dq")


def _tiles(text: str) -> dict:
    """``fwd=512x4096x512,dq=...`` -> {"fwd": (512, 4096, 512), ...}."""
    out = {}
    for item in filter(None, (text or "").split(",")):
        kernel, _, sizes = item.partition("=")
        if kernel not in KERNELS:
            raise SystemExit(f"unknown kernel `{kernel}` (one of {KERNELS})")
        out[kernel] = tuple(int(n) for n in sizes.split("x"))
    return out


def cut(flash, part: str) -> None:
    """Cut ``flash``'s kernels the way ``part`` names (module docstring)."""
    if part == "all-masked":
        ranges = flash._turn_ranges

        def all_masked(*args, over_cols, **kw):
            lo, _, _, hi = ranges(*args, over_cols=over_cols, **kw)
            return (lo, lo, lo, hi) if over_cols else (lo, hi, hi, hi)
        flash._turn_ranges = all_masked
    elif part == "copies":
        flash._walk = lambda *args, **kw: None
    elif part:
        raise SystemExit(f"unknown part `{part}`")


def best_ms(fn, args, n: int, calls: int = 5) -> float:
    """Best of ``n`` timings of ``calls`` back-to-back calls, ms a call
    (the first call compiles and is not timed)."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def time_kernels(flash, shape, tiles, window=0, n=3, seed=0) -> dict:
    """ms a call of the three kernels of ``flash`` at ``shape``, each in
    a program of its own (the backward's two are one function: the
    unused one's call is dropped by the compiler). ``tiles`` None: the
    seed's signatures."""
    import jax
    import jax.numpy as jnp

    b, s, h, kv, d = shape
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, kv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, kv, s, d), jnp.bfloat16)
    do = jax.random.normal(keys[3], (b, h, s, d), jnp.bfloat16)
    scale = d ** -0.5
    fwd_tiles = (tiles["fwd"],) if tiles else (512, 512)
    bwd_tiles = (tiles["dkdv"], tiles["dq"]) if tiles else (256, 256)

    @jax.jit
    def fwd(q, k, v):
        return flash._flash_fwd_pallas(q, k, v, None, True, scale,
                                       *fwd_tiles, False, window)

    o, lse = jax.block_until_ready(fwd(q, k, v))
    dlse = jnp.zeros_like(lse)

    def bwd(q, k, v, o, lse, do):
        return flash._flash_bwd_pallas(
            True, scale, *bwd_tiles, window, False,
            (q, k, v, None, o, lse), do, dlse)

    dkdv = jax.jit(lambda *a: bwd(*a)[1:])
    dq = jax.jit(lambda *a: bwd(*a)[0])
    res = (q, k, v, o, lse, do)
    return {"fwd": best_ms(fwd, (q, k, v), n),
            "dkdv": best_ms(dkdv, res, n),
            "dq": best_ms(dq, res, n)}


def startup(flash, s: int, sites: int) -> dict:
    """Seconds to trace, lower, compile and first run a program of
    ``sites`` forward calls over ``s`` tokens (module docstring)."""
    import jax
    import jax.numpy as jnp

    def program(q, k, v):
        for i in range(sites):
            q = q + flash.flash_attention(
                q, k, v, causal=True, window=4096 if i % 4 else None)
        return q

    keys = jax.random.split(jax.random.key(0), 2)
    q = jax.random.normal(keys[0], (1, s, 28, 128), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, s, 4, 128), jnp.bfloat16)
    jax.block_until_ready((q, k))
    t0 = time.perf_counter()
    traced = jax.jit(program).trace(q, k, k)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()
    jax.block_until_ready(compiled(q, k, k))
    t4 = time.perf_counter()
    return {"trace_s": t1 - t0, "lower_s": t2 - t1, "compile_s": t3 - t2,
            "first_run_s": t4 - t3, "text_bytes": len(lowered.as_text())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--shape", default="3,4096,32,8,128")
    ap.add_argument("--n", type=int, default=3)
    ap.add_argument("--tiles", default="")
    ap.add_argument("--part", default="")
    ap.add_argument("--startup", type=int, default=0)
    ap.add_argument("--sites", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax

    from polyaxon_tpu.ops import flash
    from polyaxon_tpu.runtime import compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("bench_flash needs a TPU (found "
                         f"`{device.platform}`)")
    line = {"tree": args.tree, "device": device.device_kind}
    if args.startup:
        compile_cache.enable()
        line.update(startup=args.startup, sites=args.sites,
                    **startup(flash, args.startup, args.sites))
        print(json.dumps(line), flush=True)
        return 0
    if hasattr(flash, "KERNELS"):
        cut(flash, args.part)
    for spec in args.shape.split(";"):
        spec, _, window = spec.partition("@")
        shape = tuple(int(n) for n in spec.split(","))
        b, s, h, kv, d = shape
        window = int(window or 0)
        # The causal half is what the algorithm needs (benchmark/kernels/
        # flash_fwd.py): two products forward, four in dk/dv, three in dq.
        half = 2.0 * b * h * s * min(s, window or s) * d / 197e12 * 1e3
        for override in args.tiles.split(";"):
            tiles = None
            if hasattr(flash, "KERNELS"):
                tiles = flash.auto_blocks(s, s, d, window=window or None)
                for kernel, (resident, copied, sub) in _tiles(
                        override).items():
                    tiles[kernel] = (
                        (copied, resident, sub) if kernel == "dkdv"
                        else (resident, copied, sub))
            try:
                ms = time_kernels(flash, shape, tiles, window, args.n)
            except Exception as exc:  # noqa: BLE001 — a refusal is a result
                print(json.dumps({**line, "shape": shape, "tiles": tiles,
                                  "error": str(exc)[:300]}), flush=True)
                continue
            print(json.dumps({
                **line, "shape": shape, "window": window, "part": args.part,
                "tiles": tiles, "ms": ms, "share_of_197_tflops": {
                    "fwd": half / ms["fwd"], "dkdv": 2.0 * half / ms["dkdv"],
                    "dq": 1.5 * half / ms["dq"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
