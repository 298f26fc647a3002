#!/usr/bin/env python3
"""`tools/aot_memory.py` for a family whose cache has per-row leaves
(``paged_init_rows``: a recurrent state a sequence beside the pages).

Compiles, for a *described* v5e (no chip attached), the programs a
serving cell of such a configuration runs, at its own sizes, as the
engine builds them (``serving/batching.py``): the weights' draw with the
cast to the served precision, the decode step over the paged pool and
the rows, a whole-prompt prefill. Prints each one's
``memory_analysis()`` and, for the decode step, every operation that
copies a whole per-row leaf (none is the point: a step reads and writes
each row's state in place). Nothing is measured here.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_rows.py \
        --config nemotron3_super_120b_a12b [--slots 64] [--pages 8192]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from harness import spec  # noqa: E402

# The one-line report of a compiled program's memory is the other tool's.
_sizing = spec._module(os.path.join(HERE, "aot_memory.py"), "aot_memory")
GIB = _sizing.GIB


def report(name, compiled, **more):
    total = _sizing.report(name, compiled)
    if more:
        print(json.dumps({"program": name, **more}), flush=True)
    return total


def whole_leaf_copies(hlo: str, leaves: dict) -> list:
    """The instructions of `hlo` whose result is a whole per-row leaf
    and which are a copy of one (`copy`, or a fusion the compiler named
    a copy)."""
    out = []
    for name, leaf in leaves.items():
        shape = ",".join(str(n) for n in leaf.shape)
        for line in hlo.splitlines():
            head = line.strip().split(" = ")
            if len(head) < 2 or f"[{shape}]" not in head[1].split("(")[0]:
                continue
            if re.search(r"\bcopy(-start|-done)?\(|copy_fusion|kind=kCopy",
                         head[1]) or head[0].lstrip("%").startswith("copy"):
                out.append(f"{name}: {line.strip()[:160]}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--pages", type=int, default=None)
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--keep-hlo", default=None, metavar="DIR")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import program
    from polyaxon_tpu.models.common import served_params

    jax.config.update("jax_enable_compilation_cache", False)
    config = spec.load_config(args.config)
    serve = config["serve"]
    slots = args.slots or serve["slots"]
    pages = args.pages or serve["kv_pages"]
    page = serve["page_size"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        _, family, cfg = program.register(config, "serve")

        def aval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

        def draw(key):
            return served_params(family.init(cfg, key)["params"], cfg.dtype,
                                 family.READ_AT_FLOAT32)

        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
        worst = report("load_params", jax.jit(draw).lower(key).compile())
        params = jax.tree.map(aval, jax.eval_shape(draw, jax.random.key(0)))

        def build():
            cache = family.paged_init_cache(cfg, pages + 1, page)
            cache["rows"] = family.paged_init_rows(cfg, slots)
            return cache

        cache = jax.tree.map(aval, jax.eval_shape(build))
        weights = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
        held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        maxp = serve["max_len"] // page
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)

        def step(params, cache, tokens, pos, tables):
            logits, cache = family.decode_step_paged(
                cfg, params, cache, tokens, pos, tables)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, i32(slots), i32(slots), i32(slots, maxp)).compile()
        hlo = compiled.as_text()
        copies = whole_leaf_copies(hlo, cache["rows"])
        worst = max(worst, report("decode_step", compiled,
                                  whole_row_leaf_copies=copies))
        if args.keep_hlo:
            os.makedirs(args.keep_hlo, exist_ok=True)
            with open(os.path.join(args.keep_hlo, "decode_step.hlo"),
                      "w") as fh:
                fh.write(hlo)

        def prefill(params, prompt, cache, page_ids, row):
            return family.paged_insert_prefill(
                cache, *family.paged_prefill_kv(cfg, params, prompt),
                page_ids, page, row)

        plen = args.prompt - 1
        compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, i32(1, plen), cache, i32(maxp), i32()).compile()
        worst = max(worst, report(f"prefill_{plen}", compiled))
        if args.keep_hlo:
            with open(os.path.join(args.keep_hlo, "prefill.hlo"), "w") as fh:
                fh.write(compiled.as_text())
        print(json.dumps({
            "slots": slots, "pages": pages, "max_len": serve["max_len"],
            "weights_gib": round(weights / GIB, 3),
            "cache_gib": round(held / GIB, 3),
            "worst_program_gib": round(worst / GIB, 3), "chip_gib": 15.75}))
    finally:
        jax.default_backend = real_backend


if __name__ == "__main__":
    main()
