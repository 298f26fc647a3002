#!/usr/bin/env python3
"""What the chip's compiler says a cell's programs need, before any chip is.

Compiles, for a *described* v5e (no chip attached; on-chip-measurement
guide, section 2), the programs a configuration's cells run, at candidate
depths and engine sizes, and prints each one's ``memory_analysis()``.
PERF.md's depth-cut tables come from this. Nothing is measured here.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py serve \
        --config mistral_7b_v03 --layers 8 --slots 16 --pages 2048
    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py train \
        --config mistral_7b_v03 --layers 2 --batch 4
    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py train \
        --config mixtral_8x7b_v01 --layers 1 --batch 8 --chips 4

The program asks ``jax.default_backend()`` to choose between a Pallas
kernel and its reference path; under the CPU backend it would compile
the reference. The script answers "tpu" for it while it lowers (steering
done here, not through an option of the program).

A sizing tool, not on a run's path: where a run goes through the
program's server, this calls the family module's ``init`` and paged
functions (``paged_init_cache``, ``decode_step_paged``, the prefill and
suffix pairs) itself, with the arguments the two families here take. A
family with other state to size brings a tool of its own.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

GIB = 2.0 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "program": name,
        "args_gib": round(m.argument_size_in_bytes / GIB, 3),
        "out_gib": round(m.output_size_in_bytes / GIB, 3),
        "alias_gib": round(m.alias_size_in_bytes / GIB, 3),
        "temp_gib": round(m.temp_size_in_bytes / GIB, 3),
        "total_gib": round(total / GIB, 3)}), flush=True)
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=["serve", "train"])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--pages", type=int, default=2048)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--suffix", type=int, default=256)
    ap.add_argument("--prefix-pages", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from harness import program, spec

    jax.config.update("jax_enable_compilation_cache", False)
    config = spec.load_config(args.config)
    config["num_hidden_layers"] = args.layers
    for role in ("serve", "train"):
        if role in config:
            config[role]["num_hidden_layers"] = args.layers
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        if args.kind == "serve":
            serve = dict(config["serve"])
            serve.update(slots=args.slots, kv_pages=args.pages)
            if args.max_len:
                serve["max_len"] = args.max_len
            config["serve"] = serve
            name, family, cfg = program.register(config, "serve")
            cfg = dataclasses.replace(cfg, paged_attention_impl="pallas")
            one = SingleDeviceSharding(topo.devices[0])

            def aval(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

            params = jax.tree.map(aval, jax.eval_shape(
                lambda k: family.init(cfg, k)["params"], jax.random.key(0)))
            page = serve["page_size"]
            cache = jax.tree.map(aval, jax.eval_shape(
                lambda: family.paged_init_cache(cfg, args.pages + 1, page)))
            maxp = serve["max_len"] // page
            B = args.slots
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)

            def step(params, cache, tokens, pos, tables):
                logits, cache = family.decode_step_paged(
                    cfg, params, cache, tokens, pos, tables)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache

            worst = report("decode_step", jax.jit(step, donate_argnums=(1,)).lower(
                params, cache, i32(B), i32(B), i32(B, maxp)).compile())

            def prefill(params, prompt, cache, page_ids):
                k, v = family.paged_prefill_kv(cfg, params, prompt)
                return family.paged_insert_prefill(cache, k, v, page_ids, page)

            for plen in sorted({args.prompt - 1,
                                args.prefix_pages * page + 31}):
                worst = max(worst, report(
                    f"prefill_{plen}", jax.jit(prefill, donate_argnums=(2,)).lower(
                        params, i32(1, plen), cache, i32(maxp)).compile()))

            def suffix(params, suf, cache, page_ids, m, real_len):
                pref = jnp.maximum(page_ids[:args.prefix_pages], 0)
                kp = family.paged_gather(cache["k"], pref)
                vp = family.paged_gather(cache["v"], pref)
                k, v = family.paged_prefill_suffix_kv(cfg, params, suf, kp, vp, m)
                return family.paged_insert_suffix(cache, k, v, page_ids, m,
                                                  page, real_len)

            s0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
            worst = max(worst, report(
                f"suffix_{args.suffix}x{args.prefix_pages}",
                jax.jit(suffix, donate_argnums=(2,)).lower(
                    params, i32(1, args.suffix), cache, i32(maxp), s0,
                    s0).compile()))
            print(json.dumps({"layers": args.layers, "slots": B,
                              "pages": args.pages, "max_len": serve["max_len"],
                              "worst_program_gib": round(worst / GIB, 3),
                              "chip_gib": 15.75}))
        else:
            from polyaxon_tpu.models import get_model
            from polyaxon_tpu.parallel import build_mesh, rules_for_mesh
            from polyaxon_tpu.parallel.sharding import batch_spec
            from polyaxon_tpu.runtime.config import RuntimeConfig
            from polyaxon_tpu.runtime.optim import build_optimizer
            from polyaxon_tpu.runtime.step import build_init, build_train_step

            train = dict(config["train"])
            train["global_batch_size"] = args.batch
            config["train"] = train
            name, family, cfg = program.register(config, "train")
            runtime = program.runtime_section(config, name, seed=0,
                                              seq_len=4096)
            rc = RuntimeConfig.model_validate(runtime)
            overrides = rc.model_overrides(type(cfg))
            model_def = get_model(name, **overrides)
            axes = dict(train["mesh"]) if args.chips > 1 else {"dp": 1}
            mesh = build_mesh(axes=axes, devices=list(topo.devices)[:args.chips])
            rules = rules_for_mesh(mesh)
            opt = build_optimizer(rc)
            with mesh:
                init_fn = build_init(model_def, opt, mesh, rules)
                state = jax.eval_shape(init_fn, jax.random.key(0))
                lowered_init = init_fn.lower(jax.random.key(0))
                out_sh = lowered_init.compile().output_shardings
                state = jax.tree.map(
                    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                    state, out_sh)
                batch = {"tokens": jax.ShapeDtypeStruct(
                    (args.batch, 4096), jnp.int32,
                    sharding=NamedSharding(mesh, batch_spec(mesh, rules, ndim=2)))}
                step = build_train_step(model_def, opt, mesh, rules)
                rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                           sharding=NamedSharding(mesh, P()))
                try:
                    total = report("train_step",
                                   step.lower(state, batch, rng).compile())
                except Exception as exc:  # the compiler's own refusal
                    import re
                    found = re.search(r"Used [^.]*\.\d*G of [^ ]* hbm", str(exc))
                    print(json.dumps({"layers": args.layers,
                                      "batch": args.batch,
                                      "chips": args.chips, "refused":
                                      found.group(0) if found
                                      else str(exc)[:300]}))
                    return
            print(json.dumps({"layers": args.layers, "batch": args.batch,
                              "chips": args.chips,
                              "per_chip_gib": round(total / GIB, 3),
                              "chip_gib": 15.75}))
    finally:
        jax.default_backend = real_backend


if __name__ == "__main__":
    main()
