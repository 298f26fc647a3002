"""The chip's compiler, asked about the hot-path kernels before any chip is.

Interpret mode on the CPU mesh runs a Pallas kernel as plain jax.numpy,
so it cannot see what the TPU lowering refuses: a block whose trailing
dims are not whole tiles, more VMEM than a kernel may use, a Mosaic call
GSPMD is asked to partition. libtpu compiles for a *described* topology
with no chip attached (on-chip-measurement guide, section 2.3), so each
case here is a real Mosaic compile at a published model's widths, a
second or two each.

libtpu admits one loading process at a time (a second one aborts on its
lock file), so every case compiles in ONE child process, started once
per test run behind a file lock — xdist workers share its JSON report —
and each case asserts its own entry. The module skips where the topology
cannot be described.
"""

from __future__ import annotations

import contextlib
import fcntl
import json
import os
import re
import subprocess
import sys
import time

import pytest

TOPOLOGY = "v5e:2x2"
FLASH_KERNELS = {"flash_fwd": 1, "flash_bwd_dkdv": 1, "flash_bwd_dq": 1}
PAGED_KERNELS = {"paged_decode": 1}

# name -> (kind, shape/mesh arguments, kernels the compiled module holds).
# Widths are the zoo's published configs (models/llama.py CONFIGS):
# llama3_1b H32/KV8/D64, llama3_8b and mistral_7b H32/KV8/D128 (mistral
# adds the 4096 window), gemma_2b H8/KV1/D256; serving pages hold 16.
CASES = {
    "flash-llama3_1b-s2048": (
        "flash", dict(h=32, kv=8, d=64, s=2048), FLASH_KERNELS),
    "flash-llama3_1b-s8192-window-segments": (
        "flash", dict(h=32, kv=8, d=64, s=8192, window=4096, segments=True),
        FLASH_KERNELS),
    "flash-llama3_1b-explicit-1024-tiles": (
        "flash", dict(h=32, kv=8, d=64, s=2048, block=1024),
        FLASH_KERNELS),
    "flash-llama3_8b-d128-segments": (
        "flash", dict(h=32, kv=8, d=128, s=2048, segments=True),
        FLASH_KERNELS),
    "flash-mistral_7b-d128-window": (
        "flash", dict(h=32, kv=8, d=128, s=8192, window=4096), FLASH_KERNELS),
    "flash-gemma_2b-d256-mqa": (
        "flash", dict(h=8, kv=1, d=256, s=2048), FLASH_KERNELS),
    # The shapes the benchmark's cells send through the kernels with the
    # tiles a default call takes (ops/flash.py auto_blocks): the train
    # cell's three kernels (3 rows of 4,096, 32 / 8 heads of 128), and
    # the forward alone, which is all a prefill traces, at SmallThinker's
    # longest prompt (28 / 4 heads, full and under the window), Qwen3-
    # Next's and Kimi's head size 256 (a 16,384-token document there)
    # and LFM2's 64, and at a prompt that is one block of 320. The fit
    # in VMEM is the compiler's word, not `_tile_bytes`' estimate.
    "flash-mistral7b-train-cell-3x4096": (
        "flash", dict(h=32, kv=8, d=128, s=4096, b=3), FLASH_KERNELS),
    "flash-fwd-smallthinker-cell-12288-full": (
        "flash", dict(h=28, kv=4, d=128, s=12288, b=1, grad=False),
        {"flash_fwd": 1}),
    "flash-fwd-smallthinker-cell-12288-window": (
        "flash", dict(h=28, kv=4, d=128, s=12288, b=1, window=4096,
                      grad=False), {"flash_fwd": 1}),
    "flash-fwd-qwen3_next-cell-d256": (
        "flash", dict(h=16, kv=2, d=256, s=1024, b=1, grad=False),
        {"flash_fwd": 1}),
    "flash-fwd-kimi_k2-cell-16384-d256": (
        "flash", dict(h=64, kv=64, d=256, s=16384, b=1, grad=False),
        {"flash_fwd": 1}),
    "flash-fwd-lfm2-cell-d64": (
        "flash", dict(h=32, kv=8, d=64, s=1024, b=1, grad=False),
        {"flash_fwd": 1}),
    "flash-fwd-one-block-of-320": (
        "flash", dict(h=32, kv=8, d=128, s=320, b=1, grad=False),
        {"flash_fwd": 1}),
    "flash-in-fsdp4-mesh": (
        "flash", dict(h=32, kv=8, d=64, s=2048, b=8, mesh={"fsdp": 4}),
        FLASH_KERNELS),
    "flash-in-dp2-tp2-mesh": (
        "flash", dict(h=32, kv=8, d=64, s=2048, b=8,
                      mesh={"dp": 2, "tp": 2}), FLASH_KERNELS),
    "paged-llama3_1b-kv8-d64": (
        "paged", dict(h=32, kv=8, d=64), PAGED_KERNELS),
    "paged-llama3_8b-mistral_7b-kv8-d128": (
        "paged", dict(h=32, kv=8, d=128), PAGED_KERNELS),
    "paged-gemma_2b-kv1-d256": (
        "paged", dict(h=8, kv=1, d=256), PAGED_KERNELS),
    "paged-in-tp4-mesh": (
        "paged", dict(h=32, kv=8, d=64, mesh={"tp": 4}), PAGED_KERNELS),
    "paged-mistral_7b-d128-in-tp4-mesh": (
        "paged", dict(h=32, kv=8, d=128, mesh={"tp": 4}), PAGED_KERNELS),
    # The engines the benchmark serves (benchmark/configs/*.json `serve`).
    "paged-mistral7b-cells-16x4096-kv8-d128": (
        "paged", dict(h=32, kv=8, d=128, slots=16, max_len=4096,
                      n_pages=3073), PAGED_KERNELS),
    "paged-lfm2-cell-32x2048-kv8-d64": (
        "paged", dict(h=32, kv=8, d=64, slots=32, max_len=2048,
                      n_pages=3073), PAGED_KERNELS),
    # The Mamba-2 / attention / latent-expert family's two serving
    # programs at a small size with the published head sizes (128; state
    # 64 x 128): the decode step over pages and rows holds the paged
    # kernel, the prefill the grouped-matmul kernel (two a held-expert
    # layer, ops/grouped_matmul.py) and no ``ragged-dot`` of the
    # compiler's beside it.
    "nemotron_h-decode-step-pages-and-rows": (
        "nemotron_h", dict(program="decode", n_pages=8193), PAGED_KERNELS),
    "nemotron_h-prefill-sorted-dispatch": (
        "nemotron_h", dict(program="prefill", n_pages=8193),
        {"grouped_matmul": 2}),
    # The Gated DeltaNet / gated attention / routed SwiGLU family's two
    # serving programs at a small size with the published head sizes
    # (attention 256, a quarter of it turned; state 128 x 128): the
    # streamed paged kernel at head size 256 and the three delta layers'
    # update kernel (ops/gdn_update.py, over the leaf in place:
    # STATE_LEAVES) in the decode step, three
    # grouped matmuls an expert block and the chunked form's triangular
    # solve in the prefill (whose last layer's expert block feeds
    # nothing the program returns, K, V and the rows' state, and is not
    # compiled: three blocks of the four).
    "qwen3_next-decode-step-pages-and-rows": (
        "qwen3_next", dict(program="decode"),
        {**PAGED_KERNELS, "gdn_update": 3}),
    "qwen3_next-prefill-sorted-dispatch": (
        "qwen3_next", dict(program="prefill", n_pages=20481),
        {"grouped_matmul": 9}),
    # The two other decode programs of the benchmark's engines, whole
    # (one kernel in the text is one a layer: llama's layers are a scan).
    "llama-decode-step-mistral7b-cells": ("llama_decode", {}, PAGED_KERNELS),
    "lfm2-decode-step-lfm2-cell": ("lfm2_decode", {}, PAGED_KERNELS),
    # The same engine's whole-prompt prefill with its insert (383
    # tokens: the cells' median prompt), for PROJECTION_SLICES and
    # POOL_LIMITS, and its suffix prefill (a 128-token bucket behind
    # 128 matched pages, where it starts and how much of it is real
    # traced: the shared-prefix cell's program), for POOL_LIMITS.
    "llama-prefill-mistral7b-cells": ("llama_prefill", {}, {}),
    "llama-suffix-mistral7b-cells": (
        "llama_prefill", dict(prompt=128, n_pref=128), {}),
    # POOL_LIMITS' control (TOKEN_WISE): the same prefill with its K and
    # V written token by token (`_token_wise_insert`).
    "llama-prefill-mistral7b-cells-token-wise": (
        "llama_prefill", dict(token_wise=True), {}),
    # The windowed form of the streamed kernel at the SmallThinker
    # cell's own shapes (28 query heads on 4 KV heads of 128, 32 slots
    # of 16,384 tokens, the window space's 32 x 257 + 1 pages), named so
    # that no reader of `paged_decode` takes it for a full layer's call.
    "window-smallthinker-cell-32x16384-kv4-d128": (
        "paged", dict(h=28, kv=4, d=128, slots=32, max_len=16384,
                      n_pages=8225, window=4096, layers=6),
        {"window_decode": 1}),
    # The window / full attention / routed ReGLU family's two serving
    # programs, one period (G W W W) at the published head sizes and a
    # quarter of the widths: the decode step over both page spaces holds
    # one `paged_decode` (the full layer) and three `window_decode`, the
    # prefill a flash kernel and three grouped matmuls a layer but the
    # last, whose attention and expert block feed nothing the program
    # returns (its K and V do); neither moves a pool (POOL_LIMITS: both
    # spaces' pools).
    "smallthinker-decode-step-two-page-spaces": (
        "smallthinker", dict(program="decode"),
        {"paged_decode": 1, "window_decode": 3}),
    "smallthinker-prefill-page-writes": (
        "smallthinker", dict(program="prefill"),
        {"flash_fwd": 3, "grouped_matmul": 9}),
    # Latent attention's decode kernel (ops/mla_decode.py) at the Kimi
    # cell's own shapes: 64 absorbed queries of 640 (a latent of 576
    # padded to whole lane tiles) a row, 64 slots of 18,432 tokens, five
    # layers' pools of 32,769 pages stacked.
    "mla-kimi_k2-cell-64x18432-h64-w640": (
        "mla", dict(h=64, w=640, c=512, slots=64, max_len=18432,
                    n_pages=32769, layers=5), {"mla_decode": 1}),
    # The latent-attention / sigmoid-routed family's serving programs,
    # a dense layer and an expert layer at the published head and latent
    # widths and a quarter of its experts: the decode step holds one
    # `mla_decode` a layer and no per-head K or V of the table's length
    # (PER_HEAD_KV), the suffix prefill behind 64 cached pages the
    # grouped matmuls of its one expert block that feeds a latent (the
    # last layer's feeds nothing the program returns); neither moves
    # the pool (POOL_LIMITS).
    "kimi_k2-decode-step-latent-pages": (
        "kimi_k2", dict(program="decode"), {"mla_decode": 2}),
    "kimi_k2-suffix-behind-cached-latents": (
        "kimi_k2", dict(program="suffix"), {}),
    # PROJECTION_SLICES' controls: the same programs over the tree with
    # every projection ``[D, N]`` as ``family.init`` draws it.
    "llama-decode-step-mistral7b-cells-plain-tree": (
        "llama_decode", dict(held=False), PAGED_KERNELS),
    "llama-prefill-mistral7b-cells-plain-tree": (
        "llama_prefill", dict(held=False), {}),
    "qwen3_next-decode-step-pages-and-rows-plain-tree": (
        "qwen3_next", dict(program="decode", held=False),
        {**PAGED_KERNELS, "gdn_update": 3}),
    "smallthinker-decode-step-two-page-spaces-plain-tree": (
        "smallthinker", dict(program="decode", held=False),
        {"paged_decode": 1, "window_decode": 3}),
}

# What a decode or prefill program may do to the page pool: name -> (one
# layer's pool [P, KV, page, Hd], the most instructions that may give a
# layer's pool or the whole stack as their result, the most bytes of
# temporaries). Where the head size fills the lanes, updating the pool
# in place (an aliasing fusion) and reading it (the kernel, whose result
# is a row's; a prefill's gather of the pages it merges into) are the
# whole of it: before the pool rode the layer walk and was written by
# whole pages, the Mistral decode program held 11 such instructions a
# layer and 2.06 GiB of temporaries, and the small Nemotron one 7;
# before a prefill wrote by whole pages (`llama.paged_write_span`) the
# Mistral prefill copied each pool into the token scatter's order and
# back, 0.77 GiB of temporaries (TOKEN_WISE). A pool of head size 64
# arrives in the compiler's own layout and is copied into the kernel's
# and back whatever the walk does (ROADMAP S1): 6 such instructions,
# held to the 9 there were.
POOL_LIMITS = {
    "llama-decode-step-mistral7b-cells": ((3073, 8, 16, 128), 0, 64 << 20),
    "llama-prefill-mistral7b-cells": ((3073, 8, 16, 128), 0, 64 << 20),
    "llama-suffix-mistral7b-cells": ((3073, 8, 16, 128), 0, 64 << 20),
    # The pools at the benchmark's sizes: one of 2 MB (257 pages) the
    # compiler stages through fast memory, a copy each way.
    "nemotron_h-decode-step-pages-and-rows": ((8193, 2, 16, 128), 0, None),
    "nemotron_h-prefill-sorted-dispatch": ((8193, 2, 16, 128), 0, None),
    "qwen3_next-prefill-sorted-dispatch": ((20481, 2, 16, 256), 0, None),
    "lfm2-decode-step-lfm2-cell": ((3073, 8, 16, 64), 9, None),
    # Both spaces' pools (`_compile_smallthinker` gives them one size):
    # the decode step writes a page a row, the prefill the prompt's
    # pages (`llama.paged_write_pages`), each where it lies.
    "smallthinker-decode-step-two-page-spaces": ((2057, 4, 16, 128), 0, None),
    "smallthinker-prefill-page-writes": ((2057, 4, 16, 128), 0, None),
    # One leaf of latents, a page a row a decode step and the tail's
    # pages a suffix prefill, each where it lies.
    "kimi_k2-decode-step-latent-pages": ((2049, 1, 16, 640), 0, None),
    "kimi_k2-suffix-behind-cached-latents": ((2049, 1, 16, 640), 0, None),
}

# A latent-attention decode program reads its cache as latents: no
# tensor of the block table's length (8 slots x 2,048 positions) with a
# head dimension beside it, which is what an up-projected K or V of the
# prefix would be (name -> a pattern no instruction's type may match).
PER_HEAD_KV = {
    "kimi_k2-decode-step-latent-pages":
        r"\[8,(2048,8,(192|128|64)|8,2048,(192|128|64))\]",
}


# The control of a case above: the case of this suffix beside it, whose
# program writes token by token, has to show instructions of the same
# pool's size, so that a case cannot pass by looking for the wrong type.
TOKEN_WISE = "-token-wise"


# The projections a server holds ``[N, D]`` (each family's
# ``HELD_TRANSPOSED``; ``models/common.py served_params``): name -> the
# types, by the case's own widths, of one layer's slice of each, as the
# walk's ``[1, D, N]`` slice of a scanned stack or as the ``[N, D]`` a
# static plan's dot takes. The chip's compiler folds the split into
# heads into the projection's dot and wants the contracted dimension
# minor in the weight: over a tree that holds it ``[D, N]`` every decode
# and prefill program copies each layer's slice transposed (0.29 ms a
# step for Mistral's ``wq``), over the served tree none does. No
# ``copy`` instruction's result may have one of these types; the
# ``-plain-tree`` case of the same program has to show them, so that a
# case cannot pass by looking for the wrong type. (The slices the
# compiler brings into fast memory with ``copy-start`` / ``copy-done``
# are the weight's one read and stay.)
PROJECTION_SLICES = {
    # wq [4096, 32 x 128]; wk, wv [4096, 8 x 128].
    "llama-decode-step-mistral7b-cells": (
        "bf16[1,4096,4096]", "bf16[1,4096,1024]"),
    "llama-prefill-mistral7b-cells": (
        "bf16[1,4096,4096]", "bf16[1,4096,1024]"),
    # w_qkvz [512, 2 x 16 x 128 + 2 x 32 x 128]; wq [512, 2 x 4 x 256]
    # (query and gate); wk, wv [512, 2 x 256].
    "qwen3_next-decode-step-pages-and-rows": (
        "bf16[12288,512]", "bf16[2048,512]", "bf16[512,512]"),
    # wq [512, 8 x 128]; wk, wv [512, 4 x 128].
    "smallthinker-decode-step-two-page-spaces": (
        "bf16[1024,512]", "bf16[512,512]"),
}
PLAIN_TREE = "-plain-tree"


# The per-row state a decode or prefill program updates: name -> the
# whole leaf's type. No instruction may copy it (a step reads and
# writes each row's state where it lies: the delta layers' kernel takes
# the leaf as an aliased operand, a prefill's `models/plan.py
# set_row` is a `dynamic_update_slice` the compiler aliases).
# The leaf is held at the benchmark's rows and heads (128 x 32 x 128 x
# 128 a layer: 268 MB): a leaf of a few megabytes the compiler stages
# whole through fast memory, a copy each way, which says nothing.
STATE_LEAVES = {
    "qwen3_next-decode-step-pages-and-rows": "f32[3,128,32,128,128]",
    "qwen3_next-prefill-sorted-dispatch": "f32[3,128,32,128,128]",
}


# ------------------------------------------------------------------ child
def _described_mesh(topo, axes):
    from polyaxon_tpu.parallel import build_mesh

    n = 1
    for size in (axes or {}).values():
        n *= size
    return build_mesh(axes=dict(axes or {"dp": 1}),
                      devices=list(topo.devices)[:n])


def _compile_flash(topo, h, kv, d, s, b=2, window=None, segments=False,
                   block=None, mesh=None, grad=True):
    """Forward AND backward (the custom-vjp Pallas kernels) of one flash
    call (``grad`` False: the forward alone, what a prefill traces),
    under `mesh` when given, batch and heads sharded as the train step
    shards them; ``block``: explicit tiles in place of the rule's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyaxon_tpu.ops import flash

    mesh = _described_mesh(topo, mesh)
    batch = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    heads = "tp" if mesh.shape.get("tp", 1) > 1 else None

    def aval(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    bshd = P(batch or None, None, heads, None)
    args = [aval((b, s, h, d), jnp.bfloat16, bshd),
            aval((b, s, kv, d), jnp.bfloat16, bshd),
            aval((b, s, kv, d), jnp.bfloat16, bshd)]
    if segments:
        args.append(aval((b, s), jnp.int32, P(batch or None, None)))
    tiles = dict(block_q=block, block_k=block) if block else {}

    def loss(q, k, v, *seg):
        out = flash.flash_attention(
            q, k, v, causal=True, window=window, interpret=False,
            segment_ids=seg[0] if seg else None, **tiles)
        return jnp.sum(out.astype(jnp.float32))

    with mesh:
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2)) if grad
                       else loss).lower(*args).compile()


def _compile_paged(topo, h, kv, d, page=16, slots=8, max_len=8192,
                   n_pages=None, mesh=None, layers=2, window=None):
    """The kernel alone, told the layer (traced) of a stacked pool."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyaxon_tpu.ops.paged_attention import paged_decode_attention

    mesh = _described_mesh(topo, mesh)
    heads = "tp" if mesh.shape.get("tp", 1) > 1 else None

    def aval(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    maxp = max_len // page
    pool = aval((layers, n_pages or slots * maxp + 1, kv, page, d),
                jnp.bfloat16, P(None, None, heads, None, None))
    with mesh:
        return jax.jit(
            lambda *a: paged_decode_attention(
                *a, window=window, interpret=False)).lower(
                aval((slots, h, d), jnp.bfloat16, P(None, heads, None)),
                pool, pool, aval((), jnp.int32),
                aval((slots, maxp), jnp.int32),
                aval((slots,), jnp.int32)).compile()


def _engine_avals(topo, family, cfg, slots, n_pages, page, held=True):
    """(params, cache, i32) as the engine holds them
    (serving/batching.py), as shapes on the first described chip:
    served weights (``held`` False: every projection ``[D, N]``, the
    tree before a server held any ``[N, D]``), the paged cache with the
    family's per-row leaves, and a maker of int32 arguments."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from polyaxon_tpu.models.common import served_params

    one = SingleDeviceSharding(topo.devices[0])

    def avals(build):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            jax.eval_shape(build))

    def build_cache():
        cache = family.paged_init_cache(cfg, n_pages, page)
        if hasattr(family, "paged_init_rows"):
            cache["rows"] = family.paged_init_rows(cfg, slots)
        return cache

    params = avals(lambda: served_params(
        family.init(cfg, jax.random.key(0))["params"], cfg.dtype,
        family.READ_AT_FLOAT32, family.HELD_TRANSPOSED if held else ()))
    return params, avals(build_cache), lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one)


@contextlib.contextmanager
def _kernel_path():
    """The family code asks `jax.default_backend()` which attention to
    run: answer for the chip while the program is traced."""
    import jax

    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real_backend


def _compile_decode_step(topo, family, cfg, slots, max_len, n_pages,
                         page=16, held=True):
    """`family.decode_step_paged` as the engine builds it: the cache
    donated."""
    import jax

    params, cache, i32 = _engine_avals(topo, family, cfg, slots, n_pages,
                                       page, held)

    def decode_step(params, cache, tokens, pos, tables):
        return family.decode_step_paged(cfg, params, cache, tokens, pos,
                                        tables)

    with _kernel_path():
        return jax.jit(decode_step, donate_argnums=(1,)).lower(
            params, cache, i32(slots), i32(slots),
            i32(slots, max_len // page)).compile()


def _mistral_cells_cfg():
    """The benchmark's Mistral-7B engine: 8 of its layers (16 slots of
    4,096 tokens over 3,073 pages)."""
    import dataclasses

    import jax.numpy as jnp

    from polyaxon_tpu.models import llama

    return dataclasses.replace(
        llama.CONFIGS["mistral_7b"], n_layers=8, vocab_size=32768,
        sliding_window=None, dtype=jnp.bfloat16,
        paged_attention_impl="pallas")


def _compile_llama_decode(topo, held=True):
    from polyaxon_tpu.models import llama

    return _compile_decode_step(topo, llama, _mistral_cells_cfg(), slots=16,
                                max_len=4096, n_pages=3073, held=held)


def _token_wise_insert(cache, k_all, v_all, page_ids, page):
    """The whole-prompt insert there was before a prefill wrote by whole
    pages: every token scattered to its (page, offset)."""
    import jax.numpy as jnp

    t = jnp.arange(k_all.shape[1])
    pidx = jnp.maximum(page_ids[t // page], 0)
    return {name: cache[name].at[:, pidx, :, t % page].set(
        jnp.moveaxis(kv, 1, 0)) for name, kv in (("k", k_all), ("v", v_all))}


def _compile_llama_prefill(topo, held=True, prompt=383, max_len=4096,
                           page=16, n_pref=None, token_wise=False):
    """That engine's whole-prompt prefill with its insert, the cache
    donated (``token_wise``: with `_token_wise_insert` in its place);
    with ``n_pref``, its suffix prefill of a ``prompt``-token bucket
    behind that many matched pages (serving/batching.py
    `compiled_suffix_prefill`)."""
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import llama

    cfg = _mistral_cells_cfg()
    params, cache, i32 = _engine_avals(topo, llama, cfg, 16, 3073, page,
                                       held)
    insert = _token_wise_insert if token_wise else llama.paged_insert_prefill

    def prefill(params, tokens, cache, page_ids):
        return insert(
            cache, *llama.paged_prefill_kv(cfg, params, tokens), page_ids,
            page)

    def suffix(params, tokens, cache, page_ids, m, real_len):
        pref = jnp.maximum(page_ids[:n_pref], 0)
        novel = llama.paged_prefill_suffix_kv(
            cfg, params, tokens, *llama.paged_gather_prefix(cache, pref), m)
        return llama.paged_insert_suffix(
            cache, *novel, page_ids, m, page, real_len)

    extent = () if n_pref is None else (i32(), i32())
    with _kernel_path():
        return jax.jit(prefill if n_pref is None else suffix,
                       donate_argnums=(2,)).lower(
            params, i32(1, prompt), cache, i32(max_len // page),
            *extent).compile()


def _compile_lfm2_decode(topo):
    """The benchmark's LFM2-8B-A1B engine: the dense convolution layer
    and one period behind it (one attention layer), 32 slots of 2,048."""
    import dataclasses

    import jax.numpy as jnp

    from polyaxon_tpu.models import lfm2

    cfg = dataclasses.replace(
        lfm2.CONFIGS["lfm2_8b_a1b"], n_layers=5, n_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        dtype=jnp.bfloat16, paged_attention_impl="pallas")
    return _compile_decode_step(topo, lfm2, cfg, slots=32, max_len=2048,
                                n_pages=3073)


def _compile_nemotron_h(topo, program, slots=8, max_len=512, page=16,
                        n_pages=257, prompt=127):
    """`decode_step_paged` or the whole-prompt prefill with its insert,
    as the engine builds them (serving/batching.py), for a one-period
    model of a quarter of its experts."""
    import dataclasses

    import jax

    from polyaxon_tpu.models import nemotron_h as nh

    cfg = dataclasses.replace(
        nh.CONFIGS["nemotron_h_tiny"], vocab_size=1024, dim=512,
        pattern="*EM", n_heads=4, n_kv_heads=2, head_dim=128, ssm_heads=16,
        ssm_head_dim=64, ssm_state=128, ssm_groups=8, chunk_size=128,
        n_experts=32, held_experts=(8, 8), experts_per_token=6,
        moe_latent_dim=256, moe_ffn_dim=384, shared_ffn_dim=512,
        paged_attention_impl="pallas")
    if program == "decode":
        return _compile_decode_step(topo, nh, cfg, slots, max_len, n_pages,
                                    page)
    params, cache, i32 = _engine_avals(topo, nh, cfg, slots, n_pages, page)

    def prefill(params, tokens, cache, page_ids, row):
        return nh.paged_insert_prefill(
            cache, *nh.paged_prefill_kv(cfg, params, tokens), page_ids,
            page, row)

    with _kernel_path():
        return jax.jit(prefill, donate_argnums=(2,)).lower(
            params, i32(1, prompt), cache, i32(max_len // page),
            i32()).compile()


def _compile_qwen3_next(topo, program, slots=128, max_len=512, page=16,
                        n_pages=257, prompt=127, held=True):
    """`decode_step_paged` or the whole-prompt prefill with its insert,
    as the engine builds them, for a one-period model of a quarter of
    its experts."""
    import dataclasses

    import jax

    from polyaxon_tpu.models import qwen3_next as qn

    cfg = dataclasses.replace(
        qn.CONFIGS["qwen3_next_tiny"], vocab_size=1024, dim=512, n_layers=4,
        n_heads=4, n_kv_heads=2, head_dim=256, gdn_key_heads=16,
        gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128, chunk_size=64,
        n_experts=32, held_experts=(8, 8), experts_per_token=4,
        moe_ffn_dim=256, shared_ffn_dim=256, paged_attention_impl="pallas")
    if program == "decode":
        return _compile_decode_step(topo, qn, cfg, slots, max_len, n_pages,
                                    page, held)
    params, cache, i32 = _engine_avals(topo, qn, cfg, slots, n_pages, page)

    def prefill(params, tokens, cache, page_ids, row):
        return qn.paged_insert_prefill(
            cache, *qn.paged_prefill_kv(cfg, params, tokens), page_ids,
            page, row)

    with _kernel_path():
        return jax.jit(prefill, donate_argnums=(2,)).lower(
            params, i32(1, prompt), cache, i32(max_len // page),
            i32()).compile()


def _compile_mla(topo, h, w, c, slots, max_len, n_pages, layers, page=16):
    """`mla_decode_attention` alone, the pool stacked over the layers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from polyaxon_tpu.ops.mla_decode import mla_decode_attention

    one = SingleDeviceSharding(topo.devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return jax.jit(lambda *a: mla_decode_attention(
        *a, scale=0.1, value_width=c, interpret=False)).lower(
        aval((slots, h, w), jnp.bfloat16),
        aval((layers, n_pages, 1, page, w), jnp.bfloat16),
        aval((), jnp.int32), aval((slots, max_len // page), jnp.int32),
        aval((slots,), jnp.int32)).compile()


def _compile_kimi_k2(topo, program, slots=8, max_len=2048, page=16,
                     n_pages=2049, bucket=128, n_pref=64):
    """`decode_step_paged`, or the suffix prefill of a ``bucket``-token
    tail behind ``n_pref`` matched pages with its insert, as the engine
    builds them: a dense and an expert layer at the published head and
    latent widths."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import kimi_k2 as k2

    cfg = dataclasses.replace(
        k2.CONFIGS["kimi_k2_6"], vocab_size=1024, dim=512, n_layers=2,
        n_heads=8, q_lora_rank=256, ffn_dim=1024, n_experts=32,
        held_experts=(8, 8), experts_per_token=4, moe_ffn_dim=256,
        max_seq_len=max_len, attention_impl="flash",
        paged_attention_impl="pallas")
    if program == "decode":
        return _compile_decode_step(topo, k2, cfg, slots, max_len, n_pages,
                                    page)
    params, cache, i32 = _engine_avals(topo, k2, cfg, slots, n_pages, page)

    def suffix(params, tokens, cache, page_ids, m, real_len):
        pref = jnp.maximum(page_ids[:n_pref], 0)
        novel = k2.paged_prefill_suffix_kv(
            cfg, params, tokens, *k2.paged_gather_prefix(cache, pref), m)
        return k2.paged_insert_suffix(cache, *novel, page_ids, m, page,
                                      real_len)

    with _kernel_path():
        return jax.jit(suffix, donate_argnums=(2,)).lower(
            params, i32(1, bucket), cache, i32(max_len // page), i32(),
            i32()).compile()


def _compile_smallthinker(topo, program, slots=8, max_len=8192, page=16,
                          prompt=4607, held=True):
    """`decode_step_paged` over both page spaces, or the whole-prompt
    prefill with its page-wise insert, as the engine builds them: one
    period at the published head sizes, the window 4,096, a prompt
    longer than it. Both spaces hold ``slots`` x 257 + 1 pages, so one
    shape finds either pool."""
    import dataclasses

    import jax

    from polyaxon_tpu.models import smallthinker as st

    cfg = dataclasses.replace(
        st.CONFIGS["smallthinker_tiny"], vocab_size=1024, dim=512,
        n_heads=8, n_kv_heads=4, head_dim=128, sliding_window=4096,
        n_experts=16, experts_per_token=4, moe_ffn_dim=256,
        max_seq_len=max_len, attention_impl="flash",
        paged_attention_impl="pallas")
    n_pages = slots * (cfg.sliding_window // page + 1) + 1
    class _TwoSpaces:
        """`st` with `paged_init_cache` told the window space's size,
        which `_engine_avals` does not know to pass."""

        def __getattr__(self, name):
            return getattr(st, name)

        @staticmethod
        def paged_init_cache(cfg, n, page_size):
            return st.paged_init_cache(cfg, n, page_size, n)

    family = _TwoSpaces()
    params, cache, i32 = _engine_avals(topo, family, cfg, slots, n_pages,
                                       page, held)
    maxp = max_len // page
    if program == "decode":
        def decode_step(params, cache, tokens, pos, full, window):
            return st.decode_step_paged(cfg, params, cache, tokens, pos,
                                        (full, window))

        with _kernel_path():
            return jax.jit(decode_step, donate_argnums=(1,)).lower(
                params, cache, i32(slots), i32(slots), i32(slots, maxp),
                i32(slots, maxp)).compile()

    def prefill(params, tokens, cache, page_ids):
        return st.paged_insert_prefill(
            cache, *st.paged_prefill_kv(cfg, params, tokens), page_ids, page)

    with _kernel_path():
        return jax.jit(prefill, donate_argnums=(2,)).lower(
            params, i32(1, prompt), cache, i32(2, maxp)).compile()


def _leaf_copies(text: str, leaf: str) -> list:
    """The instructions of a compiled program whose result is the whole
    leaf ``leaf`` (its type as the text prints it) and which copy it."""
    out = []
    for line in text.splitlines():
        head = line.strip().split(" = ")
        if len(head) < 2 or not head[1].startswith(leaf):
            continue
        if re.search(r"\bcopy(-start|-done)?\(|copy_fusion|kind=kCopy",
                     head[1]) or head[0].lstrip("%").startswith("copy"):
            out.append(line.strip()[:160])
    return out


def _copies_of(text: str, types: tuple) -> list:
    """The ``copy`` instructions of a compiled program whose result has
    one of ``types`` (whatever its layout): the instruction's name and
    its result as the text prints it."""
    found = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                       r"copy\(", text, re.MULTILINE)
    return [f"{name} {result}" for name, result in found if result in types]


def _pool_sized_instructions(text: str, pool_shape: tuple) -> list:
    """The instructions a compiled program runs whose result is a
    bfloat16 array ``[P, KV, page, Hd]`` or a stack of them, found by
    type: all but the ones that move nothing (a parameter, a tuple's
    element, a bitcast) and the fusions that update their operand in
    place (``aliasing_operands``). What is inside a fusion is not an
    instruction of its own."""
    dims = ",".join(str(n) for n in pool_shape)
    result = re.compile(r"^\s*(?:ROOT )?%(\S+) = bf16\[(?:\d+,)?"
                        + re.escape(dims) + r"\]\S* ([\w\-]+)\(")
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, inside = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
            continue
        hit = result.match(line)
        if not hit or inside in fused:
            continue
        name, op = hit.groups()
        in_place = op == "fusion" and re.search(
            r'"aliasing_operands":\{"lists":\[\{', line)
        if op not in ("parameter", "get-tuple-element",
                      "bitcast") and not in_place:
            found.append(f"{op} %{name}")
    return found


def _child_main() -> int:
    """Compile every case against the described topology; one JSON
    object on stdout. Never raises: a refusal is that case's entry."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Without it libtpu spends minutes asking a GCP metadata server
    # that is not there (perf/aot.py has the story).
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    from jax.experimental import topologies

    # A compile for a described device is written to the persistent
    # cache but cannot be read back without a chip; keep it off.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as exc:  # noqa: BLE001 — the module then skips
        print(json.dumps({"skip": f"{type(exc).__name__}: {exc}"[:300]}))
        return 0
    from polyaxon_tpu.perf.hlo import pallas_kernels

    report = {}
    for name, (kind, kwargs, _) in CASES.items():
        compile_case = {"flash": _compile_flash, "paged": _compile_paged,
                        "nemotron_h": _compile_nemotron_h,
                        "qwen3_next": _compile_qwen3_next,
                        "smallthinker": _compile_smallthinker,
                        "mla": _compile_mla, "kimi_k2": _compile_kimi_k2,
                        "llama_decode": _compile_llama_decode,
                        "llama_prefill": _compile_llama_prefill,
                        "lfm2_decode": _compile_lfm2_decode}[kind]
        t0 = time.time()
        try:
            compiled = compile_case(topo, **kwargs)
            text = compiled.as_text()
            kernels = pallas_kernels(text)
            # The compiler's own grouped matmul (what `sorted_dispatch`
            # ran on the chip before ops/grouped_matmul.py, and runs off
            # it) carries no pallas_call name: it is counted by its
            # custom call's, so a program that fell back to it fails
            # its case.
            grouped = len(re.findall(r"^\s*(?:ROOT )?%ragged-dot[\w.\-]* = "
                                     r"(?!\()", text, re.MULTILINE))
            if grouped:
                kernels["ragged-dot"] = grouped
            report[name] = {"ok": True, "kernels": kernels}
            if name in STATE_LEAVES:
                report[name]["state_copies"] = _leaf_copies(
                    text, STATE_LEAVES[name])
                report[name]["state_seen"] = STATE_LEAVES[name] in text
            held_case = name.removesuffix(PLAIN_TREE)
            if held_case in PROJECTION_SLICES:
                report[name]["projection_copies"] = _copies_of(
                    text, PROJECTION_SLICES[held_case])
                report[name]["temp_bytes"] = (
                    compiled.memory_analysis().temp_size_in_bytes)
            if name in PER_HEAD_KV:
                report[name]["per_head_kv"] = re.findall(
                    rf"\w+{PER_HEAD_KV[name]}", text)[:5]
            pool_case = name.removesuffix(TOKEN_WISE)
            if pool_case in POOL_LIMITS:
                report[name]["pool_sized"] = _pool_sized_instructions(
                    text, POOL_LIMITS[pool_case][0])
                report[name]["temp_bytes"] = (
                    compiled.memory_analysis().temp_size_in_bytes)
        except Exception as exc:  # noqa: BLE001 — the refusal IS the result
            report[name] = {"ok": False,
                            "error": f"{type(exc).__name__}: {exc}"[:600]}
        report[name]["seconds"] = round(time.time() - t0, 2)
    print(json.dumps({"device_kind": topo.devices[0].device_kind,
                      "cases": report}))
    return 0


# ------------------------------------------------------------------ tests
@pytest.fixture(scope="module")
def aot_report(tmp_path_factory):
    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent  # the directory every worker shares
    cached = base / "aot_tpu_compile.json"
    with open(base / "aot_tpu_compile.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when the file closes
        if not cached.exists():
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child"],
                capture_output=True, text=True, timeout=900,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            lines = [ln for ln in proc.stdout.splitlines()
                     if ln.startswith("{")]
            assert lines, (f"AOT child rc={proc.returncode} left no report: "
                           f"{proc.stderr[-2000:]}")
            cached.write_text(lines[-1])
        report = json.loads(cached.read_text())
    if "skip" in report:
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here: "
                    f"{report['skip']}")
    return report


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_described_tpu(aot_report, name):
    entry = aot_report["cases"][name]
    assert entry["ok"], f"the TPU compiler refused {name}: {entry['error']}"
    # The kernel itself, not a reference path that happens to compile.
    assert entry["kernels"] == CASES[name][2], entry


@pytest.mark.parametrize("name", sorted(PER_HEAD_KV))
def test_latent_decode_program_holds_no_per_head_kv(aot_report, name):
    """The absorbed form reads the latents and nothing made of them: no
    instruction of the decode program gives keys or values a head over
    the rows' whole tables."""
    assert aot_report["cases"][name].get("per_head_kv") == []


@pytest.mark.parametrize("name", sorted(POOL_LIMITS))
def test_program_leaves_the_pool_in_place(aot_report, name):
    """Only the kernel and in-place page writes touch the pool in a
    decode program, only the reads of pages and in-place page writes in
    a prefill program (models/llama.py, the paged surface's comment);
    the same prefill writing token by token copies the pool whole (the
    control)."""
    entry = aot_report["cases"][name]
    assert entry["ok"], entry
    _, most, temp_bytes = POOL_LIMITS[name]
    assert len(entry["pool_sized"]) <= most, entry["pool_sized"]
    if temp_bytes is not None:
        assert entry["temp_bytes"] < temp_bytes, entry["temp_bytes"]
    if name + TOKEN_WISE in CASES:
        control = aot_report["cases"][name + TOKEN_WISE]
        assert control["ok"], control
        assert len(control["pool_sized"]) > most, control
        assert control["temp_bytes"] > temp_bytes, control["temp_bytes"]


@pytest.mark.parametrize("name", sorted(STATE_LEAVES))
def test_program_updates_the_rows_state_where_it_lies(aot_report, name):
    """No instruction copies the whole leaf of per-row states: a decode
    step updates a layer's rows in place, a prefill its row."""
    entry = aot_report["cases"][name]
    assert entry["ok"], entry
    assert entry["state_seen"], "the program does not hold the leaf"
    assert entry["state_copies"] == [], entry["state_copies"]


@pytest.mark.parametrize("name", sorted(PROJECTION_SLICES))
def test_no_program_copies_a_projection_transposed(aot_report, name):
    """Over the served tree no `copy` gives a layer's slice of a
    head-split projection; over the plain tree the same program does
    (the control), and keeps no fewer bytes of temporaries."""
    entry = aot_report["cases"][name]
    control = aot_report["cases"][name + PLAIN_TREE]
    assert entry["ok"] and control["ok"], (entry, control)
    assert entry["projection_copies"] == [], entry["projection_copies"]
    seen = {copy.split(" ")[1] for copy in control["projection_copies"]}
    assert seen == set(PROJECTION_SLICES[name]), control["projection_copies"]
    assert entry["temp_bytes"] <= control["temp_bytes"], (
        entry["temp_bytes"], control["temp_bytes"])


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(_child_main())
