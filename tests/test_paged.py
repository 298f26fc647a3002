"""Paged-KV serving: block-table decode parity against the dense ring
cache, page-pool allocator semantics, and engine-level behavior under
oversubscription (net-new surface — the reference orchestrator has no
serving path; held to this repo's own bar, VERDICT r2 missing #6)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import llama
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
from polyaxon_tpu.serving.paged import PagePool


def _cfg():
    return dataclasses.replace(llama.CONFIGS["llama_tiny"],
                               dtype=jnp.float32)


# Shapes for `test_kernel_matches_gather_reference`. `pos` is each
# row's position (-1 = idle); a row holds the pages its position needs,
# ids dealt out of order, but for `holes` (row, table entry) left at -1.
# A head size that fills the lanes (128, 256) takes the kernel's
# streamed form, 16 pages a turn; any other the pipelined form, 8 pages
# a step (fewer for a narrower table). With pages of 4 that is 64 and
# 32 tokens; with the serving page of 16, 256 and 128.
_KERNEL_CASES = {
    "ragged-hole-idle-gqa": dict(
        kv=2, rep=2, hd=16, page=4, maxp=4, pos=[6, 2, -1],
        tables=[[5, 2, -1, -1], [1, -1, -1, -1], [-1, -1, -1, -1]]),
    "hd64-kv8-rep4-bf16": dict(
        kv=8, rep=4, hd=64, page=16, maxp=16, dtype=jnp.bfloat16,
        pos=[100, 255, 0, 143]),
    "hd128-kv8-rep4-bf16": dict(
        kv=8, rep=4, hd=128, page=16, maxp=32, dtype=jnp.bfloat16,
        pos=[37, 300, -1, 511]),
    "hd128-kv2-rep4": dict(
        kv=2, rep=4, hd=128, page=4, maxp=16, pos=[33, 5, 62]),
    "hd256-kv1-rep1": dict(
        kv=1, rep=1, hd=256, page=4, maxp=8, pos=[9, 30]),
    "hd256-kv1-rep8-bf16": dict(
        kv=1, rep=8, hd=256, page=16, maxp=8, dtype=jnp.bfloat16,
        pos=[77, 16]),
    "hd64-kv2-rep1": dict(
        kv=2, rep=1, hd=64, page=4, maxp=16, pos=[40, 3]),
    "hd64-kv8-rep1": dict(
        kv=8, rep=1, hd=64, page=4, maxp=8, pos=[21, 12, -1]),
    "hd128-kv8-rep1": dict(
        kv=8, rep=1, hd=128, page=4, maxp=8, pos=[21, 12, -1]),
    # Neither the page nor the step a power of two: the index
    # arithmetic divides where it otherwise shifts.
    "hd64-page-of-3-table-of-6": dict(
        kv=2, rep=2, hd=64, page=3, maxp=6, pos=[16, 7, -1, 2]),
    "hd128-page-of-3-table-of-6": dict(
        kv=2, rep=2, hd=128, page=3, maxp=6, pos=[16, 7, -1, 2]),
}
# What the walk over the live pages has to get right, in both forms.
_KERNEL_WALKS = {
    "length-not-a-multiple-of-a-step": dict(
        maxp=48, pos=[44, 69, 134, 100]),
    "length-ends-on-a-page-boundary": dict(
        maxp=48, pos=[3, 31, 63, 127, 67]),
    "row-fills-the-whole-table": dict(maxp=32, pos=[127, 127, 10]),
    "single-token-rows": dict(maxp=32, pos=[0, 0, 17]),
    "idle-rows-between-live-ones": dict(
        maxp=32, pos=[-1, 37, -1, -1, 5, -1, 90]),
    "all-rows-idle": dict(maxp=32, pos=[-1, -1]),
    "hole-inside-a-live-range": dict(
        maxp=32, pos=[50, 22, 125, 80],
        holes=[(0, 3), (0, 9), (1, 0), (2, 31), (2, 16), (3, 17)]),
    "table-width-not-divisible-by-a-step": dict(
        maxp=20, pos=[47, 20, 79]),
    "table-width-7-narrower-than-a-step": dict(maxp=7, pos=[27, 9, -1]),
    "table-width-1": dict(maxp=1, pos=[3, 0]),
    "32-rows": dict(
        maxp=32, pos=[(7 * b) % 128 if b % 5 else -1 for b in range(32)]),
}
_KERNEL_CASES.update({
    f"{form}-{walk}": dict(kv=2, rep=4, hd=hd, page=4, **spec)
    for walk, spec in _KERNEL_WALKS.items()
    for form, hd in (("pipelined-hd64", 64), ("streamed-hd128", 128))})


def _kernel_tables(spec) -> jax.Array:
    if "tables" in spec:
        return jnp.asarray(spec["tables"], jnp.int32)
    maxp, page = spec["maxp"], spec["page"]
    tables = np.full((len(spec["pos"]), maxp), -1, np.int32)
    ids = iter(np.random.default_rng(0).permutation(tables.size) + 1)
    for b, pos in enumerate(spec["pos"]):
        for p in range(pos // page + 1 if pos >= 0 else 0):
            tables[b, p] = next(ids)
    for b, p in spec.get("holes", ()):
        tables[b, p] = -1
    return jnp.asarray(tables)


# Spans for `test_span_write_equals_token_scatter`, over pages of 4:
# where the span starts (``plain``: the Python 0 a whole-prompt insert
# passes; else traced), how long ``kv`` is, how much of it is real
# (None: `real_len` absent) and the row's block-table row (-1 = not
# allocated).
_SPAN_TABLE = [7, 3, 12, 5, 9, 14, 2, 11] + list(range(15, 33)) + [-1] * 4
_SPAN_CASES = {
    "whole-prompt-shorter-than-a-page": dict(start=0, plain=True, S=3),
    "whole-prompt-to-a-pages-edge": dict(start=0, plain=True, S=4),
    "whole-prompt-3-pages": dict(start=0, plain=True, S=11),
    "whole-prompt-25-pages": dict(start=0, plain=True, S=98),
    "traced-0-one-page": dict(start=0, S=4),
    "page-aligned-start": dict(start=8, S=7),
    "inside-a-page-and-short-of-its-end": dict(start=5, S=2),
    "inside-a-page-to-its-edge": dict(start=6, S=6),
    "inside-a-page-3-pages": dict(start=5, S=9),
    "inside-a-page-25-pages": dict(start=3, S=97),
    "bucket-padding-goes-nowhere": dict(start=5, S=8, real=3),
    "bucket-padding-page-aligned": dict(start=8, S=16, real=9),
    "whole-bucket-real": dict(start=2, S=8, real=8),
    # The padded tail reaches positions 9 .. 24 of a table that ends at
    # 16: what lies past it is scratch, not the table's last page.
    "padded-tail-past-the-table": dict(
        start=9, S=16, real=5, table=[7, 3, 12, 5]),
    "real-tail-ends-with-the-table": dict(
        start=9, S=16, real=7, table=[7, 3, 12, 5]),
    # Real positions on a page never allocated land on scratch page 0.
    "unallocated-page-inside-the-span": dict(
        start=2, S=9, table=[7, -1, 12, -1]),
}


class TestPagedDecodeParity:
    def test_matches_dense_ragged_step_by_step(self):
        """A row whose pages cover 0..p must produce the dense ragged
        step's logits at p exactly — including an idle row, non-trivial
        block-table order, and growth across a page boundary."""
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        max_len, page = 32, 4
        prompt = jax.random.randint(jax.random.key(1), (1, 7), 0,
                                    cfg.vocab_size)

        # Dense reference: slot 0 live, slot 1 idle.
        dense = llama.cb_init_cache(cfg, 2, max_len)
        row = llama.cb_prefill(cfg, params, prompt[:, :-1], max_len)
        dense = llama.insert_cache_row(dense, row, jnp.int32(0))

        # Paged: same row through the paged surface, with deliberately
        # non-contiguous page ids (allocation order must not matter).
        pool_pages = 8
        paged = llama.paged_init_cache(cfg, pool_pages, page)
        tables = np.full((2, max_len // page), -1, np.int32)
        tables[0, :2] = [5, 2]  # positions 0..7 → pages 5 then 2
        k_all, v_all = llama.paged_prefill_kv(cfg, params, prompt[:, :-1])
        paged = llama.paged_insert_prefill(
            paged, k_all, v_all, jnp.asarray(tables[0]), page)

        cur = jnp.asarray([int(prompt[0, -1]), 0], jnp.int32)
        pos = np.array([prompt.shape[1] - 1, -1], np.int32)
        for step_i in range(6):  # crosses the pos=8 page boundary
            want, dense = llama.decode_step_ragged(
                cfg, params, dense, cur, jnp.asarray(pos))
            got, paged = llama.decode_step_paged(
                cfg, params, paged, cur, jnp.asarray(pos),
                jnp.asarray(tables))
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(want[0]),
                                       atol=2e-4, rtol=2e-4)
            assert np.isfinite(np.asarray(got[1])).all()  # idle row
            nxt = int(jnp.argmax(want[0]))
            cur = jnp.asarray([nxt, 0], jnp.int32)
            pos[0] += 1
            if pos[0] // page >= 2 and tables[0, pos[0] // page] < 0:
                tables[0, pos[0] // page] = 6  # grow into a fresh page

    def test_refuses_sliding_window(self):
        cfg = dataclasses.replace(_cfg(), sliding_window=8)
        with pytest.raises(ValueError, match="sliding_window"):
            llama.paged_init_cache(cfg, 4, 4)


class TestPagePool:
    def test_admit_grow_release_accounting(self):
        pool = PagePool(slots=2, max_len=16, page_size=4, n_pages=5)
        assert pool.free_pages == 4  # page 0 is scratch
        assert pool.admit(0, 5)  # positions 0..4 → 2 pages
        assert pool.free_pages == 2
        assert (pool.tables[0, :2] >= 1).all() and pool.tables[0, 2] == -1
        assert pool.ensure(0, 5)  # already covered
        assert pool.free_pages == 2
        assert pool.ensure(0, 8)  # new page
        assert pool.free_pages == 1
        assert pool.admit(1, 4)  # exactly the last page
        assert not pool.ensure(1, 4)  # pool dry
        pool.release(0)
        assert pool.free_pages == 3
        assert (pool.tables[0] == -1).all()
        assert pool.ensure(1, 4)  # freed pages are reusable

    def test_admit_all_or_nothing(self):
        pool = PagePool(slots=1, max_len=16, page_size=4, n_pages=3)
        assert not pool.admit(0, 12)  # needs 3, has 2 — nothing taken
        assert pool.free_pages == 2
        assert (pool.tables[0] == -1).all()

    def test_match_nothing_keeps_no_retired_page_and_matches_no_prompt(self):
        """For a cache with per-row leaves (a recurrent state a row,
        `page_bytes`' third number): a matched prefix would have no
        state to resume from."""
        prompt = list(range(1, 14))
        shared = PagePool(slots=2, max_len=16, page_size=4, n_pages=9)
        assert shared.admit(0, len(prompt), prompt).matched_tokens == 0
        shared.commit_prefix(0)
        assert shared.peek_matched_tokens(len(prompt), prompt) == 12
        pool = PagePool(slots=2, max_len=16, page_size=4, n_pages=9)
        pool.match_nothing()
        assert pool.admit(0, len(prompt), prompt).matched_tokens == 0
        pool.commit_prefix(0)
        assert pool.peek_matched_tokens(len(prompt), prompt) == 0
        again = pool.admit(1, len(prompt), prompt)
        assert (again.matched_tokens, again.matched_pages, again.cow) == (
            0, 0, None)
        assert pool.free_pages == 8 - 2 * 4
        pool.release(0)
        pool.release(1)
        assert pool.free_pages == 8 and pool.radix_stats()["pages"] == 0
        assert pool.check_invariants() == []
        with pytest.raises(AssertionError, match="before match_nothing"):
            shared.match_nothing()          # only before any admission

    def test_dense_equivalent_sizing(self):
        pool = PagePool.dense_equivalent(slots=4, max_len=32, page_size=8)
        assert pool.n_pages == 4 * 4 + 1
        for s in range(4):  # every slot can hold a full-length row
            assert pool.admit(s, 32)
        assert pool.free_pages == 0


class TestPagedEngine:
    def _params(self, cfg):
        return llama.init(cfg, jax.random.key(0))["params"]

    @pytest.mark.parametrize("page_size", [1, 4])
    def test_matches_dense_engine_greedy(self, page_size):
        """Paged and dense engines share every step above the cache
        layout, so greedy decode must agree token-for-token — mixed
        prompt lengths, more requests than slots (retire→admit reuses
        freed pages). page_size=1 is the degenerate page-per-position
        case."""
        cfg = _cfg()
        params = self._params(cfg)
        rows = [[5, 6, 7], [1, 2, 3, 4], [9, 8], [3, 1, 4, 1, 5], [2, 7]]
        dense = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=2, max_len=32)
        try:
            want = dense.generate(rows, max_new_tokens=6, timeout=300)
        finally:
            dense.stop()
        paged = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=2, max_len=32,
                                         kv="paged", page_size=page_size)
        try:
            got = paged.generate(rows, max_new_tokens=6, timeout=300)
            stats = paged.stats()
        finally:
            paged.stop()
        assert got == want
        assert stats["kv"] == "paged"
        assert stats["kv_pages_free"] == stats["kv_pages_total"]  # all freed

    def test_stats_count_live_pages_beside_table_entries(self):
        """`paged_pages_live` adds, each decode step, the pages the live
        rows hold (pos // page + 1); `paged_pages_table` the entries of
        the block tables the kernel is handed (slots x max_len/page)."""
        cfg = _cfg()
        engine = ContinuousBatchingEngine(
            "llama_tiny", cfg, self._params(cfg), slots=2, max_len=32,
            kv="paged", page_size=4)
        try:
            before = engine.stats()
            assert (before["paged_pages_live"],
                    before["paged_pages_table"]) == (0, 0)
            live = steps = 0
            for prompt, new in (([3, 1, 4, 1, 5, 9], 7), ([2, 7], 3)):
                engine.generate([prompt], max_new_tokens=new, timeout=300)
                # One row alone: steps at positions n-1 .. n+new-2.
                live += sum((len(prompt) - 1 + i) // 4 + 1
                            for i in range(new))
                steps += new
                stats = engine.stats()
                assert stats["decode_steps"] == steps
                assert stats["paged_pages_live"] == live
                assert stats["paged_pages_table"] == steps * 2 * (32 // 4)
        finally:
            engine.stop()
        assert 0 < live / stats["paged_pages_table"] < 0.25
        # Read off the compiled decode program, once: nothing before it
        # compiles, an integer after (on a TPU, megabytes while the
        # program leaves the pool in place: tests/test_aot_tpu_compile).
        assert before["decode_program_temp_bytes"] is None
        assert isinstance(stats["decode_program_temp_bytes"], int)

    def test_oversubscribed_pool_backpressure(self):
        """A pool HALF the dense reservation still serves all requests
        (admission waits for retirements) — the memory win paged
        exists for."""
        cfg = _cfg()
        params = self._params(cfg)
        rows = [[5, 6, 7], [1, 2, 3, 4], [9, 8, 7]]
        # slots=2, max_len=32, page=4 → dense-equivalent 16 pages; use 8
        # (kv_pages counts usable pages; scratch is internal).
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=2, max_len=32, kv="paged",
                                          page_size=4, kv_pages=8)
        try:
            out = engine.generate(rows, max_new_tokens=5, timeout=300)
            assert all(len(r) == 5 for r in out)
        finally:
            engine.stop()

    def test_pool_exhaustion_mid_generation_fails_loudly(self):
        """Each request fits the pool ALONE (passes up-front validation)
        but two growing concurrently drain it: the starved row must
        error with the actionable message — and its released pages let
        the surviving neighbour finish."""
        cfg = _cfg()
        params = self._params(cfg)
        # 4 usable pages of 4. Each request: prompt 3 + 8 new → positions
        # 0..9 → 3 pages alone (feasible). Concurrently: 2 pages each at
        # admission+first growth (4 used, 0 free), then both need a 3rd
        # at pos 8 — slot 0 fails first, its release frees slot 1.
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=2, max_len=32, kv="paged",
                                          page_size=4, kv_pages=4)
        try:
            req_a = engine.submit([5, 6, 7], max_new_tokens=8)
            req_b = engine.submit([9, 8, 7], max_new_tokens=8)
            with pytest.raises(RuntimeError, match="pool exhausted"):
                req_a.wait(timeout=300)
            assert len(req_b.wait(timeout=300)) == 8
        finally:
            engine.stop()

    def test_paged_requires_family_surface(self):
        from polyaxon_tpu.models import t5

        cfg = t5.CONFIGS["t5_tiny"]
        params = t5.init(cfg, jax.random.key(0))["params"]
        with pytest.raises(ValueError, match="decode_step_paged"):
            ContinuousBatchingEngine("t5_tiny", cfg, params, kv="paged")

    def test_static_engine_rejects_paged(self):
        from polyaxon_tpu.serving import ServingServer

        with pytest.raises(ValueError, match="continuous"):
            ServingServer("llama_tiny", kv="paged", batching="static")

    def test_impossible_request_rejected_up_front(self):
        """A request that cannot fit the pool even alone must fail at
        submit — parking it at the FIFO head would block the queue
        forever."""
        cfg = _cfg()
        params = self._params(cfg)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32, kv="paged",
                                          page_size=4, kv_pages=2)
        try:
            with pytest.raises(ValueError, match="KV pages"):
                engine.submit([1] * 10, max_new_tokens=10)  # needs 5 pages
            # And a feasible request afterwards still works.
            assert len(engine.generate([[5, 6, 7]], max_new_tokens=4,
                                       timeout=300)[0]) == 4
        finally:
            engine.stop()


class TestMoEPaged:
    def test_moe_paged_matches_dense_engine(self):
        """The MoE family over the paged pool: greedy parity with its
        own dense engine (expert routing sees the same hidden states
        either way)."""
        from polyaxon_tpu.models import moe

        cfg = dataclasses.replace(moe.CONFIGS["moe_tiny"],
                                  dtype=jnp.float32)
        params = moe.init(cfg, jax.random.key(0))["params"]
        rows = [[5, 6, 7], [1, 2, 3, 4], [9, 8]]
        dense = ContinuousBatchingEngine("moe_tiny", cfg, params,
                                         slots=2, max_len=32)
        try:
            want = dense.generate(rows, max_new_tokens=5, timeout=300)
        finally:
            dense.stop()
        paged = ContinuousBatchingEngine("moe_tiny", cfg, params,
                                         slots=2, max_len=32,
                                         kv="paged", page_size=4)
        try:
            got = paged.generate(rows, max_new_tokens=5, timeout=300)
        finally:
            paged.stop()
        assert got == want


class TestPagedKernel:
    def test_pallas_engine_under_tp_mesh(self):
        """`plx serve --mesh tp=N` with the kernel: the pool is born
        sharded over kv heads (every device holds a slice — nothing
        whole on the first), the kernel runs per tp shard inside
        shard_map, and tokens match the one-device gather engine."""
        from polyaxon_tpu.parallel import build_mesh
        from polyaxon_tpu.serving.server import load_params

        mesh = build_mesh(axes={"tp": 2}, devices=jax.devices()[:2])
        cfg, params = load_params("llama_tiny", seed=0, mesh=mesh)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  paged_attention_impl="pallas")
        prompts = [[5, 6, 7, 1, 2, 3, 4, 9, 8, 2, 11], [3, 1, 4, 1, 5]]
        sharded = ContinuousBatchingEngine(
            "llama_tiny", cfg, params, slots=2, max_len=32, kv="paged",
            page_size=4, mesh=mesh)
        try:
            got = sharded.generate(prompts, max_new_tokens=5, timeout=300)
            shards = sharded._cache["k"].addressable_shards
            stats = sharded.stats()
        finally:
            sharded.stop()
        assert {s.device.id for s in shards} == {0, 1}
        assert all(s.data.shape[2] == cfg.n_kv_heads // 2 for s in shards)
        assert stats["device"]["count"] == 2

        cfg1, params1 = load_params("llama_tiny", seed=0)
        cfg1 = dataclasses.replace(cfg1, dtype=jnp.float32,
                                   paged_attention_impl="gather")
        single = ContinuousBatchingEngine(
            "llama_tiny", cfg1, params1, slots=2, max_len=32, kv="paged",
            page_size=4)
        try:
            want = single.generate(prompts, max_new_tokens=5, timeout=300)
        finally:
            single.stop()
        assert got == want

    @pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
    def test_kernel_matches_gather_reference(self, case):
        """The Pallas paged-decode kernel (interpret mode on CPU) must
        match the XLA gather+masked-softmax formulation on live rows —
        ragged positions, holes in the tables, GQA, every head size and
        table width the repo serves — and zero idle rows."""
        from polyaxon_tpu.ops.attention import repeat_kv
        from polyaxon_tpu.ops.paged_attention import paged_decode_attention

        spec = dict(_KERNEL_CASES[case])
        KV, rep, Hd, page = (spec[k] for k in ("kv", "rep", "hd", "page"))
        dtype = spec.get("dtype", jnp.float32)
        pos = jnp.asarray(spec["pos"], jnp.int32)
        tables = _kernel_tables(spec)
        B, maxp = tables.shape
        H, P = KV * rep, int(tables.max()) + 2
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, H, Hd), jnp.float32).astype(dtype)
        # Token-major pages for the reference; the kernel takes the
        # pool's kv-head-major layout [P, KV, page, Hd].
        k_pages = jax.random.normal(
            ks[1], (P, page, KV, Hd), jnp.float32).astype(dtype)
        v_pages = jax.random.normal(
            ks[2], (P, page, KV, Hd), jnp.float32).astype(dtype)

        def stacked(pages):
            """Three layers' pools, the pages in the middle one and NaN
            in the others: the kernel reads the layer it is told."""
            mine = pages.swapaxes(1, 2)
            other = jnp.full_like(mine, jnp.nan)
            return jnp.stack([other, mine, other])

        got = paged_decode_attention(q, stacked(k_pages), stacked(v_pages),
                                     1, tables, pos, interpret=True)
        assert got.shape == (B, H, Hd) and got.dtype == dtype

        # Gather reference (the models/llama.py formulation), float32.
        gathered = jnp.maximum(tables, 0)
        keys_r, vals_r = (
            repeat_kv(pages.astype(jnp.float32)[gathered].reshape(
                B, -1, KV, Hd), rep) for pages in (k_pages, v_pages))
        logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                            keys_r) * Hd ** -0.5
        j = jnp.arange(maxp * page)[None, :]
        allocated = jnp.repeat(tables >= 0, page, axis=1)
        valid = ((j <= jnp.maximum(pos, 0)[:, None]) & (pos[:, None] >= 0)
                 & allocated)[:, None, :]
        probs = jax.nn.softmax(jnp.where(valid, logits, -1e30), axis=-1)
        want = jnp.einsum("bhk,bkhd->bhd", probs, vals_r)

        live = np.asarray(pos) >= 0
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        got = np.asarray(got.astype(jnp.float32))
        np.testing.assert_allclose(got[live], np.asarray(want)[live],
                                   atol=tol, rtol=tol)
        assert (got[~live] == 0).all()  # idle rows → zeros

    def test_pallas_impl_matches_gather_in_step(self):
        """decode_step_paged with paged_attention_impl='pallas'
        (interpret off-TPU) equals the gather formulation on live rows
        — the serving-path integration of the kernel."""
        cfg_g = dataclasses.replace(_cfg(), paged_attention_impl="gather")
        cfg_p = dataclasses.replace(_cfg(), paged_attention_impl="pallas")
        params = llama.init(cfg_g, jax.random.key(0))["params"]
        page = 4
        paged = llama.paged_init_cache(cfg_g, 8, page)
        tables = jnp.asarray([[3, 1, -1, -1, -1, -1, -1, -1],
                              [-1] * 8], jnp.int32)
        prompt = jax.random.randint(jax.random.key(2), (1, 6), 0,
                                    cfg_g.vocab_size)
        k_all, v_all = llama.paged_prefill_kv(cfg_g, params, prompt[:, :-1])
        paged = llama.paged_insert_prefill(paged, k_all, v_all,
                                           tables[0], page)
        tokens = jnp.asarray([int(prompt[0, -1]), 0], jnp.int32)
        pos = jnp.asarray([5, -1], jnp.int32)
        want, _ = llama.decode_step_paged(cfg_g, params, paged, tokens,
                                          pos, tables)
        got, _ = llama.decode_step_paged(cfg_p, params, paged, tokens,
                                         pos, tables)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=2e-4, rtol=2e-4)
        assert np.isfinite(np.asarray(got[1])).all()

    @pytest.mark.parametrize("traced", [False, True],
                             ids=["layer-int", "layer-traced"])
    def test_page_write_equals_token_scatter(self, traced):
        """`paged_write_step` (whole pages read, changed and put back)
        leaves what the token scatter it replaced leaves on every page a
        live row owns — a row writing a page's first offset and one its
        last among them — and nothing anywhere else but scratch page 0,
        which the idle and the unallocated rows all name: old or new
        values there, in whatever order, all finite."""
        L, P, KV, page, Hd = 3, 12, 2, 4, 8
        ks = jax.random.split(jax.random.key(7), 2)
        pool = jax.random.normal(ks[0], (L, P, KV, page, Hd), jnp.float32)
        pool = pool.at[:, 0].set(0.0)  # scratch starts as zeros
        kv = jax.random.normal(ks[1], (7, KV, Hd), jnp.float32)
        tables = np.full((7, 4), -1, np.int32)
        tables[0, :2] = [3, 9]      # pos 4: offset 0 of its second page
        tables[1, :3] = [5, 2, 7]   # pos 11: offset page-1 of its third
        tables[2, :2] = [6, 4]      # pos 6
        tables[5, :2] = [8, -1]     # pos 5 and 14 fall on pages never
        tables[6, :3] = [10, 11, 1]  # allocated: the scratch page
        pos = jnp.asarray([4, 11, 6, -1, -1, 5, 14], jnp.int32)
        _, write_page, write_off, _ = llama.paged_coords(
            pos, jnp.asarray(tables), page)
        assert write_page.tolist() == [9, 7, 4, 0, 0, 0, 0]
        assert write_off.tolist() == [0, 3, 2, 0, 0, 1, 2]

        layer = 1
        write = (jax.jit(llama.paged_write_step) if traced
                 else llama.paged_write_step)
        got = np.asarray(write(pool, jnp.int32(layer) if traced else layer,
                               kv, write_page, write_off))
        want = np.asarray(
            pool.at[layer, write_page, :, write_off].set(kv))
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        for b, (pg, off) in enumerate([(9, 0), (7, 3), (4, 2)]):
            np.testing.assert_array_equal(got[layer, pg, :, off],
                                          np.asarray(kv[b]))
        scratch = got[layer, 0]
        assert np.isfinite(scratch).all()
        np.testing.assert_array_equal(got[[0, 2], 0], 0.0)
        wrote = {0: (3, 4), 1: (5,), 2: (6,)}  # offset -> rows that name it
        for off in range(page):
            allowed = [np.zeros((KV, Hd), np.float32)] + [
                np.asarray(kv[b]) for b in wrote.get(off, ())]
            assert any((scratch[:, off] == a).all() for a in allowed), off

    @pytest.mark.parametrize("case", sorted(_SPAN_CASES))
    def test_span_write_equals_token_scatter(self, case):
        """`paged_write_span` (a prefill's insert: the touched pages
        read, merged and put back) leaves at every real position of an
        allocated page what a token-wise scatter of the real tokens
        leaves, and everywhere else what was there: the slots before
        ``start`` and past the span's end in the pages it touches, every
        page it does not touch, and the padding past ``real_len``, which
        goes nowhere but scratch page 0."""
        spec = _SPAN_CASES[case]
        L, P, KV, page, Hd = 2, 40, 2, 4, 8
        start, S, real = spec["start"], spec["S"], spec.get("real")
        table = np.asarray(spec.get("table", _SPAN_TABLE), np.int32)
        # The marker: what every slot held before, no two alike, so
        # that equality with `want` below holds every slot of every page
        # but scratch to "its token, or what was there".
        pool = 100.0 + jnp.arange(L * P * KV * page * Hd,
                                  dtype=jnp.float32).reshape(
                                      L, P, KV, page, Hd)
        kv = jax.random.normal(jax.random.key(11), (L, S, KV, Hd),
                               jnp.float32)

        t = start + np.arange(S if real is None else real)
        pidx = np.maximum(table[t // page], 0)
        want = np.asarray(pool.at[:, pidx, :, t % page].set(
            jnp.moveaxis(kv[:, :len(t)], 1, 0)))

        if spec.get("plain"):
            got = llama.paged_write_span(pool, kv, jnp.asarray(table), start)
        else:
            got = jax.jit(llama.paged_write_span)(
                pool, kv, jnp.asarray(table), jnp.int32(start),
                None if real is None else jnp.int32(real))
        got = np.asarray(got)
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        assert np.isfinite(got[:, 0]).all()


class TestPrefixCache:
    def test_shared_prompt_pages_reused(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        tokens = list(range(10))  # prefill 0..8 → pages 0,1 shareable
        assert pool.admit(0, 10, tokens)
        free_after_first = pool.free_pages
        assert pool.admit(1, 10, tokens)
        assert pool.prefix_hits == 2
        # Second identical prompt costs only its private decode page.
        assert free_after_first - pool.free_pages == 1
        # The shared pages appear in both tables; privates differ.
        assert (pool.tables[0][:2] == pool.tables[1][:2]).all()
        assert pool.tables[0][2] != pool.tables[1][2]

    def test_resident_pages_survive_release_and_rehit(self):
        pool = PagePool(slots=1, max_len=32, page_size=4, n_pages=9)
        tokens = list(range(10))
        assert pool.admit(0, 10, tokens)
        pool.release(0)
        assert pool.free_pages == 8  # resident pages still allocatable
        assert pool.admit(0, 10, tokens)
        assert pool.prefix_hits == 2  # prompt KV reused across requests

    def test_distinct_prompts_do_not_cross_hit(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        assert pool.admit(0, 10, list(range(10)))
        assert pool.admit(1, 10, list(range(100, 110)))
        assert pool.prefix_hits == 0
        # Common-prefix prompts share exactly the common full pages.
        pool.release(0)
        pool.release(1)
        a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
        b = [1, 2, 3, 4, 5, 6, 7, 8, 77, 88]  # diverges in page 2
        pool2 = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        assert pool2.admit(0, 10, a)
        assert pool2.admit(1, 10, b)
        assert pool2.prefix_hits == 2  # pages 0,1 shared; page 2 private

    def test_eviction_under_pressure(self):
        pool = PagePool(slots=1, max_len=32, page_size=4, n_pages=4)
        assert pool.admit(0, 10, list(range(10)))  # 3 pages (2 prefix)
        pool.release(0)
        # A distinct prompt needs 3 pages; only 1 truly free → evicts
        # LRU resident prefix pages.
        assert pool.admit(0, 10, list(range(50, 60)))
        assert pool.free_pages == 0

    def test_failed_admission_invalidates_unwritten_keys(self):
        pool = PagePool(slots=1, max_len=32, page_size=4, n_pages=9)
        assert pool.admit(0, 10, list(range(10)))
        pool.release(0, invalidate_prefix=True)  # prefill never ran
        assert pool.admit(0, 10, list(range(10)))
        assert pool.prefix_hits == 0  # keys did not survive

    def test_engine_prefix_reuse_matches_dense(self):
        """Sequential identical prompts: the second hits the prefix
        cache AND produces exactly the dense engine's tokens (the
        resident pages hold the right content)."""
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # 2 full prefix pages
        dense = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=1, max_len=32)
        try:
            want = dense.generate([prompt], max_new_tokens=5, timeout=300)
        finally:
            dense.stop()
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4)
        try:
            first = engine.generate([prompt], max_new_tokens=5, timeout=300)
            second = engine.generate([prompt], max_new_tokens=5, timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert first == want and second == want
        assert stats["kv_prefix_hits"] >= 2  # second request reused KV

    def test_live_shared_pages_cost_nothing_at_admission(self):
        """A prompt whose prefix pages are LIVE in another slot only
        pays for its private pages — the hot-system-prompt workload
        must not be refused under pressure it doesn't create."""
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=5)
        tokens = list(range(10))  # 3 pages, 2 shareable
        assert pool.admit(0, 10, tokens)
        assert pool.free_pages == 1  # pages_for(10)=3 would not fit...
        assert pool.can_admit(10, tokens)  # ...but 2 are live shares
        assert pool.admit(1, 10, tokens)
        assert pool.free_pages == 0
        assert pool.prefix_hits == 2


class TestRadixPrefixSharing:
    """The radix-tree prefix index: copy-on-write forks at mid-page
    divergence, refcount/eviction invariants under the chaos paths
    (fork-then-release, failed admission, whole-tree invalidation),
    and cache-aware admission ordering."""

    def test_cow_fork_at_mid_page_divergence(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
        b = [1, 2, 3, 4, 5, 6, 77, 88, 99, 100]  # diverges INSIDE page 1
        assert pool.admit(0, 10, a)
        res = pool.admit(1, 10, b)
        assert res is not None
        # Page 0 fully matched; tokens 4,5 of page 1 match → CoW fork.
        assert res.matched_pages == 1
        assert res.matched_tokens == 6
        assert res.cow is not None
        src, dst = res.cow
        assert src == int(pool.tables[0][1]) and dst == int(pool.tables[1][1])
        assert src != dst  # the fork got its own private copy
        assert int(pool.tables[0][0]) == int(pool.tables[1][0])
        assert pool.cow_forks == 1
        assert pool.check_invariants() == []

    def test_live_rows_append_to_pages_they_alone_hold(self):
        """What the decode step's whole-page write leans on
        (`llama.paged_write_step`): whatever a row adopted at admission
        — full pages of a live row's prompt, a fork of a page it
        diverges inside, the head of a longer prompt cut inside a page —
        the page it appends to, at admission and at every position
        after, is in no other row's table and has one reference."""
        page = 4
        pool = PagePool(slots=5, max_len=32, page_size=page, n_pages=41)
        a = list(range(100, 117))  # 17 tokens: pages 0..3 go to the tree
        prompts = [
            a,
            list(a),                      # every shareable page adopted
            a[:6] + [7] * 5,              # diverges inside page 1: a fork
            a[:11],                       # ends inside a's page 2: a fork
            a + [1, 2, 3, 4, 5, 6, 7],    # a's prompt is its prefix
        ]
        results = [pool.admit(slot, len(tokens), tokens)
                   for slot, tokens in enumerate(prompts)]
        assert all(results)
        assert [r.matched_pages for r in results] == [0, 4, 1, 2, 4]
        assert [r.cow is not None for r in results] == [
            False, False, True, True, False]
        for step in range(2 * page + 1):  # across two page boundaries
            appends = []
            for slot, tokens in enumerate(prompts):
                pos = len(tokens) - 1 + step
                assert pool.ensure(slot, pos)
                appends.append(int(pool.tables[slot, pos // page]))
            assert min(appends) > 0  # never the scratch page
            assert [int(pool._ref[p]) for p in appends] == [1] * 5, step
            for slot, pg in enumerate(appends):
                others = np.delete(pool.tables, slot, axis=0)
                assert not (others == pg).any(), (step, slot)
        assert pool.check_invariants() == []

    def test_fork_then_release_leaks_nothing(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
        b = [1, 2, 3, 4, 5, 6, 77, 88, 99, 100]
        assert pool.admit(0, 10, a)
        assert pool.admit(1, 10, b)
        pool.release(0)
        assert pool.check_invariants() == []
        pool.release(1)
        assert pool.check_invariants() == []
        # 3 chain pages (a's two + b's forked branch) stay resident but
        # reclaimable; both private decode pages went back to the free
        # list — every usable page is allocatable again.
        assert pool.free_pages == 8
        assert pool.radix_stats()["pages"] == 3
        # And both branches re-hit their own content.
        assert pool.admit(0, 10, a)
        assert pool.admit(1, 10, b)
        assert pool.check_invariants() == []

    def test_eviction_of_live_referenced_page_impossible(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=5)
        a = list(range(10))  # 3 pages, 2 in the tree — slot 0 LIVE
        assert pool.admit(0, 10, a)
        distinct = [50, 51, 52, 53, 54, 55]  # needs 2 fresh pages
        # Only 1 page is truly free and the tree pages are referenced
        # by slot 0: nothing may be evicted from under it.
        assert not pool.can_admit(6, distinct)
        assert not pool.admit(1, 6, distinct)
        assert pool.check_invariants() == []
        assert (pool.tables[0][:3] >= 1).all()  # row untouched
        pool.release(0)  # now resident → evictable
        bigger = list(range(50, 60))  # 3 pages: must evict a resident
        assert pool.admit(1, 10, bigger)
        assert pool.prefix_evictions >= 1
        assert pool.check_invariants() == []

    def test_invalidate_prefix_cache_drops_whole_tree(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        a = list(range(10))
        assert pool.admit(0, 10, a)
        pool.release(0)
        assert pool.radix_stats()["pages"] == 2
        pool.invalidate_prefix_cache()
        assert pool.radix_stats() == {"nodes": 0, "pages": 0,
                                      "referenced": 0, "resident": 0}
        assert pool.free_pages == 8
        assert pool.check_invariants() == []
        assert pool.admit(0, 10, a)
        assert pool.prefix_hits == 0  # nothing survived

    def test_invalidate_with_live_rows_keeps_allocations(self):
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        a = list(range(10))
        assert pool.admit(0, 10, a)  # LIVE while the tree is dropped
        pool.invalidate_prefix_cache()
        assert pool.check_invariants() == []
        assert (pool.tables[0][:3] >= 1).all()
        assert pool.admit(1, 10, a)
        assert pool.prefix_hits == 0  # shareability gone, pages intact
        pool.release(0)
        pool.release(1)
        assert pool.check_invariants() == []

    def test_failed_prefill_detaches_only_the_fresh_leaf(self):
        """Mid-prefill failure/requeue chaos: invalidating slot 1's
        admission must forget ONLY the chain pages it registered —
        the prefix it adopted from slot 0 keeps serving hits."""
        pool = PagePool(slots=2, max_len=32, page_size=4, n_pages=9)
        a = list(range(10))            # chain: pages 0..1 (tokens 0..7)
        b = list(range(8)) + list(range(200, 206))  # extends a's chain
        assert pool.admit(0, 10, a)
        res = pool.admit(1, 14, b)
        assert res is not None and res.matched_pages == 2
        pool.release(1, invalidate_prefix=True)  # prefill never ran
        assert pool.check_invariants() == []
        # a's chain still matches; b's extension is gone.
        assert pool.peek_matched_tokens(14, b) == 8
        res2 = pool.admit(1, 14, b)
        assert res2 is not None and res2.matched_pages == 2
        assert pool.check_invariants() == []

    def test_commit_prefix_makes_leaf_durable(self):
        pool = PagePool(slots=1, max_len=32, page_size=4, n_pages=9)
        a = list(range(10))
        assert pool.admit(0, 10, a)
        pool.commit_prefix(0)  # prefill completed
        # invalidate_prefix on release is now a no-op for the leaf.
        pool.release(0, invalidate_prefix=True)
        assert pool.admit(0, 10, a)
        assert pool.prefix_hits == 2
        assert pool.check_invariants() == []

    def test_engine_cow_parity_with_dense(self):
        """Two prompts diverging mid-page: the forked request's tokens
        must match the dense engine exactly (the CoW copy + suffix
        prefill reconstruct the same KV), with zero invariant
        violations afterwards."""
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        p1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        p2 = [3, 1, 4, 1, 5, 9, 7, 7, 5, 3]  # diverges at index 6
        dense = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=1, max_len=32)
        try:
            want1 = dense.generate([p1], max_new_tokens=5, timeout=300)
            want2 = dense.generate([p2], max_new_tokens=5, timeout=300)
        finally:
            dense.stop()
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4)
        try:
            got1 = engine.generate([p1], max_new_tokens=5, timeout=300)
            got2 = engine.generate([p2], max_new_tokens=5, timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert got1 == want1 and got2 == want2
        assert stats["kv_cow_forks"] >= 1
        assert stats["prefill_tokens_skipped"] > 0
        assert stats["kv_invariant_violations"] == 0

    def test_suffix_from_inside_a_forked_page_matches_whole_prompt(self):
        """A prompt that leaves another's inside a page: the shared page
        is forked and the suffix insert merges the novel tokens into
        the copy, behind what it holds of the match. The tokens decoded
        are those of the same prompt prefilled whole (an engine that
        shares nothing), and the first prompt's pages still hold what
        they held: asked again, it decodes the same."""
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        p1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
        p2 = p1[:6] + [7, 7, 5, 3, 2, 3, 8, 4]  # leaves at 6: page 1, slot 2
        whole = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=1, max_len=32, kv="paged",
                                         page_size=4, prefix_cache=False)
        try:
            want1 = whole.generate([p1], max_new_tokens=5, timeout=300)
            want2 = whole.generate([p2], max_new_tokens=5, timeout=300)
            assert whole.stats()["prefill_tokens_skipped"] == 0
        finally:
            whole.stop()
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4)
        try:
            before = engine.stats()
            got1 = engine.generate([p1], max_new_tokens=5, timeout=300)
            got2 = engine.generate([p2], max_new_tokens=5, timeout=300)
            again1 = engine.generate([p1], max_new_tokens=5, timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert (got1, got2, again1) == (want1, want2, want1)
        assert stats["kv_cow_forks"] >= 1
        assert stats["prefill_tokens_skipped"] >= 6
        assert stats["kv_invariant_violations"] == 0
        # One whole-prompt and one suffix program have compiled: the
        # larger's temporaries (on a TPU, megabytes while the insert
        # writes by whole pages: tests/test_aot_tpu_compile).
        assert before["prefill_program_temp_bytes"] is None
        assert isinstance(stats["prefill_program_temp_bytes"], int)

    def test_engine_full_prefill_cache_hit(self):
        """A prompt whose whole prefill sits in the tree (a previous
        longer prompt wrote it) runs NO prefill program and still
        decodes the dense engine's tokens."""
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        a = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7]  # chain: 12 tokens
        b = a[:13]  # prefill = a[:12] — fully inside a's chain
        dense = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=1, max_len=32)
        try:
            want = dense.generate([b], max_new_tokens=5, timeout=300)
        finally:
            dense.stop()
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4)
        try:
            engine.generate([a], max_new_tokens=5, timeout=300)
            got = engine.generate([b], max_new_tokens=5, timeout=300)
            stats = engine.stats()
            timeline = engine.request_timeline(
                engine.recent_requests()[0]["request_id"])
        finally:
            engine.stop()
        assert got == want
        # Second admission skipped its entire 12-token prefill.
        assert stats["prefill_tokens_skipped"] >= 12
        assert stats["kv_invariant_violations"] == 0
        from polyaxon_tpu.obs import analyze

        summary = analyze.request_phases(timeline)
        assert summary["prefix_cached_tokens"] == 12

    def test_prefix_cache_off_disables_sharing(self):
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4,
                                          prefix_cache=False)
        try:
            first = engine.generate([prompt], max_new_tokens=4, timeout=300)
            second = engine.generate([prompt], max_new_tokens=4, timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert first == second
        assert stats["kv_prefix_hits"] == 0
        assert stats["prefill_tokens_skipped"] == 0
        assert stats["kv_invariant_violations"] == 0

    def test_cache_aware_admission_prefers_hot_prefix(self):
        """Among admissible pending requests the one with the hottest
        matched prefix is admitted first; overtaken requests age, and
        a request at the skip cap becomes a barrier nothing younger
        passes."""
        from polyaxon_tpu.serving.batching import _Request

        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4)
        engine.stop()  # drive _pick_next_locked deterministically
        pool = engine._pool
        hot = list(range(12))
        assert pool.admit(0, 12, hot)
        pool.release(0)  # hot's chain is resident in the tree
        cold = list(range(100, 112))
        r_cold = _Request(list(cold), 4, 0.0, 0)
        r_hot = _Request(list(hot), 4, 0.0, 0)
        engine._queues["batch"].extend([r_cold, r_hot])
        with engine._cv:
            assert engine._pick_next_locked() is r_hot
        assert r_cold.admit_skips == 1  # the overtaken request aged
        engine._queues["batch"].clear()
        # Barrier: a starved request terminates the scan and wins.
        r_starved = _Request(list(cold), 4, 0.0, 0)
        r_starved.admit_skips = engine._admit_skip_cap
        r_hot2 = _Request(list(hot), 4, 0.0, 0)
        engine._queues["batch"].extend([r_starved, r_hot2])
        with engine._cv:
            assert engine._pick_next_locked() is r_starved

    def test_moe_prefix_reuse_matches_dense(self):
        """The MoE family's suffix prefill (expert FFN over the novel
        tokens only): the same prompt served whole, and served from
        cached pages plus a suffix, decodes to the same logits as the
        dense engine.

        Logits, not tokens: with random weights two candidates can lie
        a rounding apart (this test once compared tokens and read
        [77, 77, 77, 123, 77] against [77, 77, 77, 77, 123]). The
        engines compute in float32 and differ only in the order of
        their sums (the suffix's tokens are routed as a group of their
        own, padded to a bucket), a few 1e-6 on logits of size ~1:
        2e-4 leaves room and is far under what a dropped pair or a
        wrong page gives (1e-1 and up). Where the greedy tokens part,
        the two candidates must lie within that tolerance of each
        other at the step they part, and the comparison ends there."""
        from polyaxon_tpu.models import moe

        cfg = dataclasses.replace(moe.CONFIGS["moe_tiny"],
                                  dtype=jnp.float32)
        params = moe.init(cfg, jax.random.key(0))["params"]
        prompt = [5, 6, 7, 1, 2, 3, 4, 9, 8, 2]
        seen = []

        def watched(real):
            def step(cfg, params, cache, tokens, pos, *tables):
                logits, cache = real(cfg, params, cache, tokens, pos,
                                     *tables)
                jax.debug.callback(
                    lambda p, l: seen.append((int(p[0]), np.array(l[0]))),
                    pos, logits)
                return logits, cache
            return step

        def served(engine):
            seen.clear()
            out = engine.generate([prompt], max_new_tokens=5, timeout=300)
            jax.effects_barrier()
            by_pos = dict(seen)
            return out[0], np.stack([by_pos[len(prompt) - 1 + i]
                                     for i in range(5)])

        def same_until_a_tie(a, b, tol=2e-4):
            (tok_a, log_a), (tok_b, log_b) = a, b
            for i in range(5):
                np.testing.assert_allclose(log_a[i], log_b[i], atol=tol,
                                           rtol=tol)
                if tok_a[i] != tok_b[i]:
                    assert abs(log_a[i][tok_a[i]]
                               - log_a[i][tok_b[i]]) <= 2 * tol
                    return

        real = (moe.decode_step_ragged, moe.decode_step_paged)
        moe.decode_step_ragged, moe.decode_step_paged = map(watched, real)
        try:
            dense = ContinuousBatchingEngine("moe_tiny", cfg, params,
                                             slots=1, max_len=32)
            try:
                want = served(dense)
            finally:
                dense.stop()
            paged = ContinuousBatchingEngine("moe_tiny", cfg, params,
                                             slots=1, max_len=32,
                                             kv="paged", page_size=4)
            try:
                first = served(paged)
                second = served(paged)
                stats = paged.stats()
            finally:
                paged.stop()
        finally:
            moe.decode_step_ragged, moe.decode_step_paged = real
        same_until_a_tie(first, want)
        same_until_a_tie(second, want)
        assert stats["prefill_tokens_skipped"] > 0
        assert stats["kv_invariant_violations"] == 0


class TestSuffixBucketUnit:
    """Pure bucketing math (smoke tier): padded suffix lengths are
    powers of two with a floor, so the distinct-executable count per
    prefix-page count is O(log max_suffix)."""

    def test_power_of_two_with_floor(self):
        from polyaxon_tpu.serving.batching import bucket_suffix_len

        assert bucket_suffix_len(1) == 8
        assert bucket_suffix_len(8) == 8
        assert bucket_suffix_len(9) == 16
        assert bucket_suffix_len(16) == 16
        assert bucket_suffix_len(17) == 32
        assert bucket_suffix_len(1000) == 1024
        with pytest.raises(ValueError, match="suffix length"):
            bucket_suffix_len(0)

    def test_bucket_count_is_logarithmic(self):
        from polyaxon_tpu.serving.batching import bucket_suffix_len

        buckets = {bucket_suffix_len(n) for n in range(1, 1025)}
        assert buckets == {8, 16, 32, 64, 128, 256, 512, 1024}


class TestSuffixBucketing:
    def test_varied_suffix_lengths_bound_compiles_with_parity(self):
        """Shared-prefix prompts with DISTINCT suffix lengths: the
        suffix-prefill executable count is the bucket count (here 4
        lengths → 2 buckets, observed via the lru cache_info), and the
        masked padding changes no tokens vs the dense engine."""
        cfg = _cfg()
        params = llama.init(cfg, jax.random.key(0))["params"]
        base = [3, 1, 4, 1, 5, 9, 2, 6]  # exactly 2 prefix pages
        # Distinct first tokens → divergence at the page boundary →
        # every request skips exactly the 2 base pages (one n_pref).
        # Prefill excludes the prompt's LAST token (fed at decode), so
        # these give prefill-suffix lengths 1, 3, 7, 9.
        suffixes = [[11, 30], [12, 13, 14, 30],
                    [15, 16, 17, 18, 13, 14, 15, 30],
                    [19, 20, 21, 22, 23, 24, 25, 26, 27, 30]]
        prompts = [base + s for s in suffixes]
        dense = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                         slots=1, max_len=32)
        try:
            want = [dense.generate([p], max_new_tokens=4, timeout=300)
                    for p in prompts]
        finally:
            dense.stop()
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          kv="paged", page_size=4)
        try:
            # Warmup writes the base chain; its own prefill is
            # monolithic (nothing cached yet) — not a suffix compile.
            engine.generate([base + [10]], max_new_tokens=4, timeout=300)
            got = [engine.generate([p], max_new_tokens=4, timeout=300)
                   for p in prompts]
            info = engine._suffix_prefill.cache_info()
            stats = engine.stats()
        finally:
            engine.stop()
        assert got == want
        # Suffix lengths 1, 3, 7, 9 land in buckets {8, 16}: two
        # executables serve all four requests.
        assert info.misses == 2
        assert info.hits == 2
        assert stats["kv_invariant_violations"] == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_radix_walk_compares_runs_and_gives_the_token_walks_answers(seed):
    """`_common` compares whole runs (an int32 prompt against the
    node's key as an array, any other sequence slice against slice):
    for keys and prompts that agree up to every kind of place (nowhere,
    inside the first page, on a page boundary, inside a late page, past
    the key's end, past the limit) both give what the walk token by
    token gives, and so do `match`, `peek_matched_tokens` and
    `admissible_match` over a tree of documents with tails."""
    from polyaxon_tpu.serving import paged

    rng = np.random.default_rng(seed)
    key = tuple(int(t) for t in rng.integers(0, 50, 200))
    node = paged._RadixNode(key, [], None)

    def walk(tokens, start, limit):
        n = min(len(key), max(limit - start, 0))
        j = 0
        while j < n and key[j] == tokens[start + j]:
            j += 1
        return j

    for agree in (0, 1, 15, 16, 17, 64, 150, 199, 200):
        for start in (0, 3):
            tokens = [99] * start + list(key[:agree]) + [77] * (260 - agree)
            for limit in (0, start + 10, start + 160, start + 200, 400):
                want = walk(tokens, start, limit)
                assert paged._common(node, tokens, start, limit) == want
                assert paged._common(node, np.asarray(tokens, np.int32),
                                     start, limit) == want
    assert node.array is not None and len(node.array) == len(key)

    ps = 4
    pool = PagePool(4, 256, ps, 200)
    docs = [rng.integers(0, 50, 40).tolist() for _ in range(3)]
    prompts = [docs[i % 3] + rng.integers(0, 50, 5 + i).tolist()
               for i in range(6)]
    for slot, prompt in enumerate(prompts[:3]):
        assert pool.admit(slot, len(prompt), prompt)
        pool.commit_prefix(slot)
    for prompt in prompts:
        as_array = np.asarray(prompt, np.int32)
        n = len(prompt)
        seen = pool.peek_matched_tokens(n, prompt)
        assert seen == pool.peek_matched_tokens(n, as_array)
        assert pool.can_admit(n, prompt)
        assert pool.admissible_match(n, prompt) == seen
        assert pool.admissible_match(n, as_array) == seen
    assert pool.peek_matched_tokens(len(prompts[3]), prompts[3]) >= 40
    assert pool.check_invariants() == []
