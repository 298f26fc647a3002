"""`qwen3_next_tiny` through pages and rows: what the engine does on the
device for an admission and a step, and the engine itself as the server
starts it, against the plain reference's full forward (logits, then
greedy tokens through the engine). Weights, tolerances and helpers are
tests/test_qwen3_next.py's."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_qwen3_next import (  # noqa: F401  (`model` is a fixture)
    TOL, _cfg, _init, _tokens, model, qn, ref)

from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
from polyaxon_tpu.serving.paged import PagePool, page_bytes

PAGE = 4


class _Rows:
    """What the engine does on the device for an admission and a step,
    with the pool's own bookkeeping: the prefill (whole, or in padded
    chunks as the prefill lane runs it), then `decode_step_paged` a
    token at a time. Returns logits."""

    def __init__(self, cfg, params, slots=2, max_len=64, n_pages=40):
        self.cfg, self.params, self.slots = cfg, params, slots
        self.pool = PagePool(slots, max_len, PAGE, n_pages)
        self.cache = qn.paged_init_cache(cfg, n_pages, PAGE)
        self.cache["rows"] = qn.paged_init_rows(cfg, slots)
        assert page_bytes(self.cache, n_pages, PAGE)[2] > 0
        self.pool.match_nothing()
        self.pos = np.full(slots, -1, np.int32)
        self._step = jax.jit(lambda cache, tokens, pos, tables:
                             qn.decode_step_paged(cfg, params, cache, tokens,
                                                  pos, tables))

    def admit(self, b: int, prompt: list, chunk=None):
        res = self.pool.admit(b, len(prompt), prompt)
        assert res is not None and res.matched_tokens == 0
        toks = prompt[:-1]
        ids = jnp.asarray(self.pool.padded_row(b))
        row = jnp.int32(b)
        if chunk is None:
            self.cache = qn.paged_insert_prefill(
                self.cache, *qn.paged_prefill_kv(
                    self.cfg, self.params, jnp.asarray([toks], jnp.int32)),
                ids, PAGE, row)
        for i in range(0, len(toks), chunk) if chunk else ():
            piece = toks[i:i + chunk]
            padded = piece + [0] * (chunk - len(piece))
            pref = jnp.maximum(ids[:-(-i // PAGE)], 0)
            novel = qn.paged_prefill_suffix_kv(
                self.cfg, self.params, jnp.asarray([padded], jnp.int32),
                *qn.paged_gather_prefix(self.cache, pref, row),
                jnp.int32(i), jnp.int32(len(piece)))
            self.cache = qn.paged_insert_suffix(
                self.cache, *novel, ids, jnp.int32(i), PAGE,
                jnp.int32(len(piece)), row)
        self.pos[b] = len(prompt) - 1

    def step(self, cur: dict) -> np.ndarray:
        tokens = np.zeros(self.slots, np.int32)
        for b, tok in cur.items():
            tokens[b] = tok
            assert self.pool.ensure(b, int(self.pos[b]))
        # Copies, and the result read back before the host's arrays move
        # on: the CPU backend aliases what `jnp.asarray` is handed, and a
        # jitted step reads it after the call returns.
        logits, self.cache = self._step(
            self.cache, jnp.asarray(tokens), jnp.asarray(self.pos.copy()),
            jnp.asarray(self.pool.tables.copy()))
        logits = np.asarray(logits)
        for b in cur:
            self.pos[b] += 1
        return logits

    def release(self, b: int):
        self.pool.release(b)
        self.pos[b] = -1


@pytest.mark.parametrize("chunk", [None, 8])
def test_paged_prefill_then_decode_through_pages_and_rows(model, chunk):
    """Two rows of different lengths: prefilled whole, or in padded
    chunks behind their own earlier chunks, then decoded together;
    every logit against the reference's full forward."""
    cfg, params, config, weights = model
    seqs = [_tokens(27), _tokens(22, 1)]
    starts = [14, 11]
    want = [np.asarray(ref.logits(
        config, weights, jnp.asarray([s], jnp.int32)))[0] for s in seqs]
    rows = _Rows(cfg, params)
    for b in (0, 1):
        rows.admit(b, seqs[b][:starts[b] + 1], chunk)
    for t in range(8):
        cur = {b: seqs[b][starts[b] + t] for b in (0, 1)}
        logits = rows.step(cur)
        for b in (0, 1):
            np.testing.assert_allclose(logits[b], want[b][starts[b] + t],
                                       **TOL)
    assert rows.pool.check_invariants() == []


def test_a_row_finished_and_readmitted_into_a_used_row_prefills_again(model):
    """A row released mid-generation and admitted again, into a row
    another sequence used meanwhile, serves what the reference computes:
    nothing of the old state is resumed from, and nothing matches."""
    cfg, params, config, weights = model
    first, other = _tokens(20), _tokens(17, 3)
    want = np.asarray(ref.logits(config, weights,
                                 jnp.asarray([first], jnp.int32)))[0]
    rows = _Rows(cfg, params, slots=1)
    rows.admit(0, first[:11])
    for t in range(3):
        rows.step({0: first[10 + t]})
    rows.release(0)
    rows.admit(0, other[:9])
    rows.step({0: other[8]})
    rows.release(0)
    assert rows.pool.peek_matched_tokens(11, first[:11]) == 0
    rows.admit(0, first[:11])                    # readmitted: prefills again
    for t in range(6):
        logits = rows.step({0: first[10 + t]})
        np.testing.assert_allclose(logits[0], want[10 + t], **TOL)
    assert rows.pool.check_invariants() == []


def test_decode_counts_held_pairs_and_pairs_elsewhere():
    share = _cfg(held_experts=(4, 4))
    params = _init(share)
    rows = _Rows(share, params)
    rows.admit(0, _tokens(9))
    for _ in range(3):
        rows.step({0: 7})
    held = np.asarray(rows.cache["moe_expert_tokens"])
    elsewhere = np.asarray(rows.cache["moe_pairs_elsewhere"])
    assert held.shape == (4, 4) and elsewhere.shape == (4,)
    np.testing.assert_array_equal(
        held.sum(-1) + elsewhere, [3 * share.experts_per_token] * 4)
    assert elsewhere.min() > 0


class TestEngine:
    """Through `ContinuousBatchingEngine` as the server starts it."""

    def _engine(self, cfg, params, **kw):
        qn.CONFIGS["qwen3_next_tiny_f32"] = cfg
        kw.setdefault("slots", 2)
        return ContinuousBatchingEngine(
            "qwen3_next_tiny_f32", cfg, params, max_len=48, kv="paged",
            page_size=PAGE, **kw)

    def _greedy(self, config, weights, prompt, n):
        seq = list(prompt)
        for _ in range(n):
            logits = ref.logits(config, weights, jnp.asarray([seq], jnp.int32))
            seq.append(int(np.asarray(logits)[0, -1].argmax()))
        return seq[len(prompt):]

    def test_continuous_paged_serving_is_the_references_greedy(self, model):
        """Three requests on two slots: the third is admitted into a row
        a finished request used."""
        cfg, params, config, weights = model
        prompts = [_tokens(13), _tokens(9, 1), _tokens(17, 2)]
        engine = self._engine(cfg, params)
        try:
            outs = engine.generate(prompts, max_new_tokens=5, timeout=600)
            stats = engine.stats()
        finally:
            engine.stop()
        for prompt, out in zip(prompts, outs):
            assert out == self._greedy(config, weights, prompt, 5)
        rows = qn.paged_init_rows(cfg, 1)
        per_slot = sum(leaf.size * leaf.dtype.itemsize
                       for leaf in rows.values())
        assert stats["kv_state_bytes_per_slot"] == per_slot
        assert stats["kv_state_bytes_per_page"] == 0
        assert stats["kv_invariant_violations"] == 0
        assert stats["prefill_tokens_skipped"] == 0
        assert stats["kv_pages_free"] == stats["kv_pages_total"]
        assert len(stats["moe_expert_tokens"]) == cfg.n_layers
        assert len(stats["moe_pairs_elsewhere"]) == cfg.n_layers

    def test_the_prefill_lane_hands_the_rows_state_over(self, model):
        cfg, params, config, weights = model
        prompts = [_tokens(23), _tokens(14, 1)]
        engine = self._engine(cfg, params, prefill_slots=1, prefill_chunk=8)
        try:
            outs = engine.generate(prompts, max_new_tokens=4, timeout=600)
            stats = engine.stats()
        finally:
            engine.stop()
        for prompt, out in zip(prompts, outs):
            assert out == self._greedy(config, weights, prompt, 4)
        assert stats["handoffs"] == 2
        assert stats["kv_invariant_violations"] == 0

    def test_tokens_and_endings_are_announced_to_the_streaming_handlers(
            self, model):
        """A handler sleeps on its request's ``fresh`` event: the engine
        sets it for every request that got a token or ended, a step's
        after the next step is launched and the last ones' when nothing
        is live any more; nothing stays unannounced in an idle engine."""
        cfg, params, config, weights = model
        prompts = [_tokens(9), _tokens(11, 1), _tokens(7, 2)]
        engine = self._engine(cfg, params)
        try:
            reqs = engine.submit_all(prompts, 4)
            for req in reqs:
                assert not req.fresh.is_set() or req.out or req.done.is_set()
            for req in reqs:
                assert req.done.wait(600)
                assert req.fresh.wait(10)      # the end is announced too
            deadline = time.time() + 10
            while engine._unannounced and time.time() < deadline:
                time.sleep(0.01)
            assert engine._unannounced == []
            # A handler that clears the event and finds nothing new
            # sleeps until the next announcement: none comes for a
            # request that has ended.
            reqs[0].fresh.clear()
            assert not reqs[0].fresh.wait(0.05)
        finally:
            engine.stop()
        for prompt, req in zip(prompts, reqs):
            assert req.out == self._greedy(config, weights, prompt, 4)

    @pytest.mark.parametrize("kw, says", [
        (dict(kv="dense", prefill_chunk=4), "decode_chunk"),
        (dict(kv="dense", draft=("llama_tiny", None, None, 2)),
         "decode_chunk"),
    ])
    def test_the_engine_refuses_what_the_family_lacks(self, model, kw, says):
        """Speculation and dense chunked prefill need a state that
        rolls back: refused by the missing surface, not by a name."""
        cfg, params, _, _ = model
        qn.CONFIGS["qwen3_next_tiny_f32"] = cfg
        assert not hasattr(qn, "decode_chunk")
        with pytest.raises(ValueError, match=says):
            ContinuousBatchingEngine("qwen3_next_tiny_f32", cfg, params,
                                     slots=2, max_len=32, **kw)
