"""The hybrid decoder (models/lfm2.py) against its plain reference
(benchmark/reference/lfm2.py) at `lfm2_tiny`, float32, seeded weights
with a non-zero `expert_bias`. Logits are compared, never sampled
tokens: with random weights the largest logit changes on rounding.

Tolerances. Both sides compute in float32 on the CPU; they differ in
the order of their sums (the program's fused projections and one-hot
dispatch, the reference's loops), so logits of size ~1 agree to a few
1e-5. `TOL` leaves a factor of ten over that and is a thousand times
under what a wrong state, page or routing weight gives (1e-1 and up)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import lfm2 as ref  # noqa: E402

from polyaxon_tpu.models import lfm2, moe, plan  # noqa: E402
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine  # noqa: E402
from polyaxon_tpu.serving.paged import PagePool, page_bytes  # noqa: E402

TOL = dict(atol=3e-4, rtol=3e-4)
PAGE = 4
SEED = 5


def _cfg():
    return dataclasses.replace(lfm2.CONFIGS["lfm2_tiny"], dtype=jnp.float32)


def _ref_config(cfg) -> dict:
    """The tiny config under the published file's key names."""
    return {
        "hidden_size": cfg.dim, "intermediate_size": cfg.ffn_dim,
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": cfg.n_dense_layers,
        "layer_types": list(cfg.layer_types), "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "use_expert_bias": cfg.use_expert_bias,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "conv_L_cache": cfg.conv_kernel, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = lfm2.init(cfg, jax.random.key(SEED))["params"]
    config = _ref_config(cfg)
    weights = ref.init_weights(config, cfg.n_layers, SEED)
    return cfg, params, config, weights


def _tokens(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def test_reference_weights_are_the_programs_bit_for_bit(model):
    cfg, params, _, weights = model
    assert jax.tree.structure(params) == jax.tree.structure(weights)
    for ours, theirs in zip(jax.tree.leaves(params), jax.tree.leaves(weights)):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    assert float(jnp.abs(params["moe"]["expert_bias"]).min()) > 0


def test_forward_matches_the_reference(model):
    cfg, params, config, weights = model
    tokens = jnp.asarray([_tokens(23), _tokens(23, 1)], jnp.int32)
    got = lfm2.forward(cfg, params, tokens)
    want = ref.logits(config, weights, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_int8_control_fails_the_serving_tolerance(model):
    """The control one precision down (int8 matmul inputs) lies far
    outside what the served path is held to."""
    cfg, params, config, weights = model
    tokens = jnp.asarray([_tokens(23)], jnp.int32)
    want = np.asarray(ref.logits(config, weights, tokens))
    low = np.asarray(ref.logits(config, weights, tokens, "int8"))
    assert np.abs(low - want).max() > 30 * TOL["atol"]


class _Rows:
    """What the engine does on the device for an admission and a step,
    with the pool's own bookkeeping: the prefill or suffix program,
    then `decode_step_paged` a token at a time. Returns logits."""

    def __init__(self, cfg, params, slots=2, max_len=64, n_pages=40):
        self.cfg, self.params = cfg, params
        self.pool = PagePool(slots, max_len, PAGE, n_pages)
        self.cache = lfm2.paged_init_cache(cfg, n_pages, PAGE)
        self.pool.whole_page_matches = page_bytes(
            self.cache, n_pages, PAGE)[1] > 0
        self.pos = np.full(slots, -1, np.int32)

    def admit(self, b: int, prompt: list):
        res = self.pool.admit(b, len(prompt), prompt)
        assert res is not None and res.cow is None
        toks = prompt[:-1]
        skip = min(res.matched_tokens, len(toks))
        ids = jnp.asarray(self.pool.padded_row(b))
        if skip == 0 and toks:
            self.cache = lfm2.paged_insert_prefill(
                self.cache, *lfm2.paged_prefill_kv(
                    self.cfg, self.params, jnp.asarray([toks], jnp.int32)),
                ids, PAGE)
        elif skip < len(toks):
            suffix = toks[skip:]
            padded = suffix + [0] * (8 - len(suffix) % 8)   # a padded bucket
            pref = jnp.maximum(ids[:-(-skip // PAGE)], 0)
            novel = lfm2.paged_prefill_suffix_kv(
                self.cfg, self.params, jnp.asarray([padded], jnp.int32),
                *lfm2.paged_gather_prefix(self.cache, pref), jnp.int32(skip))
            self.cache = lfm2.paged_insert_suffix(
                self.cache, *novel, ids, jnp.int32(skip), PAGE,
                jnp.int32(len(suffix)))
        self.pool.commit_prefix(b)
        self.pos[b] = len(prompt) - 1
        return res

    def step(self, cur: list) -> np.ndarray:
        for b, p in enumerate(self.pos):
            if p >= 0:
                assert self.pool.ensure(b, int(p))
        logits, self.cache = lfm2.decode_step_paged(
            self.cfg, self.params, self.cache, jnp.asarray(cur, jnp.int32),
            jnp.asarray(self.pos), jnp.asarray(self.pool.tables))
        self.pos[self.pos >= 0] += 1
        return np.asarray(logits)

    def release(self, b: int):
        self.pool.release(b)
        self.pos[b] = -1


def _decode(rows: _Rows, b: int, seq: list, start: int, steps: int):
    """Teacher-forced: feed seq[start], seq[start+1], ...; logits [steps, V]."""
    out = []
    for i in range(steps):
        cur = [0] * len(rows.pos)
        cur[b] = seq[start + i]
        out.append(rows.step(cur)[b])
    return np.stack(out)


@pytest.mark.parametrize("prompt_len", [7, 9, 10],
                         ids=["inside-a-page", "on-a-boundary", "one-past"])
def test_prefill_then_decode_through_the_pages_matches_the_reference(
        model, prompt_len):
    """The prefill's last position ends inside a page (6 tokens in
    pages of 4), on a boundary (8) and one past it (9); 12 decode steps
    then cross three more pages, beside an idle row."""
    cfg, params, config, weights = model
    seq = _tokens(prompt_len + 12, seed=prompt_len)
    rows = _Rows(cfg, params)
    rows.admit(1, seq[:prompt_len])
    got = _decode(rows, 1, seq, prompt_len - 1, 12)
    want = np.asarray(ref.logits(config, weights,
                                 jnp.asarray([seq], jnp.int32)))[0]
    np.testing.assert_allclose(got, want[prompt_len - 1:prompt_len + 11],
                               **TOL)
    assert rows.pool.check_invariants() == []


def test_conv_state_after_prefill_is_the_references_z(model):
    """Every page the prefill touched holds the reference's `z` of the
    last two positions written there; the last page's are the prompt's
    last two."""
    cfg, params, config, weights = model
    prompt = _tokens(11, seed=3)             # prefill 10 tokens: pages 0,1,2
    rows = _Rows(cfg, params)
    rows.admit(0, prompt)
    keep = {}
    ref.hidden(config, weights, jnp.asarray([prompt[:-1]], jnp.int32),
               keep=keep)
    z = np.stack([np.asarray(layer[0]) for layer in keep["z"]])  # [Lc, 10, D]
    conv = np.asarray(rows.cache["conv"])
    for page_index, last in enumerate([3, 7, 9]):
        page = int(rows.pool.tables[0][page_index])
        np.testing.assert_allclose(conv[:, page], z[:, last - 1:last + 1],
                                   **TOL)


def test_same_prompt_twice_matches_whole_pages_and_the_same_logits(model):
    """The second pass adopts whole pages only (10 prefill tokens: 2
    pages of 4, not the 9 or 10 tokens a token-granular match would
    give), plans no fork, prefills the rest as a padded suffix behind
    the pages' state, and decodes to the same logits."""
    cfg, params, config, weights = model
    seq = _tokens(11 + 6, seed=4)
    rows = _Rows(cfg, params)
    rows.admit(0, seq[:11])
    first = _decode(rows, 0, seq, 10, 6)
    rows.release(0)
    res = rows.admit(1, seq[:11])
    assert (res.matched_pages, res.matched_tokens, res.cow) == (2, 8, None)
    second = _decode(rows, 1, seq, 10, 6)
    want = np.asarray(ref.logits(config, weights,
                                 jnp.asarray([seq], jnp.int32)))[0, 10:16]
    np.testing.assert_allclose(first, want, **TOL)
    np.testing.assert_allclose(second, want, **TOL)
    assert rows.pool.cow_forks == 0 and rows.pool.check_invariants() == []


def test_eviction_and_readmission_give_the_same_logits(model):
    """A row evicted mid-generation (its pages released as
    `_evict_slot` releases them) and admitted again recomputes only
    what the radix tree does not hold, and goes on as before."""
    cfg, params, config, weights = model
    seq = _tokens(14 + 8, seed=6)
    want = np.asarray(ref.logits(config, weights,
                                 jnp.asarray([seq], jnp.int32)))[0]
    rows = _Rows(cfg, params)
    rows.admit(0, seq[:14])
    before = _decode(rows, 0, seq, 13, 3)
    rows.release(0)                              # evicted at position 16
    res = rows.admit(0, seq[:14])                # regenerated from the prompt
    assert res.matched_tokens == 12
    after = _decode(rows, 0, seq, 13, 8)
    np.testing.assert_allclose(before, want[13:16], **TOL)
    np.testing.assert_allclose(after, want[13:21], **TOL)


def test_dense_slot_cache_matches_the_reference(model):
    cfg, params, config, weights = model
    seq = _tokens(9 + 5, seed=8)
    logits, cache = lfm2.prefill(cfg, params,
                                 jnp.asarray([seq[:9]], jnp.int32), 32)
    got = [np.asarray(logits[0])]
    for i in range(4):
        logits, cache = lfm2.decode_step_ragged(
            cfg, params, cache, jnp.asarray([seq[9 + i]], jnp.int32),
            jnp.asarray([9 + i], jnp.int32))
        got.append(np.asarray(logits[0]))
    want = np.asarray(ref.logits(config, weights,
                                 jnp.asarray([seq], jnp.int32)))[0, 8:13]
    np.testing.assert_allclose(np.stack(got), want, **TOL)


class TestRouter:
    """Selection uses score + bias, the weights the score alone."""

    CFG = dataclasses.replace(_cfg(), n_experts=4, experts_per_token=2)
    # Scores rise with the expert's index; the bias turns the order over.
    LOGITS = jnp.asarray([[-1.0, 0.0, 1.0, 2.0]])
    BIAS = jnp.asarray([0.9, 0.6, 0.0, -0.9])

    def test_selection_by_score_plus_bias_weights_by_score(self):
        idx, w, scores = moe.route(self.CFG, self.LOGITS, self.BIAS)
        s = np.asarray(jax.nn.sigmoid(self.LOGITS))[0]
        by_score = set(np.argsort(-s)[:2].tolist())
        by_biased = set(np.argsort(-(s + np.asarray(self.BIAS)))[:2].tolist())
        assert by_score != by_biased                 # the test can tell
        assert set(np.asarray(idx)[0].tolist()) == by_biased
        chosen = s[np.asarray(idx)[0]]
        np.testing.assert_allclose(np.asarray(w)[0],
                                   chosen / (chosen.sum() + 1e-6), rtol=1e-6)
        biased = chosen + np.asarray(self.BIAS)[np.asarray(idx)[0]]
        assert np.abs(np.asarray(w)[0] - biased / biased.sum()).max() > 0.05
        np.testing.assert_allclose(np.asarray(scores)[0], s, rtol=1e-6)

    def test_softmax_routing_is_mixtrals(self):
        cfg = dataclasses.replace(moe.CONFIGS["moe_tiny"])
        idx, w, probs = moe.route(cfg, self.LOGITS)
        p = np.asarray(jax.nn.softmax(self.LOGITS))[0]
        assert np.asarray(idx)[0].tolist() == [3, 2]
        np.testing.assert_allclose(np.asarray(w)[0], p[[3, 2]] / p[[3, 2]].sum(),
                                   rtol=1e-6)

    def test_the_expert_block_follows_a_large_bias(self, model):
        """With a bias large enough to change most choices, the
        program's block and the reference's still agree: neither uses
        `s` to choose nor `s + b` to weigh."""
        cfg, params, config, weights = model
        bias = 0.5 * jnp.sign(params["moe"]["expert_bias"][0])
        layer = {**plan._at(params["moe"], 0), "expert_bias": bias}
        x = jax.random.normal(jax.random.key(1), (1, 12, cfg.dim))
        got, onehot = lfm2.expert_ffn(cfg, layer, x)
        want = ref.expert_ffn(config, layer, x[0], "highest")
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), **TOL)
        unbiased = lfm2.expert_ffn(cfg, {**layer, "expert_bias": 0 * bias},
                                   x)[1]
        assert float(jnp.abs(onehot.sum(1) - unbiased.sum(1)).sum()) > 0


class TestEngine:
    """Through `ContinuousBatchingEngine(kv="paged")`: the logits the
    decode program produced (a spy around the family's step, inside the
    compiled program) against the reference over what was served."""

    @pytest.fixture()
    def spy(self, monkeypatch):
        seen = []
        real = lfm2.decode_step_paged

        def watched(cfg, params, cache, tokens, pos, tables):
            logits, cache = real(cfg, params, cache, tokens, pos, tables)
            jax.debug.callback(
                lambda p, l: seen.append((np.array(p), np.array(l))),
                pos, logits)
            return logits, cache

        monkeypatch.setattr(lfm2, "decode_step_paged", watched)
        return seen

    def _served_logits(self, seen, n_prompt):
        got = {int(p[0]): l[0] for p, l in seen if p[0] >= 0}
        return np.stack([got[n_prompt - 1 + i] for i in range(len(got))])

    def test_prefix_cache_serves_whole_pages_and_counts_experts(
            self, model, spy):
        cfg, params, config, weights = model
        prompt = _tokens(11, seed=9)
        engine = ContinuousBatchingEngine(
            "lfm2_tiny", cfg, params, slots=2, max_len=32, kv="paged",
            page_size=PAGE, prefix_cache=True)
        try:
            first = engine.generate([prompt], max_new_tokens=6, timeout=300)
            jax.effects_barrier()
            logits_first = self._served_logits(spy, len(prompt))
            spy.clear()
            second = engine.generate([prompt], max_new_tokens=6, timeout=300)
            jax.effects_barrier()
            logits_second = self._served_logits(spy, len(prompt))
            stats = engine.stats()
            timeline = engine.recent_requests()
        finally:
            engine.stop()
        seq = prompt + first[0][:-1]
        want = np.asarray(ref.logits(
            config, weights, jnp.asarray([seq], jnp.int32)))[0, 10:]
        np.testing.assert_allclose(logits_first, want, **TOL)
        np.testing.assert_allclose(logits_second, want, **TOL)
        assert first == second
        # 10 prefill tokens: two whole pages served, never 9 or 10 tokens.
        assert stats["prefill_tokens_skipped"] == 8
        assert stats["kv_cow_forks"] == 0
        assert stats["kv_invariant_violations"] == 0
        n = lfm2.kind_counts(cfg)
        state = n["conv"] * (cfg.conv_kernel - 1) * cfg.dim * 4
        tokens = 2 * n["attn"] * cfg.n_kv_heads * PAGE * cfg.head_dim * 4
        assert stats["kv_state_bytes_per_page"] == state
        assert stats["kv_page_bytes"] == state + tokens
        # Every decode step routed its one live row to k experts a layer.
        routed = np.asarray(stats["moe_expert_tokens"])
        assert routed.shape == (n["moe"], cfg.n_experts)
        assert routed.sum(1).tolist() == [12 * cfg.experts_per_token] * n["moe"]
        assert timeline

    def test_speculation_and_chunked_prefill_are_refused(self, model):
        cfg, params, _, _ = model
        with pytest.raises(ValueError, match="decode_chunk"):
            ContinuousBatchingEngine(
                "lfm2_tiny", cfg, params, slots=1, max_len=32,
                draft=("lfm2_tiny", cfg, params, 2))
        with pytest.raises(ValueError, match="decode_chunk"):
            ContinuousBatchingEngine(
                "lfm2_tiny", cfg, params, slots=1, max_len=32,
                prefill_chunk=4)

    def test_dense_kv_engine_serves_the_same_tokens(self, model):
        cfg, params, _, _ = model
        prompt = _tokens(9, seed=10)
        outs = []
        for kv in ("dense", "paged"):
            engine = ContinuousBatchingEngine(
                "lfm2_tiny", cfg, params, slots=2, max_len=32, kv=kv,
                page_size=PAGE)
            try:
                outs.append(engine.generate([prompt], max_new_tokens=5,
                                            timeout=300))
            finally:
                engine.stop()
        assert outs[0] == outs[1]


def _page_bytes_cases():
    """(cache, (bytes a page holds of tokens, of per-page state, bytes a
    row holds)) by the three kinds of leaf."""
    from polyaxon_tpu.models import llama, nemotron_h

    plain_cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"],
                                    dtype=jnp.float32)
    kv = (2 * plain_cfg.n_layers * plain_cfg.n_kv_heads * PAGE
          * plain_cfg.head_dim * 4)
    yield "per-token", llama.paged_init_cache(plain_cfg, 9, PAGE), (kv, 0, 0)
    cfg = _cfg()
    n = lfm2.kind_counts(cfg)
    yield "per-page", lfm2.paged_init_cache(cfg, 9, PAGE), (
        2 * n["attn"] * cfg.n_kv_heads * PAGE * cfg.head_dim * 4,
        n["conv"] * (cfg.conv_kernel - 1) * cfg.dim * 4, 0)
    rowed = dataclasses.replace(nemotron_h.CONFIGS["nemotron_h_tiny"],
                                dtype=jnp.float32)
    m = nemotron_h.kind_counts(rowed)
    per_row = m["ssm"] * (
        rowed.ssm_heads * rowed.ssm_head_dim * rowed.ssm_state * 4
        + (rowed.conv_kernel - 1) * rowed.conv_dim * 4)
    tokens = 2 * m["attn"] * rowed.n_kv_heads * PAGE * rowed.head_dim * 4
    for rows in (3, 9):      # as many rows as pages tells nothing apart
        yield f"per-row-{rows}", {
            **nemotron_h.paged_init_cache(rowed, 9, PAGE),
            "rows": nemotron_h.paged_init_rows(rowed, rows)}, (
                tokens, 0, per_row)
    # The counters beside them ([L, E], [L]) are no page's and no row's.
    yield "counters-only", {
        name: leaf for name, leaf in nemotron_h.paged_init_cache(
            rowed, 9, PAGE).items() if name.startswith("moe_")}, (0, 0, 0)


@pytest.mark.parametrize("case", ["per-token", "per-page", "per-row-3",
                                  "per-row-9", "counters-only"])
def test_page_bytes_reads_the_caches_structure(case):
    cache, want = next((c, w) for name, c, w in _page_bytes_cases()
                       if name == case)
    assert page_bytes(cache, 9, PAGE) == want


def test_a_match_inside_a_page_rounds_down_to_whole_pages():
    pool = PagePool(2, 32, PAGE, 9)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    b = [1, 2, 3, 4, 5, 6, 77, 88, 99, 100]      # diverges inside page 1
    pool.whole_page_matches = True
    assert pool.admit(0, 10, a)
    assert pool.peek_matched_tokens(10, b) == 4
    res = pool.admit(1, 10, b)
    assert (res.matched_pages, res.matched_tokens, res.cow) == (1, 4, None)
