"""The window / full attention decoder with routed ReGLU experts
(models/smallthinker.py) against its plain reference
(benchmark/reference/smallthinker.py) at `smallthinker_tiny` (hidden 64,
4 heads on 2 of 16, one period G W W W, window 32 = two pages of 16, 8
experts of width 32, 2 a token, vocabulary 256), float32, seeded
weights whose norm gains are moved off the identity so that each shows.
Logits are compared, never sampled tokens.

Tolerances. Both sides compute in float32 on the CPU and differ in the
order of their sums (fused projections, the one-hot or sorted dispatch,
pages and an online softmax against the reference's full squares and
loops), so logits of size ~1 agree to a few 1e-5. `TOL` leaves a factor
of ten over that and is a thousand times under what a wrong mask,
rotation, routing weight or page gives (1e-1 and up)."""

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

from reference import smallthinker as ref  # noqa: E402

from polyaxon_tpu.models import smallthinker as st  # noqa: E402
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine  # noqa: E402
from polyaxon_tpu.serving.paged import (  # noqa: E402
    WindowedPagePool, page_bytes, window_page_bytes)

TOL = dict(atol=3e-4, rtol=3e-4)
SEED = 7
PAGE = 16


def _cfg(**changes):
    return dataclasses.replace(st.CONFIGS["smallthinker_tiny"],
                               dtype=jnp.float32, max_seq_len=256, **changes)


def _ref_config(cfg) -> dict:
    """The tiny config under the published file's key names."""
    return {
        "hidden_size": cfg.dim, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "moe_ffn_hidden_size": cfg.moe_ffn_dim,
        "moe_num_primary_experts": cfg.n_experts,
        "moe_num_active_primary_experts": cfg.experts_per_token,
        "norm_topk_prob": True, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "rope_layout": list(cfg.rope_layout),
        "sliding_window_layout": list(cfg.window_layout),
        "sliding_window_size": cfg.sliding_window,
        "vocab_size": cfg.vocab_size, "torch_dtype": "float32"}


def _init(cfg):
    """The program's seeded weights, norm gains drawn off the identity;
    the reference's tree is the same arrays."""
    params = st.init(cfg, jax.random.key(SEED))["params"]
    keys = iter(jax.random.split(jax.random.key(SEED + 1), 3))

    def gains(shape):
        return 1.0 + 0.2 * jax.random.normal(next(keys), shape)

    params["attn"]["attn_norm"] = gains(params["attn"]["attn_norm"].shape)
    params["moe"]["moe_norm"] = gains(params["moe"]["moe_norm"].shape)
    params["final_norm"] = gains(params["final_norm"].shape)
    return params


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = _init(cfg)
    tokens = _tokens(cfg, 104)
    want = np.asarray(ref.logits(_ref_config(cfg), params,
                                 jnp.asarray(tokens[None])))[0]
    return cfg, params, tokens, want


def test_reference_draws_the_programs_weights():
    cfg = _cfg()
    ours = st.init(cfg, jax.random.key(3))["params"]
    theirs = ref.init_weights(_ref_config(cfg), cfg.n_layers, 3)
    flat = dict(jax.tree_util.tree_leaves_with_path(theirs))
    for path, leaf in jax.tree_util.tree_leaves_with_path(ours):
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat[path]))


def test_layer_plan_is_the_published_period():
    cfg = st.CONFIGS["smallthinker_21b_a3b"]
    plan = st.layer_plan(cfg)
    assert [kind for kind, _, _ in plan[:4]] == [
        "full", "window", "window", "window"]
    assert [rotary for _, _, rotary in plan[:4]] == [False, True, True, True]
    assert st.kind_counts(cfg) == {"full": 13, "window": 39}
    with pytest.raises(ValueError, match="side by side"):
        _cfg(window_layout=(1, 1, 1, 1))


def test_forward_matches_the_reference(model):
    cfg, params, tokens, want = model
    got = st.forward(cfg, params, jnp.asarray(tokens[None]))[0]
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_a_window_and_a_rotation_show_in_the_logits(model):
    """What the comparison would miss if it could not see them: the
    window (a full-attention twin differs past the window and not
    before it) and the per-layer rotary switch."""
    cfg, params, tokens, want = model
    wide = dataclasses.replace(cfg, sliding_window=4096)
    got = np.asarray(st.forward(wide, params, jnp.asarray(tokens[None]))[0])
    np.testing.assert_allclose(got[:cfg.sliding_window],
                               want[:cfg.sliding_window], **TOL)
    assert np.abs(got[-1] - want[-1]).max() > 1e-2
    turned = dataclasses.replace(cfg, rope_layout=(1, 1, 1, 1))
    got = np.asarray(st.forward(turned, params, jnp.asarray(tokens[None]))[0])
    assert np.abs(got[-1] - want[-1]).max() > 1e-2


def test_dense_prefill_then_decode_matches_the_reference(model):
    cfg, params, tokens, want = model
    P = 40
    logits, cache = st.prefill(cfg, params, jnp.asarray(tokens[None, :P]), 128)
    np.testing.assert_allclose(np.asarray(logits[0]), want[P - 1], **TOL)
    step = jax.jit(lambda c, t, p: st.decode_step(cfg, params, c, t, p))
    for t in range(P, 104):
        logits, cache = step(cache, jnp.asarray(tokens[t:t + 1]), t)
    np.testing.assert_allclose(np.asarray(logits[0]), want[103], **TOL)


class _Rows:
    """What the engine does on the device for an admission and a step,
    with the pool's own bookkeeping in both page spaces."""

    def __init__(self, cfg, params, slots=2, max_len=256, n_pages=40):
        self.cfg, self.params, self.slots = cfg, params, slots
        self.pool = WindowedPagePool(slots, max_len, PAGE, n_pages,
                                     window=cfg.sliding_window)
        self.cache = st.paged_init_cache(cfg, n_pages, PAGE,
                                         self.pool.window_n_pages)
        self.pos = np.full(slots, -1, np.int32)
        self._step = jax.jit(lambda cache, tokens, pos, full, window:
                             st.decode_step_paged(cfg, params, cache, tokens,
                                                  pos, (full, window)))

    def admit(self, b: int, prompt: list):
        assert self.pool.admit(b, len(prompt), prompt)
        self.cache = st.paged_insert_prefill(
            self.cache, *st.paged_prefill_kv(
                self.cfg, self.params, jnp.asarray([prompt[:-1]], jnp.int32)),
            jnp.asarray(self.pool.padded_row(b)), PAGE)
        self.pos[b] = len(prompt) - 1

    def step(self, cur: dict) -> np.ndarray:
        tokens = np.zeros(self.slots, np.int32)
        for b, tok in cur.items():
            tokens[b] = tok
            assert self.pool.ensure(b, int(self.pos[b]))
            self.pool.roll(b, int(self.pos[b]))
        logits, self.cache = self._step(
            self.cache, jnp.asarray(tokens), jnp.asarray(self.pos.copy()),
            jnp.asarray(self.pool.tables.copy()),
            jnp.asarray(self.pool.window_tables.copy()))
        logits = np.asarray(logits)
        for b in cur:
            self.pos[b] += 1
        return logits


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_paged_prefill_then_decode_past_three_windows(model, impl):
    """Prefill through both page spaces, then decode to 104 positions
    (window 32: the window chain rolled over more than three times, and
    its released pages were taken again by the second row), logits
    against the reference at every step. A second row at another length
    shares the pools."""
    cfg, params, tokens, want = model
    cfg = dataclasses.replace(cfg, paged_attention_impl=impl)
    rows = _Rows(cfg, params)
    other = _tokens(cfg, 80, seed=1)
    other_want = np.asarray(ref.logits(_ref_config(cfg), params,
                                       jnp.asarray(other[None])))[0]
    rows.admit(0, tokens[:50].tolist())
    rows.admit(1, other[:37].tolist())
    assert page_bytes(rows.cache, rows.pool.n_pages, PAGE) == (
        2 * 1 * 2 * PAGE * 16 * 4, 0, 0)
    assert window_page_bytes(rows.cache) == 2 * 3 * 2 * PAGE * 16 * 4
    for t in range(49, 104):
        cur = {0: tokens[t]}
        if t - 13 < 80:
            cur[1] = other[t - 13]
        elif rows.pos[1] >= 0:          # its request ended: an idle row
            rows.pool.release(1)
            rows.pos[1] = -1
        logits = rows.step(cur)
        np.testing.assert_allclose(logits[0], want[t], **TOL)
        if 1 in cur:
            np.testing.assert_allclose(logits[1], other_want[t - 13], **TOL)
        held = np.count_nonzero(rows.pool.window_tables >= 0, axis=1)
        assert held.max() <= cfg.sliding_window // PAGE + 1
    assert rows.pool.window_pages_released >= 5
    assert rows.pool.check_invariants() == []
    assert rows.cache["moe_expert_tokens"].sum() == (
        cfg.n_layers * cfg.experts_per_token * (55 + 44))


def test_engine_serves_it_through_both_page_spaces(model):
    """`ContinuousBatchingEngine(kv="paged")` as the server starts it:
    greedy tokens against the reference's argmax, rows longer than three
    windows, the pool's counters in `/v1/stats`."""
    cfg, params, _, _ = model
    st.CONFIGS["smallthinker_test"] = cfg
    try:
        engine = ContinuousBatchingEngine(
            "smallthinker_test", cfg, params, slots=3, kv="paged",
            page_size=PAGE, kv_pages=48)
        try:
            prompts = [_tokens(cfg, n, seed=n).tolist() for n in (50, 70, 33, 90)]
            outs = engine.generate(prompts, 70)
            stats = engine.stats()
        finally:
            engine.stop()
    finally:
        del st.CONFIGS["smallthinker_test"]
    for prompt, out in zip(prompts, outs):
        seq = np.asarray(prompt + out[:-1], np.int32)
        lg = np.asarray(ref.logits(_ref_config(cfg), params,
                                   jnp.asarray(seq[None])))[0]
        at = lg[len(prompt) - 1:]
        gap = at.max(-1) - at[np.arange(len(out)), np.asarray(out)]
        assert gap.max() < 1e-3, gap.max()
    assert stats["kv_invariant_violations"] == 0
    assert stats["step_failures"] == 0
    assert stats["kv_window"] == 32
    assert stats["kv_window_row_pages_max"] == 3
    assert stats["kv_window_pages_released"] > 0
    assert stats["kv_window_pages_live"] == 0
    assert stats["kv_window_pages_free"] == stats["kv_window_pages_total"] == 9
    assert stats["tick_phase_ns"]["step.window"] > 0
    assert stats["kv_prefix_hits"] == 0
    assert np.asarray(stats["moe_expert_tokens"]).sum() > 0


def test_llama_paged_cache_still_refuses_a_uniform_window():
    from polyaxon_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"], sliding_window=16)
    with pytest.raises(ValueError, match="window layers"):
        llama.paged_init_cache(cfg, 8, 4)
