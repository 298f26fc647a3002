"""The window / full attention decoder with sigmoid-routed experts behind
a dense layer (models/exaone_moe.py) against its plain reference
(benchmark/reference/exaone_moe.py) at `exaone_moe_tiny` (hidden 64, 8
heads on 2 of 16 with q/k norm gains, one period W W W G, window 16 = one
page of 16, a dense layer and three expert layers of 16 experts of width
32, 4 a token, vocabulary 256), seeded weights whose norm gains are moved
off the identity so that each shows; and a shared prefix under a window
(serving/paged.py `WindowedPagePool`, the suffix surface of
models/smallthinker.py) through the engine, for this family and for
SmallThinker. Logits are compared, never sampled tokens.

Tolerances. In float32 both sides compute on the CPU and differ in the
order of their sums (fused projections, the one-hot or sorted dispatch,
pages and two attentions merged by their logsumexp against the
reference's blocks of full rows), so logits of size ~1 agree to a few
1e-5: `TOL` leaves a factor of ten over that and is a thousand times
under what a wrong mask, rotation, norm, routing weight or page gives
(1e-1 and up). In bfloat16 the program rounds every projection's product
to 8 bits of mantissa where the reference keeps float32 (the weights
themselves are the same bfloat16 values on both sides). At this size the
logits' spread is 0.14, and they then differ by 0.003 in the mean over a
row, up to 0.05 in the mean of one position and 0.17 at the worst entry
of one: `TOL_BF16` and `MEAN_BF16` leave half as much again over those,
where a window four positions too wide moves a position's mean by 0.15
and an entry by 0.8. The float32 case holds the mathematics; the
bfloat16 one holds the path a server runs (projections held transposed,
the casts) against a gross fault."""

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

from reference import exaone_moe as ref  # noqa: E402

from polyaxon_tpu.models import (common, exaone_moe as em, llama,  # noqa: E402
                                 moe, smallthinker as st)
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine  # noqa: E402
from polyaxon_tpu.serving.paged import (  # noqa: E402
    WindowedPagePool, window_suffix_start)

TOL = dict(atol=3e-4, rtol=3e-4)
TOL_BF16 = dict(atol=0.25, rtol=0.05)
MEAN_BF16 = 0.08
SEED = 7
PAGE = 16
KINDS = {1: "sliding_attention", 0: "full_attention"}


def _cfg(**changes):
    return dataclasses.replace(em.CONFIGS["exaone_moe_tiny"],
                               dtype=jnp.float32, **changes)


def _ref_config(cfg, dtype="float32") -> dict:
    """The tiny config under the published file's key names."""
    first, count = cfg.held
    config = {
        "hidden_size": cfg.dim, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "layer_types": [KINDS[w] for w in cfg.window_layout],
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "intermediate_size": cfg.ffn_dim,
        "first_k_dense_replace": cfg.first_dense,
        "moe_intermediate_size": cfg.moe_ffn_dim, "num_experts": count,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": True, "rms_norm_eps": cfg.norm_eps,
        "vocab_size": cfg.vocab_size, "torch_dtype": dtype}
    if cfg.held_experts:
        config["reduced"] = {"num_experts": {"source": cfg.n_experts}}
        config["deployment"] = {"rank": first // count}
    return config


def _init(cfg):
    """The program's seeded weights, norm gains drawn off the identity;
    the reference's tree is the same arrays."""
    params = em.init(cfg, jax.random.key(SEED))["params"]
    keys = iter(jax.random.split(jax.random.key(SEED + 1), 6))

    def off(leaf):
        return 1.0 + 0.2 * jax.random.normal(next(keys), leaf.shape)

    for stack, name in (("attn", "attn_norm"), ("attn", "q_norm"),
                        ("attn", "k_norm"), ("dense", "mlp_norm"),
                        ("moe", "moe_norm")):
        params[stack][name] = off(params[stack][name])
    params["final_norm"] = off(params["final_norm"])
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


# ------------------------------------------------------- weights and plan
def test_reference_draws_the_programs_weights():
    cfg = _cfg(held_experts=(4, 4))
    mine = em.init(cfg, jax.random.key(SEED))["params"]
    theirs = ref.init_weights(_ref_config(cfg), cfg.n_layers, SEED)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layer_plan_is_the_published_period():
    cfg = em.CONFIGS["k_exaone_236b_a23b"]
    plan = st.layer_plan(cfg)
    assert [kind for kind, _, _ in plan[:4]] == [
        "window", "window", "window", "full"]
    assert [rotary for _, _, rotary in plan[:4]] == [True, True, True, False]
    assert st.kind_counts(cfg) == {"full": 12, "window": 36}
    layers = em._layers(cfg)
    assert layers[0] == ("window", 0, "dense", 0)
    assert layers[1] == ("window", 1, "moe", 0)
    assert layers[47] == ("full", 47, "moe", 46)
    with pytest.raises(ValueError, match="side by side"):
        _cfg(window_layout=(1, 1, 1, 1))
    with pytest.raises(ValueError, match="lie outside"):
        _cfg(held_experts=(12, 8))
    # The expert block is one function for the two families that have it.
    from polyaxon_tpu.models import kimi_k2
    assert kimi_k2.expert_block is moe.deepseek_expert_block


def test_forward_matches_the_reference(model):
    cfg, params, tokens, want = model
    got = em.forward(cfg, params, jnp.asarray(tokens[None]))[0]
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_the_window_the_rotation_and_the_qk_norm_show_in_the_logits(model):
    """What the comparison would miss if it could not see them."""
    cfg, params, tokens, want = model
    row = jnp.asarray(tokens[None])
    wide = dataclasses.replace(cfg, sliding_window=4096)
    got = np.asarray(em.forward(wide, params, row)[0])
    np.testing.assert_allclose(got[:cfg.sliding_window],
                               want[:cfg.sliding_window], **TOL)
    assert np.abs(got[-1] - want[-1]).max() > 1e-2
    flat = {**params, "attn": {**params["attn"], "q_norm": jnp.ones_like(
        params["attn"]["q_norm"])}}
    assert np.abs(np.asarray(em.forward(cfg, flat, row)[0, -1])
                  - want[-1]).max() > 1e-2
    # Rotary positions in the window layers and in no other.
    class Turned(em.ExaoneMoEConfig):
        rope_layout = (1, 1, 1, 1)
    turned = Turned(**{f.name: getattr(cfg, f.name)
                       for f in dataclasses.fields(cfg)})
    assert np.abs(np.asarray(em.forward(turned, params, row)[0, -1])
                  - want[-1]).max() > 1e-2


def test_dense_prefill_then_decode_matches_the_reference(model):
    cfg, params, tokens, want = model
    P = 40
    logits, cache = em.prefill(cfg, params, jnp.asarray(tokens[None, :P]), 128)
    np.testing.assert_allclose(np.asarray(logits[0]), want[P - 1], **TOL)
    step = jax.jit(lambda c, t, p: em.decode_step(cfg, params, c, t, p))
    for t in range(P, 72):
        logits, cache = step(cache, jnp.asarray(tokens[t:t + 1]), t)
    np.testing.assert_allclose(np.asarray(logits[0]), want[71], **TOL)


# ------------------------------------------------------ both page spaces
class _Rows:
    """What the engine does on the device for an admission and a step,
    with the pool's own bookkeeping in both page spaces."""

    def __init__(self, family, cfg, params, slots=2, max_len=256, n_pages=40):
        self.family, self.cfg, self.params = family, cfg, params
        self.slots = slots
        self.pool = WindowedPagePool(
            slots, max_len, PAGE, n_pages, window=cfg.sliding_window,
            window_layers=st.kind_counts(cfg)["window"])
        self.cache = family.paged_init_cache(cfg, n_pages, PAGE,
                                             self.pool.window_n_pages)
        self.pos = np.full(slots, -1, np.int32)
        self._step = jax.jit(
            lambda cache, tokens, pos, full, window: family.decode_step_paged(
                cfg, params, cache, tokens, pos, (full, window)))

    def admit(self, b: int, prompt: list, start=None):
        """The whole-prompt program, or behind a match the suffix program
        from `suffix_start` (or from ``start``, to show a wrong one)."""
        res = self.pool.admit(b, len(prompt), prompt)
        assert res
        prefill, m = prompt[:-1], res.matched_tokens
        ids = jnp.asarray(self.pool.padded_row(b))
        if m == 0:
            self.cache = self.family.paged_insert_prefill(
                self.cache, *self.family.paged_prefill_kv(
                    self.cfg, self.params, jnp.asarray([prefill], jnp.int32)),
                ids, PAGE)
        else:
            start = self.pool.suffix_start(m) if start is None else start
            run = np.zeros(-(-(len(prefill) - start) // 64) * 64, np.int32)
            run[:len(prefill) - start] = prefill[start:]
            pages = jnp.maximum(ids[0, :m // PAGE], 0)
            self.cache = self.family.paged_insert_suffix(
                self.cache, *self.family.paged_prefill_suffix_kv(
                    self.cfg, self.params, jnp.asarray([run]),
                    *self.family.paged_gather_prefix(self.cache, pages),
                    start),
                ids, start, m, jnp.int32(len(prefill) - start))
        self.pool.commit_prefix(b)
        self.pos[b] = len(prompt) - 1
        return res

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
        for b in cur:
            self.pos[b] += 1
        return np.asarray(logits)


@pytest.mark.parametrize("dtype,tol", [("float32", TOL),
                                       ("bfloat16", TOL_BF16)])
def test_paged_prefill_then_decode_through_both_spaces(model, dtype, tol):
    """Prefill through both page spaces, then decode (window 16: the
    window chain rolls every page), logits against the reference at
    every step: one row longer than the window from its prompt on, one
    shorter than it that grows past it. In bfloat16 with the weights held
    as a server holds them (`served_params`) and the reference handed
    the same rounded values."""
    cfg, params, tokens, want = model
    other = _tokens(cfg, 60, seed=1)
    if dtype == "bfloat16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        served = common.served_params(params, cfg.dtype, em.READ_AT_FLOAT32,
                                      em.HELD_TRANSPOSED)
        rounded = common.served_params(params, cfg.dtype, em.READ_AT_FLOAT32)
        want = np.asarray(ref.logits(_ref_config(cfg, "bfloat16"), rounded,
                                     jnp.asarray(tokens[None])))[0]
        params = served
    else:
        rounded = params
    other_want = np.asarray(ref.logits(
        _ref_config(cfg, dtype), rounded, jnp.asarray(other[None])))[0]
    rows = _Rows(em, cfg, params)
    rows.admit(0, tokens[:50].tolist())
    rows.admit(1, other[:9].tolist())
    for t in range(49, 90):
        logits = rows.step({0: tokens[t], 1: other[t - 41]})
        for got, ref_row in ((logits[0], want[t]),
                             (logits[1], other_want[t - 41])):
            np.testing.assert_allclose(got, ref_row, **tol)
            if dtype == "bfloat16":
                assert np.abs(got - ref_row).mean() < MEAN_BF16
        held = np.count_nonzero(rows.pool.window_tables >= 0, axis=1)
        assert held.max() <= cfg.sliding_window // PAGE + 1
    assert rows.pool.window_pages_released >= 4
    assert rows.pool.check_invariants() == []
    n_moe = cfg.n_layers - cfg.first_dense
    assert rows.cache["moe_expert_tokens"].shape == (n_moe, cfg.n_experts)
    assert rows.cache["moe_expert_tokens"].sum() == (
        n_moe * cfg.experts_per_token * 2 * 41)


# ------------------------------------------------ a prefix under a window
@pytest.mark.parametrize("m,window,layers,page", [
    (m, window, layers, page)
    for page in (4, 16, 32) for window in (32, 128) if window % page == 0
    for layers in (1, 3, 6, 36) for m in (0, page, 7 * page, 96 * page,
                                          768 * page)])
def test_the_suffix_start_is_never_above_the_exact_bound(m, window, layers,
                                                         page):
    """The i-th window layer's input is exact from ``start + (window -
    1) i``, and its K and V must be over ``[m - window, m)``: the start
    may not lie above ``m - window - (window - 1)(layers - 1)``. It is a
    page boundary, 0 where the match is shorter than what is computed
    again, and no further below the bound than a page and the slack of
    one position a layer."""
    start = window_suffix_start(m, window, layers, page)
    bound = m - window - (window - 1) * (layers - 1)
    assert start % page == 0 and 0 <= start <= max(m, 0)
    assert start <= max(bound, 0)
    if bound > 0:
        assert start > bound - page - layers
    pool = WindowedPagePool(2, 1024 * page, page, 8, window=window,
                            window_layers=layers)
    assert pool.suffix_start(m) == start


def test_a_suffix_behind_a_match_agrees_and_a_late_start_does_not(model):
    """Two rows that share 208 tokens (13 pages; 3 window layers of 16:
    48 are computed again): the second adopts the full space's pages,
    its suffix program starts at 160 (so its full layer attends 128
    cached positions under no mask, and 32 more that lead its own run),
    and prefill then decode give the reference's logits, the shared
    pages bit for bit what they were. The same admission started at the
    match itself, with empty window layers (the error this mechanism can
    make), disagrees far beyond the tolerance."""
    cfg, params, _, _ = model
    first = _tokens(cfg, 230, seed=2)
    second = np.concatenate([first[:208], _tokens(cfg, 40, seed=3)])
    want = np.asarray(ref.logits(_ref_config(cfg), params,
                                 jnp.asarray(second[None])))[0]
    rows = _Rows(em, cfg, params, slots=3, max_len=384, n_pages=64)
    rows.admit(0, first.tolist())
    shared = np.asarray(rows.pool.tables[0, :13])
    before = np.asarray(rows.cache["k"][:, shared])
    for b, start in ((1, None), (2, 208)):
        res = rows.admit(b, second[:222].tolist(), start=start)
        assert (res.matched_tokens, res.matched_pages, res.cow) == (
            208, 13, None)
        np.testing.assert_array_equal(rows.pool.tables[b, :13], shared)
    assert rows.pool.suffix_start(208) == 160
    np.testing.assert_array_equal(
        np.asarray(rows.cache["k"][:, shared]), before)
    worst = np.zeros(3)
    for t in range(221, 236):
        logits = rows.step({1: second[t], 2: second[t]})
        worst = np.maximum(worst, np.abs(logits - want[t]).max(-1))
    assert rows.pool.check_invariants() == []
    assert worst[1] < TOL["atol"] * 2, worst
    assert worst[2] > 100 * TOL["atol"], worst


def _serve(family, cfg, params, prompts, share, new=12, kv_pages=256):
    """(each prompt's tokens, served one after another; the engine's
    stats after the last)."""
    family.CONFIGS["_window_test"] = cfg
    try:
        engine = ContinuousBatchingEngine(
            "_window_test", cfg, params, slots=2, kv="paged", page_size=4,
            kv_pages=kv_pages, max_len=256, prefix_cache=share)
        try:
            outs = [engine.generate([prompt], new)[0] for prompt in prompts]
            return outs, engine.stats()
        finally:
            engine.stop()
    finally:
        del family.CONFIGS["_window_test"]


@pytest.mark.parametrize("name", ["exaone_moe_tiny", "smallthinker_tiny"])
def test_engine_shares_a_prefix_under_a_window(name):
    """Through `ContinuousBatchingEngine(kv="paged")`: a second request
    behind a page-aligned prefix longer than ``window x layers`` skips
    what lies below its suffix's start, a third behind a prefix shorter
    than that recomputes all of it, skipped + recomputed + computed is
    the prompt every time, and every request's tokens are those of a run
    that shares nothing, which are the reference's argmax."""
    family = em if name == "exaone_moe_tiny" else st
    cfg = dataclasses.replace(family.CONFIGS[name], dtype=jnp.float32,
                              max_seq_len=256)
    params = family.init(cfg, jax.random.key(SEED))["params"]
    span = cfg.sliding_window * st.kind_counts(cfg)["window"]
    long = _tokens(cfg, span + 40, seed=5).tolist()
    short = _tokens(cfg, span - 16, seed=6).tolist()
    tails = [_tokens(cfg, n, seed=10 + n).tolist() for n in (9, 17, 5, 11)]
    prompts = [long + tails[0], long + tails[1], short + tails[2],
               short + tails[3]]
    shared, stats = _serve(family, cfg, params, prompts, True)
    alone, plain = _serve(family, cfg, params, prompts, False)
    assert shared == alone
    if family is em:
        for prompt, out in zip(prompts[:2], shared[:2]):
            seq = np.asarray(prompt + out[:-1], np.int32)
            lg = np.asarray(ref.logits(_ref_config(cfg), params,
                                       jnp.asarray(seq[None])))[0]
            at = lg[len(prompt) - 1:]
            gap = at.max(-1) - at[np.arange(len(out)), np.asarray(out)]
            assert gap.max() < 1e-3, gap.max()
    # Request 2 matches `long` by whole pages of 4 and starts `span`
    # below; request 4 matches `short` (shorter than the span) and
    # starts at 0.
    m_long = len(long) // 4 * 4
    m_short = len(short) // 4 * 4
    assert stats["prefill_tokens_matched"] == m_long + m_short
    assert stats["prefill_tokens_recomputed"] == span + m_short
    assert stats["prefill_tokens_skipped"] == m_long - span
    assert stats["prefill_tokens_total"] == sum(len(p) - 1 for p in prompts)
    assert stats["kv_prefix_hits"] == (m_long + m_short) // 4
    assert stats["kv_cow_forks"] == 0
    assert stats["kv_invariant_violations"] == 0
    assert stats["kv_window_pages_live"] == 0
    for key in ("prefill_tokens_matched", "prefill_tokens_recomputed",
                "prefill_tokens_skipped", "kv_prefix_hits"):
        assert plain[key] == 0


def test_evicting_a_shared_prefix_under_a_window_leaves_the_pool_sound():
    """A pool too small to keep every retired prompt: the tree's pages
    are evicted under pressure (the window space pins nothing), later
    requests match what is left or nothing, tokens stay those of the
    unshared run and the invariants hold."""
    cfg = _cfg()
    params = em.init(cfg, jax.random.key(SEED))["params"]
    base = _tokens(cfg, 80, seed=8).tolist()
    prompts = [base + _tokens(cfg, 20, seed=20 + i).tolist()
               if i % 2 == 0 else _tokens(cfg, 100, seed=40 + i).tolist()
               for i in range(5)]
    shared, stats = _serve(em, cfg, params, prompts, True, new=6, kv_pages=56)
    alone, _ = _serve(em, cfg, params, prompts, False, new=6, kv_pages=56)
    assert shared == alone
    assert stats["kv_prefix_evictions"] > 0
    assert stats["kv_prefix_hits"] > 0
    assert stats["kv_invariant_violations"] == 0
    assert stats["kv_window_pages_live"] == 0
    assert stats["kv_pages_free"] == 56


# ------------------------------------------------------------- the shares
def test_sixteen_shares_add_up_to_the_uncut_layer():
    """An expert layer of 16 experts, 4 a token, held one a chip by 16
    chips: every share's routed part, with the shared expert counted
    once, adds up to the reference's uncut layer; a share's own residual
    is the reference's share (the shared expert on every chip)."""
    cfg = _cfg()
    params = _init(cfg)
    stack = params["moe"]
    x = jax.random.normal(jax.random.key(5), (1, 24, cfg.dim))
    block = {name: leaf[0] for name, leaf in stack.items()}
    want = np.asarray(ref.experts(_ref_config(cfg), block, x[0], "highest"))
    total = np.zeros_like(want)
    for rank in range(16):
        share = dataclasses.replace(cfg, held_experts=(rank, 1))
        held = {**stack, **{name: stack[name][:, rank:rank + 1]
                            for name in ("w_gate", "w_up", "w_down")}}
        tokens = llama._norm(share, x, held["moe_norm"][0]).reshape(24, -1)
        routed, _ = moe.deepseek_routed_experts(share, held, 0, tokens,
                                                sequence=True)
        one_hot, _ = moe.deepseek_routed_experts(share, held, 0, tokens,
                                                 sequence=False)
        np.testing.assert_allclose(np.asarray(one_hot), np.asarray(routed),
                                   atol=2e-5, rtol=2e-5)
        total += np.asarray(routed)
        if rank in (0, 9):
            mine, _ = moe.deepseek_expert_block(share, held, 0, x)
            theirs = ref.experts(
                _ref_config(share),
                {**block, **{name: block[name][rank:rank + 1]
                             for name in ("w_gate", "w_up", "w_down")}},
                x[0], "highest")
            np.testing.assert_allclose(np.asarray(mine[0]),
                                       np.asarray(theirs), **TOL)
    shared_once = np.asarray(ref.experts(
        _ref_config(dataclasses.replace(cfg, held_experts=(0, 1))),
        {**block, **{name: jnp.zeros_like(block[name][:1])
                     for name in ("w_gate", "w_up", "w_down")}},
        x[0], "highest"))
    np.testing.assert_allclose(total + shared_once, want, **TOL)
