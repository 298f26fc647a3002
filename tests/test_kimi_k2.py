"""The latent-attention decoder with sigmoid-routed experts
(models/kimi_k2.py) against its plain reference
(benchmark/reference/kimi_k2.py) at `kimi_k2_tiny` (hidden 64, 4 heads of
16 + 8 | 16, latent 32 + 8 padded to 128, one dense layer and two expert
layers of 16 experts of width 32, 4 a token, vocabulary 256), seeded
weights whose norm gains are moved off the identity so that each shows.
Logits are compared, never sampled tokens.

Tolerances. In float32 both sides compute on the CPU and differ in the
order of their sums (the absorbed form against the up-projected one, a
block-wise online softmax against full rows, the one-hot or sorted
dispatch against a loop over experts), so logits of size ~1 agree to a
few 1e-5: `TOL` leaves a factor of ten over that and is a thousand
times under what a wrong mask, rotation, scale, routing weight or page
gives (1e-1 and up). In bfloat16 the program rounds every projection's
product to 8 bits of mantissa where the reference keeps float32 (the
weights themselves are the same bfloat16 values on both sides): logits
of size ~1 then differ by a few 1e-2, and `TOL_BF16` is held against the
largest entry of 512 (a few 1e-2 in the mean, up to 0.13 at the worst
entry seen), while a fault moves most entries: the mean is held too."""

import dataclasses
import math
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

from reference import kimi_k2 as ref  # noqa: E402

from polyaxon_tpu.models import common, kimi_k2 as k2, llama  # noqa: E402
from polyaxon_tpu.ops.mla_decode import (  # noqa: E402
    mla_decode_attention, mla_decode_reference)
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine  # noqa: E402
from polyaxon_tpu.serving.paged import PagePool, page_bytes  # noqa: E402

TOL = dict(atol=3e-4, rtol=3e-4)
TOL_BF16 = dict(atol=0.25, rtol=0.05)
MEAN_BF16 = 0.04
SEED = 7
PAGE = 8


def _cfg(**changes):
    return dataclasses.replace(k2.CONFIGS["kimi_k2_tiny"],
                               dtype=jnp.float32, **changes)


def _ref_config(cfg, dtype="float32") -> dict:
    """The tiny config under the published file's key names."""
    first, count = cfg.held
    config = {
        "hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": {k: v for k, v in cfg.rope_scaling.items()},
        "intermediate_size": cfg.ffn_dim,
        "first_k_dense_replace": cfg.first_dense,
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "n_routed_experts": count,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": True, "rms_norm_eps": cfg.norm_eps,
        "vocab_size": cfg.vocab_size, "torch_dtype": dtype}
    if cfg.held_experts:
        config["reduced"] = {"n_routed_experts": {"source": cfg.n_experts}}
        config["deployment"] = {"rank": first // count}
    return config


def _init(cfg):
    """The program's seeded weights, norm gains drawn off the identity;
    the reference's tree is the same arrays."""
    params = k2.init(cfg, jax.random.key(SEED))["params"]
    keys = iter(jax.random.split(jax.random.key(SEED + 1), 6))

    def off(leaf):
        return 1.0 + 0.2 * jax.random.normal(next(keys), leaf.shape)

    for stack, name in (("attn", "attn_norm"), ("attn", "q_norm"),
                        ("attn", "kv_norm"), ("dense", "mlp_norm"),
                        ("moe", "moe_norm")):
        params[stack][name] = off(params[stack][name])
    params["final_norm"] = off(params["final_norm"])
    return params


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = _init(cfg)
    tokens = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (2, 72)), jnp.int32)
    want = np.asarray(ref.logits(_ref_config(cfg), params, tokens))
    return cfg, params, tokens, want


def _paged(cfg, n_pages=40):
    return k2.paged_init_cache(cfg, n_pages, PAGE)


def _row(n_pages_used, first=1, width=12):
    ids = np.full((width,), -1, np.int32)
    ids[:n_pages_used] = np.arange(first, first + n_pages_used)
    return ids


# ------------------------------------------------------- weights and rule
def test_reference_draws_the_programs_weights():
    cfg = _cfg(held_experts=(4, 4))
    mine = k2.init(cfg, jax.random.key(SEED))["params"]
    theirs = ref.init_weights(_ref_config(cfg), cfg.n_layers, SEED)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_yarn_frequencies_and_scale_at_the_published_rule():
    """Closed forms at theta 50,000 over 64 rotary dims, factor 64, an
    original context of 4,096, beta 32 / 1: the ramp runs from pair 8 to
    pair 20 (d(32) = 8.91, d(1) = 19.16); pairs 0..8 turn as published,
    pairs 20..31 sixty-four times slower, pair 14 halfway; the softmax
    scale is 192^-0.5 (0.1 ln 64 + 1)^2."""
    rule = k2.CONFIGS["kimi_k2_6"].rope_scaling
    got = np.asarray(common.yarn_frequencies(32, 50_000.0, rule), np.float64)
    plain = 50_000.0 ** (-np.arange(32) / 32)
    d = lambda n: 32 * math.log(4096 / (2 * math.pi * n)) / math.log(5e4)
    assert (math.floor(d(32)), math.ceil(d(1))) == (8, 20)
    np.testing.assert_allclose(got[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(got[20:], plain[20:] / 64, rtol=1e-6)
    np.testing.assert_allclose(got[14], plain[14] * (0.5 + 0.5 / 64),
                               rtol=1e-6)
    assert np.all(np.diff(got) < 0)
    np.testing.assert_allclose(
        got, np.asarray(ref.yarn_frequencies({
            "rope_scaling": rule, "rope_theta": 50_000,
            "qk_rope_head_dim": 64})), rtol=1e-6)
    # The rule by name through the tree's one entry point.
    np.testing.assert_array_equal(
        np.asarray(common.rope_frequencies(32, 50_000.0, rule)),
        np.asarray(common.yarn_frequencies(32, 50_000.0, rule)))
    scale = k2.CONFIGS["kimi_k2_6"].softmax_scale
    assert abs(scale - 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2) < 1e-12
    assert round(scale, 5) == 0.14468
    assert common.yarn_softmax_scale(192, None) == 192 ** -0.5
    with pytest.raises(ValueError, match="mscale"):
        common.yarn_softmax_scale(192, {**rule, "mscale": 0.7})


# ------------------------------------------------------------ the forward
def test_forward_matches_the_reference(model):
    cfg, params, tokens, want = model
    got = np.asarray(k2.forward(cfg, params, tokens))
    np.testing.assert_allclose(got, want, **TOL)


def test_a_rotation_and_the_scale_show_in_the_logits(model):
    """What `TOL` is held against: the same pass with the rotary rule's
    factor changed, and with the bias left out of the choice."""
    cfg, params, tokens, want = model
    turned = np.asarray(k2.forward(
        dataclasses.replace(cfg, rope_factor=1.0), params, tokens))
    assert np.abs(turned - want).max() > 0.05
    unbiased = {**params, "moe": {
        **params["moe"],
        "expert_bias": jnp.zeros_like(params["moe"]["expert_bias"])}}
    assert np.abs(np.asarray(k2.forward(cfg, unbiased, tokens))
                  - want).max() > 0.05


@pytest.mark.parametrize("dtype,tol", [("float32", TOL),
                                       ("bfloat16", TOL_BF16)])
def test_paged_prefill_then_decode_matches_the_reference(model, dtype, tol):
    """A prompt through the whole-prompt prefill into pages, then one
    position at a time through the absorbed decode over them, both
    against the reference's one full pass; in bfloat16 with the weights
    held as a server holds them (`served_params`) and the reference
    handed the same rounded values."""
    cfg, params, tokens, want = model
    if dtype == "bfloat16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        served = common.served_params(params, cfg.dtype, k2.READ_AT_FLOAT32,
                                      k2.HELD_TRANSPOSED)
        rounded = common.served_params(params, cfg.dtype, k2.READ_AT_FLOAT32)
        want = np.asarray(ref.logits(_ref_config(cfg, "bfloat16"), rounded,
                                     tokens))
        params = served
    P = 45
    cache = _paged(cfg)
    tables = np.stack([_row(9, 1), _row(9, 20)])
    for b in range(2):
        cache = k2.paged_insert_prefill(
            cache, *k2.paged_prefill_kv(cfg, params, tokens[b:b + 1, :P]),
            jnp.asarray(tables[b]), PAGE)
    step = jax.jit(lambda c, t, p: k2.decode_step_paged(
        cfg, params, c, t, p, jnp.asarray(tables)))
    for t in range(P, 60):
        logits, cache = step(cache, tokens[:, t], jnp.full((2,), t))
        np.testing.assert_allclose(np.asarray(logits), want[:, t], **tol)
        if dtype == "bfloat16":
            assert np.abs(np.asarray(logits) - want[:, t]).mean() < MEAN_BF16
    live = sum(2 * (t + 1) for t in range(P, 60))
    high, low = np.asarray(cache["mla_decode_positions"]).tolist()
    assert (high << 30) + low == live
    # Per token per layer the pool holds the padded latent, nothing else.
    per_token, state, row = page_bytes(cache, 40, PAGE)
    assert (per_token, state, row) == (
        PAGE * cfg.n_layers * cfg.latent_pad * cache["latent"].dtype.itemsize,
        0, 0)
    assert (cfg.latent_width, cfg.latent_pad) == (40, 128)


def test_the_counter_of_positions_carries_past_thirty_bits():
    counter = jnp.asarray([3, (1 << 30) - 5], jnp.int32)
    got = k2._count_positions(counter, jnp.asarray([6, -1, 1]))
    assert np.asarray(got).tolist() == [4, 4]


def test_dense_prefill_then_decode_matches_the_reference(model):
    cfg, params, tokens, want = model
    P = 40
    logits, cache = k2.prefill(cfg, params, tokens[:, :P], 96)
    np.testing.assert_allclose(np.asarray(logits), want[:, P - 1], **TOL)
    step = jax.jit(lambda c, t, p: k2.decode_step_ragged(cfg, params, c, t, p))
    for t in range(P, 48):
        logits, cache = step(cache, tokens[:, t], jnp.full((2,), t))
        np.testing.assert_allclose(np.asarray(logits), want[:, t], **TOL)


# ------------------------------------- absorbed against up-projected forms
def test_absorbed_attention_is_the_up_projected_one(model):
    """One layer's attention over a sequence both ways: behind nothing
    (up-projected, every head's keys and values made) and as a suffix
    behind the first 24 positions' cached latents (absorbed, in blocks),
    the latents and the residual the same."""
    cfg, params, tokens, _ = model
    layer = {name: leaf[1] for name, leaf in params["attn"].items()}
    x = jax.random.normal(jax.random.key(3), (1, 56, cfg.dim))
    positions = jnp.arange(56)[None]
    h = llama._norm(cfg, x, layer["attn_norm"])
    q_nope, q_pe = k2._queries(cfg, layer, h, positions)
    latent = k2._latent(cfg, layer, h, positions)
    whole = k2._attend_whole(cfg, layer, x, q_nope, q_pe, latent)
    m = 24
    prefix = jnp.pad(latent[:, :m], ((0, 0), (0, 8), (0, 0)))  # whole pages
    prefix = prefix.at[:, m:].set(7.0)            # what is masked is not read
    old = k2.KEY_BLOCK
    k2.KEY_BLOCK = 16                             # several turns at this size
    try:
        behind = k2._attend_behind(
            cfg, layer, x[:, m:], q_nope[:, m:], q_pe[:, m:], latent[:, m:],
            prefix, jnp.int32(m))
    finally:
        k2.KEY_BLOCK = old
    np.testing.assert_allclose(np.asarray(behind), np.asarray(whole[:, m:]),
                               atol=2e-5, rtol=2e-5)


def test_suffix_prefill_behind_cached_pages_is_the_whole_prompt_prefill(model):
    """The pages and the logits: a prompt prefilled whole, and the same
    prompt as its first three pages prefilled, then a padded suffix
    behind them (a bucket of 32 holding 21 real tokens)."""
    cfg, params, tokens, want = model
    P, m = 45, 24
    ids = jnp.asarray(_row(8))
    whole = k2.paged_insert_prefill(
        _paged(cfg), *k2.paged_prefill_kv(cfg, params, tokens[:1, :P]), ids,
        PAGE)
    cache = k2.paged_insert_prefill(
        _paged(cfg), *k2.paged_prefill_kv(cfg, params, tokens[:1, :m]), ids,
        PAGE)
    suffix = jnp.pad(tokens[:1, m:P], ((0, 0), (0, 32 - (P - m))))
    novel = k2.paged_prefill_suffix_kv(
        cfg, params, suffix, *k2.paged_gather_prefix(cache, ids[:m // PAGE]),
        jnp.int32(m))
    cache = k2.paged_insert_suffix(cache, *novel, ids, jnp.int32(m), PAGE,
                                   jnp.int32(P - m))
    got = llama.paged_gather(cache["latent"], ids[:6])[:, :P]
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(llama.paged_gather(whole["latent"], ids[:6])[:, :P]),
        atol=2e-5, rtol=2e-5)
    # Nothing of the padding was written: the page past the prompt.
    assert not np.asarray(cache["latent"][:, int(ids[6])]).any()
    tables = ids[None]
    for t in range(P, 50):
        logits, cache = k2.decode_step_paged(
            cfg, params, cache, tokens[:1, t], jnp.asarray([t]), tables)
        np.testing.assert_allclose(np.asarray(logits), want[:1, t], **TOL)


# ------------------------------------------------------------- the shares
def test_thirty_two_shares_add_up_to_the_uncut_layer():
    """An expert layer of 32 experts, 4 a token, held one a chip by 32
    chips: every share's routed part, with the shared expert counted
    once, adds up to the reference's uncut layer; each share's own
    residual is the reference's share (the shared expert on every
    chip)."""
    cfg = _cfg(n_experts=32, n_layers=2)
    params = _init(cfg)
    stack = params["moe"]
    x = jax.random.normal(jax.random.key(5), (1, 24, cfg.dim))
    uncut_config = _ref_config(cfg)
    block = {name: leaf[0] for name, leaf in stack.items()}
    want = np.asarray(ref.experts(uncut_config, block, x[0], "highest"))
    total = np.zeros_like(want)
    for rank in range(32):
        share = dataclasses.replace(cfg, held_experts=(rank, 1))
        held = {**stack, **{name: stack[name][:, rank:rank + 1]
                            for name in ("w_gate", "w_up", "w_down")}}
        tokens = llama._norm(share, x, held["moe_norm"][0]).reshape(24, -1)
        routed, _ = k2.routed_experts(share, held, 0, tokens, sequence=True)
        one_hot, _ = k2.routed_experts(share, held, 0, tokens,
                                       sequence=False)
        np.testing.assert_allclose(np.asarray(one_hot), np.asarray(routed),
                                   atol=2e-5, rtol=2e-5)
        total += np.asarray(routed)
        if rank in (0, 17):
            mine, _ = k2.expert_block(share, held, 0, x)
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


# -------------------------------------------------------------- the kernel
# name -> (page, table width, each row's position (-1 = idle), holes as
# (row, table entry)). At a page of 16 a sub-block is 512 keys and a
# turn 1,024 (64 pages): the rows below end inside a sub-block, on its
# edge and on a turn's; the longer ones have whole turns with a whole
# turn two on (the straight line, with the mask and without); the
# copies begun two turns ahead run into the next row's first turns,
# past an idle row or into none.
MLA_KERNEL_CASES = {
    # Rows of unequal length, an idle row, a hole inside a live range, a
    # row that fills its table, which is narrower than a sub-block (the
    # one sub-block of its width is a turn).
    "table-narrower-than-a-sub-block-a-hole-a-full-row": (
        PAGE, 20, [37, -1, 159, 3, 70], [(2, 3)]),
    "ends-inside-a-sub-block-on-its-edge-on-a-turns-edge": (
        16, 330, [300, 511, 512, 1023, 1024, 4095], []),
    "shorter-than-a-turn-beside-several-turns": (
        16, 330, [100, 5000, 5, 4200, 0, 5279], []),
    "idle-row-between-live-ones-and-last": (
        16, 330, [4500, -1, 2100, -1, -1, 40], []),
    "idle-rows-first-and-all-but-one": (
        16, 330, [-1, -1, -1, 3290, -1, -1], []),
    "hole-inside-a-whole-turn": (
        16, 330, [5000, 1500, 2100, 1023, 2047, 300],
        [(0, 10), (0, 70), (0, 200), (1, 63), (2, 64), (2, 127), (4, 0)]),
    # Two sub-blocks cover a table of 40 pages: the turn is wider.
    "table-narrower-than-a-turn": (
        16, 40, [639, 17, -1, 400, 511], [(0, 20)]),
    "table-of-one-page": (16, 1, [15, -1, 0, 7, 3], []),
}


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("case", MLA_KERNEL_CASES)
def test_mla_decode_kernel_under_interpret_matches_jnp(case, layer):
    """The streamed loop against the same sums in plain ``jnp``, at the
    published widths' tiling (a latent of 256, values over its first
    128), over what its schedule has to get right (MLA_KERNEL_CASES)."""
    page, maxp, pos, holes = MLA_KERNEL_CASES[case]
    rng = np.random.default_rng(layer)
    L, W, C, H, B = 2, 256, 128, 8, len(pos)
    pos = np.array(pos, np.int32)
    tables = np.full((B, maxp), -1, np.int32)
    P = 1 + B * maxp              # by the shape: the cases share a program
    ids = iter(rng.permutation(P - 1) + 1)         # scattered; 0 is no row's
    for b in range(B):
        for p in range(pos[b] // page + 1 if pos[b] >= 0 else 0):
            tables[b, p] = next(ids)
    for b, p in holes:
        tables[b, p] = -1
    pool = jnp.asarray(rng.normal(size=(L, P, 1, page, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    args = (q, pool, layer, jnp.asarray(tables), jnp.asarray(pos))
    got = mla_decode_attention(*args, scale=0.1, value_width=C,
                               interpret=True)
    want = mla_decode_reference(*args, scale=0.1, value_width=C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    assert not np.asarray(got)[pos < 0].any()     # an idle row: zeros
    if holes:
        # A hole's page is masked: other contents there, same answer.
        moved = pool.at[layer, 0].set(9.0)        # holes clip to page 0
        again = mla_decode_attention(q, moved, *args[2:], scale=0.1,
                                     value_width=C, interpret=True)
        np.testing.assert_allclose(np.asarray(again), np.asarray(got),
                                   atol=1e-6)


def test_mla_decode_takes_whole_lane_tiles_only():
    q, pool = jnp.zeros((2, 8, 40)), jnp.zeros((1, 4, 1, PAGE, 40))
    with pytest.raises(ValueError, match="whole lane tiles"):
        mla_decode_attention(q, pool, 0, jnp.zeros((2, 3), jnp.int32),
                             jnp.zeros((2,), jnp.int32), scale=0.1,
                             value_width=32, interpret=True)


# -------------------------------------------------------------- the engine
def _engine(cfg, params, name="kimi_k2_tiny_f32", **kw):
    k2.CONFIGS[name] = cfg
    return ContinuousBatchingEngine(name, cfg, params, slots=2, kv="paged",
                                    page_size=PAGE, kv_pages=64, max_len=96,
                                    **kw)


def test_engine_shares_a_page_aligned_prefix_between_two_requests(model):
    """Two requests behind the same four pages: the second skips them
    (a suffix prefill behind the cached latents) and both give the tokens
    of runs that share nothing; a third that diverges inside a page forks
    it copy-on-write with the counters beside the pool left alone."""
    cfg, params, tokens, _ = model
    cfg = dataclasses.replace(cfg, held_experts=(4, 8))
    params = {**params, "moe": {**params["moe"], **{
        name: params["moe"][name][:, 4:12]
        for name in ("w_gate", "w_up", "w_down")}}}
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, 256, 32).tolist()
    a = prefix + rng.integers(0, 256, 9).tolist()
    b = prefix + rng.integers(0, 256, 5).tolist()
    c = a[:35] + [7, 7, 7]

    def alone(prompt):
        engine = _engine(cfg, params, prefix_cache=False)
        try:
            return engine.generate([prompt], 6)[0]
        finally:
            engine.stop()

    engine = _engine(cfg, params)
    try:
        assert type(engine._pool) is PagePool and engine._pool.prefix_cache
        assert engine._window_tables is None
        got = [engine.generate([p], 6)[0] for p in (a, b, c)]
        stats = engine.stats()
    finally:
        engine.stop()
    assert got == [alone(a), alone(b), alone(c)]
    assert stats["prefill_tokens_skipped"] == 32 + 35
    assert stats["kv_cow_forks"] == 1
    assert stats["kv_invariant_violations"] == 0
    assert stats["kv_token_bytes"] == cfg.latent_pad * 4 * cfg.n_layers
    assert stats["kv_page_bytes"] == PAGE * stats["kv_token_bytes"]
    assert stats["kv_state_bytes_per_slot"] == 0
    # Every decode step's live rows' positions: six steps a request,
    # the first at the prompt's last token.
    assert stats["mla_decode_positions"] == sum(
        len(p) + i for p in (a, b, c) for i in range(6))
    # The shape the kernel's loop runs at over this engine's tables: 12
    # pages of 8 a row (one sub-block of the table's width a turn).
    assert stats["mla_decode_keys_per_turn"] == 96
    assert stats["mla_decode_turns_in_flight"] == 2
    held = np.asarray(stats["moe_expert_tokens"])
    assert held.shape == (2, 8)
    assert int(held.sum()) + sum(stats["moe_pairs_elsewhere"]) == 2 * 4 * 18


def test_llama_engines_stats_gain_the_token_bytes_and_nothing_else():
    cfg = llama.CONFIGS["llama_tiny"]
    params = llama.init(cfg, jax.random.key(0))["params"]
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2,
                                      kv="paged", page_size=4, kv_pages=32)
    try:
        engine.generate([[1, 2, 3, 4, 5]], 4)
        stats = engine.stats()
    finally:
        engine.stop()
    assert stats["kv_token_bytes"] * 4 == stats["kv_page_bytes"]
    assert not [name for name in stats if name.startswith("mla_decode")]
