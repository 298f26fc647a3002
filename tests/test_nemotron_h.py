"""The Mamba-2 / attention / latent-expert decoder (models/nemotron_h.py)
against its plain reference (benchmark/reference/nemotron_h.py) at
`nemotron_h_tiny`, float32, seeded weights. Logits are compared, never
sampled tokens: with random weights the largest logit changes on
rounding.

Tolerances. Both sides compute in float32 on the CPU; they differ in
the order of their sums (the program's chunked scan, fused projections
and sorted or one-hot dispatch; the reference's sequential scan and
loops), so logits of size ~1 agree to a few 1e-5. `TOL` leaves a factor
of ten over that and is a thousand times under what a wrong state, row
or routing weight gives (1e-1 and up)."""

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

from reference import nemotron_h as ref  # noqa: E402

from polyaxon_tpu.models import moe, nemotron_h as nh, plan  # noqa: E402
from polyaxon_tpu.ops import mamba2  # noqa: E402
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine  # noqa: E402
from polyaxon_tpu.serving.paged import PagePool, page_bytes  # noqa: E402

TOL = dict(atol=3e-4, rtol=3e-4)
PAGE = 4
SEED = 5


def _cfg(**changes):
    return dataclasses.replace(nh.CONFIGS["nemotron_h_tiny"],
                               dtype=jnp.float32, **changes)


def _ref_config(cfg, rank=None) -> dict:
    """The tiny config under the published file's key names; with
    `rank`, the share of four chips that rank holds."""
    config = {
        "hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.conv_kernel, "chunk_size": cfg.chunk_size,
        "n_routed_experts": cfg.held[1],
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_latent_size": cfg.moe_latent_dim,
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "moe_shared_expert_intermediate_size": cfg.shared_ffn_dim,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "layer_norm_epsilon": cfg.norm_eps, "vocab_size": cfg.vocab_size,
        "hybrid_override_pattern": cfg.pattern,
        "num_hidden_layers": cfg.n_layers,
        "time_step_min": cfg.time_step_min,
        "time_step_max": cfg.time_step_max,
        "time_step_floor": cfg.time_step_floor, "torch_dtype": "float32"}
    if rank is not None:
        config["reduced"] = {"n_routed_experts": {"source": cfg.n_experts}}
        config["deployment"] = {"rank": rank}
    return config


def _init(cfg):
    """One jitted program, as `serving/server.py load_params` draws."""
    return jax.jit(lambda key: nh.init(cfg, key)["params"])(
        jax.random.key(SEED))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = _init(cfg)
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
    assert float(jnp.abs(params["ssm"]["D"] - 1).min()) > 0


def test_the_reference_holds_what_the_server_holds():
    """At bfloat16 the reference's weights are the server's own leaves:
    drawn at float32, rounded once, but for those read at float32."""
    from polyaxon_tpu.models.common import served_params

    cfg = dataclasses.replace(nh.CONFIGS["nemotron_h_tiny"])
    served = jax.jit(lambda key: served_params(
        nh.init(cfg, key)["params"], cfg.dtype, nh.READ_AT_FLOAT32))(
            jax.random.key(SEED))
    config = {**_ref_config(cfg), "torch_dtype": "bfloat16"}
    weights = ref.init_weights(config, cfg.n_layers, SEED)
    assert ref.FLOAT32 == set(nh.READ_AT_FLOAT32)
    for ours, theirs in zip(jax.tree.leaves(served), jax.tree.leaves(weights)):
        assert ours.dtype == theirs.dtype
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    assert weights["moe"]["w1"].dtype == jnp.bfloat16
    assert weights["ssm"]["A_log"].dtype == jnp.float32


def test_forward_matches_the_reference(model):
    cfg, params, config, weights = model
    tokens = jnp.asarray([_tokens(23), _tokens(23, 1)], jnp.int32)
    got = nh.forward(cfg, params, tokens)
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


@pytest.mark.parametrize("length", [1, 5, 8, 13, 16, 21])
def test_chunked_scan_matches_the_sequential_one(length):
    """`ssd_scan` at chunk 8 against `ssd_step` a position at a time,
    at lengths on and off the chunk's multiples, from a state that is
    not zero."""
    rng = np.random.default_rng(length)
    B, H, P, G, N = 2, 8, 4, 2, 16
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, b, c = draw(B, length, H, P), draw(B, length, G, N), draw(B, length, G, N)
    dt = jax.nn.softplus(draw(B, length, H))
    a = -jnp.exp(draw(H) * 0.5)
    state0 = draw(B, H, P, N)
    y, final = mamba2.ssd_scan(x, dt, a, b, c, 8, state0)
    state, want = state0, []
    for t in range(length):
        y_t, state = mamba2.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t],
                                     state)
        want.append(y_t)
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(want, 1)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged-dot", "pallas-kernel-interpreted"])
def test_sorted_dispatch_is_the_dense_one(model, monkeypatch, kernel):
    """Sorted pairs and grouped matmuls give what the one-hot buffers at
    the no-drop capacity give, for a share of the experts too: through
    ``ragged_dot``, this backend's, and through the chip's kernel."""
    monkeypatch.setattr(moe, "_grouped_kernel", lambda: kernel)
    cfg, params, _, _ = model
    stack = params["moe"]
    tokens = jnp.asarray(np.random.default_rng(2).normal(size=(37, cfg.dim)),
                         jnp.float32)
    for first, count in ((0, 16), (4, 4), (12, 4)):
        share = _cfg(held_experts=(first, count))
        part = {**stack, "w1": stack["w1"][:, first:first + count],
                "w2": stack["w2"][:, first:first + count]}
        dense, onehot = nh.routed_experts(share, part, 1, tokens,
                                          sequence=False)
        ragged, _ = nh.routed_experts(share, part, 1, tokens, sequence=True)
        np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)
        assert onehot.shape == (37, cfg.experts_per_token, count)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        model):
    """The test that ties the share to the model: each of four chips
    holds a quarter of the experts and routes over all of them; their
    routed parts, with what every chip computes alike (the shared
    expert) counted once, are the uncut reference's layer."""
    cfg, params, config, weights = model
    stack = params["moe"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 29, cfg.dim)),
                    jnp.float32)
    want = ref.expert_layer(config, ref._at(weights["moe"], 1), x[0],
                            "highest")
    tokens = nh.rms_norm(x, stack["moe_norm"][1], cfg.norm_eps)[0]
    quarter = cfg.n_experts // 4
    total = nh.shared_expert(cfg, stack, 1, tokens)
    for rank in range(4):
        first = rank * quarter
        share = _cfg(held_experts=(first, quarter))
        part = {**stack, "w1": stack["w1"][:, first:first + quarter],
                "w2": stack["w2"][:, first:first + quarter]}
        routed, _ = nh.routed_experts(share, part, 1, tokens, sequence=True)
        total = total + routed
        # ... and the reference, given the same share, the same part.
        theirs = ref.routed_part(
            _ref_config(share, rank), ref._at(part, 1), tokens, "highest")
        np.testing.assert_allclose(np.asarray(routed), np.asarray(theirs),
                                   **TOL)
    np.testing.assert_allclose(np.asarray(x[0] + total), np.asarray(want),
                               **TOL)


def test_a_share_serves_its_share(model):
    """A model that holds experts 4..7 of 16: forward against the
    reference given the same share, through the family's own init."""
    share = _cfg(held_experts=(4, 4))
    params = _init(share)
    config = _ref_config(share, rank=1)
    weights = ref.init_weights(config, share.n_layers, SEED)
    assert params["moe"]["w1"].shape[1] == 4
    assert params["moe"]["router"].shape[-1] == 16
    tokens = jnp.asarray([_tokens(19)], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(nh.forward(share, params, tokens)),
        np.asarray(ref.logits(config, weights, tokens)), **TOL)


def test_dense_cache_prefill_then_decode_matches_the_reference(model):
    cfg, params, config, weights = model
    tokens = jnp.asarray([_tokens(21), _tokens(21, 1)], jnp.int32)
    want = np.asarray(ref.logits(config, weights, tokens))
    logits, cache = nh.prefill(cfg, params, tokens[:, :13], 32)
    np.testing.assert_allclose(np.asarray(logits), want[:, 12], **TOL)
    for t in range(13, 21):
        logits, cache = nh.decode_step(cfg, params, cache, tokens[:, t], t)
        np.testing.assert_allclose(np.asarray(logits), want[:, t], **TOL)


def test_state_after_prefill_is_the_references(model):
    """What a row carries after a prompt: the reference's state after
    the last position and its convolution's last inputs, a layer."""
    cfg, params, config, weights = model
    prompt = jnp.asarray([_tokens(19)], jnp.int32)
    _, _, _, carried = plan.sequence_pass(nh.FAMILY, cfg, params, prompt)
    keep = {}
    ref.hidden(config, weights, prompt, keep=keep)
    for i in range(nh.kind_counts(cfg)["ssm"]):
        np.testing.assert_allclose(np.asarray(carried["ssm"][i]),
                                   np.asarray(keep["ssm"][i]), **TOL)
        np.testing.assert_allclose(np.asarray(carried["conv"][i]),
                                   np.asarray(keep["conv"][i]), **TOL)


class _Rows:
    """What the engine does on the device for an admission and a step,
    with the pool's own bookkeeping: the prefill (whole, or in padded
    chunks as the prefill lane runs it), then `decode_step_paged` a
    token at a time. Returns logits."""

    def __init__(self, cfg, params, slots=2, max_len=64, n_pages=40):
        self.cfg, self.params, self.slots = cfg, params, slots
        self.pool = PagePool(slots, max_len, PAGE, n_pages)
        self.cache = nh.paged_init_cache(cfg, n_pages, PAGE)
        self.cache["rows"] = nh.paged_init_rows(cfg, slots)
        assert page_bytes(self.cache, n_pages, PAGE)[2] > 0
        self.pool.match_nothing()
        self.pos = np.full(slots, -1, np.int32)
        self._step = jax.jit(lambda cache, tokens, pos, tables:
                             nh.decode_step_paged(cfg, params, cache, tokens,
                                                  pos, tables))

    def admit(self, b: int, prompt: list, chunk=None):
        res = self.pool.admit(b, len(prompt), prompt)
        assert res is not None and res.matched_tokens == 0
        toks = prompt[:-1]
        ids = jnp.asarray(self.pool.padded_row(b))
        row = jnp.int32(b)
        if chunk is None:
            self.cache = nh.paged_insert_prefill(
                self.cache, *nh.paged_prefill_kv(
                    self.cfg, self.params, jnp.asarray([toks], jnp.int32)),
                ids, PAGE, row)
        for i in range(0, len(toks), chunk) if chunk else ():
            piece = toks[i:i + chunk]
            padded = piece + [0] * (chunk - len(piece))
            pref = jnp.maximum(ids[:-(-i // PAGE)], 0)
            novel = nh.paged_prefill_suffix_kv(
                self.cfg, self.params, jnp.asarray([padded], jnp.int32),
                *nh.paged_gather_prefix(self.cache, pref, row),
                jnp.int32(i), jnp.int32(len(piece)))
            self.cache = nh.paged_insert_suffix(
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


def test_eviction_and_readmission_prefill_again(model):
    """A row evicted and admitted again, into a row another sequence
    used meanwhile, serves what the reference computes: nothing of the
    old state is resumed from, and nothing matches."""
    cfg, params, config, weights = model
    first, other = _tokens(20), _tokens(17, 3)
    want = np.asarray(ref.logits(config, weights,
                                 jnp.asarray([first], jnp.int32)))[0]
    rows = _Rows(cfg, params, slots=1)
    rows.admit(0, first[:11])
    for t in range(3):
        rows.step({0: first[10 + t]})
    rows.release(0)                              # evicted mid-generation
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
    assert held.shape == (2, 4) and elsewhere.shape == (2,)
    np.testing.assert_array_equal(
        held.sum(-1) + elsewhere, [3 * share.experts_per_token] * 2)
    assert elsewhere.min() > 0


class TestEngine:
    """Through `ContinuousBatchingEngine` as the server starts it."""

    def _engine(self, cfg, params, **kw):
        nh.CONFIGS["nemotron_h_tiny_f32"] = cfg
        kw.setdefault("slots", 2)
        return ContinuousBatchingEngine(
            "nemotron_h_tiny_f32", cfg, params, max_len=48, kv="paged",
            page_size=PAGE, **kw)

    def _greedy(self, config, weights, prompt, n):
        seq = list(prompt)
        for _ in range(n):
            logits = ref.logits(config, weights, jnp.asarray([seq], jnp.int32))
            seq.append(int(np.asarray(logits)[0, -1].argmax()))
        return seq[len(prompt):]

    def test_continuous_paged_serving_is_the_references_greedy(self, model):
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
        rows = nh.paged_init_rows(cfg, 1)
        per_slot = sum(leaf.size * leaf.dtype.itemsize
                       for leaf in rows.values())
        assert stats["kv_state_bytes_per_slot"] == per_slot
        assert stats["kv_state_bytes_per_page"] == 0
        assert stats["kv_invariant_violations"] == 0
        # Nothing is matched, nothing kept: a retired row's pages are free.
        assert stats["prefill_tokens_skipped"] == 0
        assert stats["kv_pages_free"] == stats["kv_pages_total"]
        assert len(stats["moe_expert_tokens"]) == 2
        assert len(stats["moe_pairs_elsewhere"]) == 2

    def test_a_repeated_prompt_matches_nothing(self, model):
        cfg, params, config, weights = model
        prompt = _tokens(21)
        engine = self._engine(cfg, params)
        try:
            first = engine.generate([prompt], max_new_tokens=4, timeout=600)
            again = engine.generate([prompt], max_new_tokens=4, timeout=600)
            stats = engine.stats()
        finally:
            engine.stop()
        assert first == again == [self._greedy(config, weights, prompt, 4)]
        assert stats["prefill_tokens_skipped"] == 0
        assert stats["kv_radix"]["pages"] == 0

    def test_the_prefill_lane_hands_the_rows_state_over(self, model):
        """Admissions land on a lane row, stream in padded chunks
        behind their own state, and the state follows the pages to the
        decode slot."""
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

    def test_dense_and_paged_engines_agree(self, model):
        cfg, params, _, _ = model
        prompt = _tokens(11)
        nh.CONFIGS["nemotron_h_tiny_f32"] = cfg
        outs = []
        for kv in ("dense", "paged"):
            engine = ContinuousBatchingEngine(
                "nemotron_h_tiny_f32", cfg, params, slots=2, max_len=32,
                kv=kv, page_size=PAGE)
            try:
                outs.append(engine.generate([prompt], max_new_tokens=5,
                                            timeout=300))
            finally:
                engine.stop()
        assert outs[0] == outs[1]

    @pytest.mark.parametrize("kw, says", [
        (dict(kv="dense", prefill_chunk=4), "decode_chunk"),
        (dict(kv="dense", draft=("llama_tiny", None, None, 2)),
         "decode_chunk"),
    ])
    def test_the_engine_refuses_what_the_family_lacks(self, model, kw, says):
        """Speculation and dense chunked prefill need a state that
        rolls back: refused by the missing surface, not by a name."""
        cfg, params, _, _ = model
        nh.CONFIGS["nemotron_h_tiny_f32"] = cfg
        assert not hasattr(nh, "decode_chunk")
        with pytest.raises(ValueError, match=says):
            ContinuousBatchingEngine("nemotron_h_tiny_f32", cfg, params,
                                     slots=2, max_len=32, **kw)


def test_training_loss_and_gradients_are_finite(model):
    cfg, params, _, _ = model
    tokens = jnp.asarray([_tokens(16), _tokens(16, 1)], jnp.int32)

    def loss(p):
        return nh.apply(cfg, {"params": p, "state": {}},
                        {"tokens": tokens})[0]

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert float(jnp.linalg.norm(grads["moe"]["w1"])) > 0
    assert float(jnp.linalg.norm(grads["ssm"]["A_log"])) > 0


def test_family_is_registered():
    from polyaxon_tpu import models

    assert nh in models.FAMILIES
    assert models.family_of("nemotron_h_tiny") is nh
    assert models.config_of("nemotron3_super_120b_a12b").n_layers == 88
    published = nh.CONFIGS["nemotron3_super_120b_a12b"]
    assert nh.kind_counts(published) == {"ssm": 40, "attn": 8, "moe": 40}
    assert published.pattern[25:36] == "*EMEMEMEMEM"
