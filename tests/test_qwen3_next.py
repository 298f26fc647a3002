"""The Gated DeltaNet / gated attention / routed SwiGLU decoder
(models/qwen3_next.py, ops/gated_delta.py) against its plain reference
(benchmark/reference/qwen3_next.py) at `qwen3_next_tiny`, float32,
seeded weights whose norm gains are moved off the identity so that each
shows. Logits are compared, never sampled tokens: with random weights
the largest logit changes on rounding.

Tolerances. Both sides compute in float32 on the CPU; they differ in
the order of their sums (the program's chunked form with its triangular
solve, fused projections and sorted or one-hot dispatch; the
reference's sequential recurrence and loops), so logits of size ~1
agree to a few 1e-5. `TOL` leaves a factor of ten over that and is a
thousand times under what a wrong state, row, gate or routing weight
gives (1e-1 and up)."""

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

from reference import qwen3_next as ref  # noqa: E402

from polyaxon_tpu.models import (  # noqa: E402
    lfm2, llama, moe, nemotron_h, plan)
from polyaxon_tpu.models import qwen3_next as qn  # noqa: E402
from polyaxon_tpu.models.common import _w, rope  # noqa: E402
from polyaxon_tpu.ops import gated_delta  # noqa: E402

TOL = dict(atol=3e-4, rtol=3e-4)
SEED = 5


def _cfg(**changes):
    return dataclasses.replace(qn.CONFIGS["qwen3_next_tiny"],
                               dtype=jnp.float32, **changes)


def _ref_config(cfg, rank=None) -> dict:
    """The tiny config under the published file's key names; with
    `rank`, the share of four chips that rank holds."""
    config = {
        "hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_theta": cfg.rope_theta,
        "full_attention_interval": cfg.full_attention_interval,
        "linear_num_key_heads": cfg.gdn_key_heads,
        "linear_num_value_heads": cfg.gdn_value_heads,
        "linear_key_head_dim": cfg.gdn_key_dim,
        "linear_value_head_dim": cfg.gdn_value_dim,
        "linear_conv_kernel_dim": cfg.conv_kernel,
        "num_experts": cfg.held[1],
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_intermediate_size": cfg.moe_ffn_dim,
        "shared_expert_intermediate_size": cfg.shared_ffn_dim,
        "norm_topk_prob": True, "rms_norm_eps": cfg.norm_eps,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.n_layers,
        "torch_dtype": "float32"}
    if rank is not None:
        config["reduced"] = {"num_experts": {"source": cfg.n_experts}}
        config["deployment"] = {"rank": rank}
    return config


def _moved(tree: dict) -> dict:
    """Every norm gain moved off the identity by a seeded draw, the same
    for the program's tree and the reference's (they are one tree)."""
    def move(path, leaf):
        name = path[-1].key
        if not name.endswith("_norm"):
            return leaf
        key = jax.random.fold_in(jax.random.key(11), sum(map(ord, name)))
        return leaf + 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_map_with_path(move, tree)


def _init(cfg):
    """One jitted program, as `serving/server.py load_params` draws."""
    return jax.jit(lambda key: qn.init(cfg, key)["params"])(
        jax.random.key(SEED))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    config = _ref_config(cfg)
    return (cfg, _moved(_init(cfg)), config,
            _moved(ref.init_weights(config, cfg.n_layers, SEED)))


def _tokens(n: int, seed: int = 0) -> list:
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def test_reference_weights_are_the_programs_bit_for_bit(model):
    cfg, params, config, weights = model
    drawn, theirs = _init(cfg), ref.init_weights(config, cfg.n_layers, SEED)
    assert jax.tree.structure(drawn) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Every term shows: gains off the identity, decays and steps spread.
    assert float(jnp.abs(params["attn"]["q_norm"]).min()) > 0
    assert float(jnp.abs(params["gdn"]["out_norm"] - 1).min()) > 0
    assert float(jnp.std(params["gdn"]["A_log"])) > 0.1
    assert float(jnp.std(params["gdn"]["dt_bias"])) > 0.1
    assert qn.layer_plan(cfg) == (("gdn", 0), ("gdn", 1), ("gdn", 2),
                                  ("attn", 0))


def test_the_reference_holds_what_the_server_holds():
    """At bfloat16 the reference's weights are the server's own leaves:
    drawn at float32, rounded once, but for those read at float32."""
    from polyaxon_tpu.models.common import served_params

    cfg = qn.CONFIGS["qwen3_next_tiny"]
    served = jax.jit(lambda key: served_params(
        qn.init(cfg, key)["params"], cfg.dtype, qn.READ_AT_FLOAT32))(
            jax.random.key(SEED))
    config = {**_ref_config(cfg), "torch_dtype": "bfloat16"}
    weights = ref.init_weights(config, cfg.n_layers, SEED)
    assert ref.FLOAT32 == set(qn.READ_AT_FLOAT32)
    assert ref.TIME_STEP == qn.TIME_STEP
    for ours, theirs in zip(jax.tree.leaves(served), jax.tree.leaves(weights)):
        assert ours.dtype == theirs.dtype
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    assert weights["moe"]["w_gate"].dtype == jnp.bfloat16
    assert weights["gdn"]["A_log"].dtype == jnp.float32


def test_forward_matches_the_reference(model):
    cfg, params, config, weights = model
    tokens = jnp.asarray([_tokens(29), _tokens(29, 1)], jnp.int32)
    want = np.asarray(ref.logits(config, weights, tokens))
    got = np.asarray(qn.forward(cfg, params, tokens))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, **TOL)


# ------------------------------------------------------ the delta rule
def _rule_inputs(B=2, S=21, H=4, dk=8, dv=8, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v = unit(f(B, S, H, dk)) * dk ** -0.5, unit(f(B, S, H, dk)), \
        f(B, S, H, dv)
    g = -jnp.asarray(rng.uniform(0.01, 1.5, (B, S, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.95, (B, S, H)), jnp.float32)
    return q, k, v, g, beta, f(B, H, dk, dv)


def _sequential(q, k, v, g, beta, state):
    """The recurrence as the equations give it, a position at a time:
    decay, read, write, read."""
    outs = []
    for t in range(q.shape[1]):
        state = jnp.exp(g[:, t])[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k[:, t])
        write = beta[:, t][..., None] * (v[:, t] - read)
        state = state + k[:, t][..., :, None] * write[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("S, chunk", [(21, 8), (16, 8), (5, 8), (64, 64)])
def test_chunked_form_is_the_sequential_recurrence(S, chunk):
    """With a carried state and lengths that are and are not multiples
    of the chunk."""
    q, k, v, g, beta, state0 = _rule_inputs(S=S)
    want, final = _sequential(q, k, v, g, beta, state0)
    got, state = gated_delta.chunked(q, k, v, g, beta, chunk, state0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final),
                               atol=2e-5, rtol=2e-5)
    fresh, _ = gated_delta.chunked(q, k, v, g, beta, chunk)
    zero, _ = _sequential(q, k, v, g, beta, jnp.zeros_like(state0))
    np.testing.assert_allclose(np.asarray(fresh), np.asarray(zero),
                               atol=2e-5, rtol=2e-5)


def test_step_after_chunked_is_chunked_over_one_more_token():
    q, k, v, g, beta, state0 = _rule_inputs(S=20)
    whole, final = gated_delta.chunked(q, k, v, g, beta, 8, state0)
    _, before = gated_delta.chunked(q[:, :-1], k[:, :-1], v[:, :-1],
                                    g[:, :-1], beta[:, :-1], 8, state0)
    o, state = gated_delta.step(q[:, -1], k[:, -1], v[:, -1], g[:, -1],
                                beta[:, -1], before)
    np.testing.assert_allclose(np.asarray(o), np.asarray(whole[:, -1]),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final),
                               atol=2e-5, rtol=2e-5)


def test_padding_past_real_len_leaves_the_state_alone(model):
    """The mixer over a padded piece gives, for its real positions and
    for what the row carries on, what the unpadded piece gives."""
    cfg, params, _, _ = model
    layer = plan._at(params["gdn"], 1)
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(1, 16, cfg.dim)), jnp.float32)
    carried = qn.init_rows(cfg, 1)
    tail0 = jnp.asarray(rng.normal(size=carried["conv"][0].shape), jnp.float32)
    state0 = jnp.asarray(rng.normal(size=carried["gdn"][0].shape), jnp.float32)
    want, tail, state = gated_delta.mixer(cfg, layer, u[:, :11], tail0, state0)
    got, tail_p, state_p = gated_delta.mixer(cfg, layer, u, tail0, state0,
                                             jnp.int32(11))
    np.testing.assert_allclose(np.asarray(got[:, :11]), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(state_p), np.asarray(state),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(tail_p), np.asarray(tail))


def test_state_after_prefill_is_the_references(model):
    cfg, params, config, weights = model
    prompt = jnp.asarray([_tokens(19)], jnp.int32)
    _, _, _, carried = plan.sequence_pass(qn.FAMILY, cfg, params, prompt)
    keep = {}
    ref.hidden(config, weights, prompt, keep=keep)
    for i in range(qn.kind_counts(cfg)["gdn"]):
        np.testing.assert_allclose(np.asarray(carried["gdn"][i]),
                                   np.asarray(keep["gdn"][i]), **TOL)
        np.testing.assert_allclose(np.asarray(carried["conv"][i]),
                                   np.asarray(keep["conv"][i]), **TOL)


# ------------------------------------------------- the gated attention
def test_gate_partial_rotary_and_qk_norms_against_the_references_attention(
        model):
    """llama's suffix walk with this family's layer (a wide ``wq``, a
    rotary embedding over a quarter of the head, gains as 1 + w) is the
    reference's attention layer; and each of the three shows."""
    cfg, params, config, weights = model
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 23, cfg.dim)),
                    jnp.float32)
    layer = plan._at(params["attn"], 0)
    positions = jnp.arange(23, dtype=jnp.int32)[None]
    empty = jnp.zeros((1, 0, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    valid = llama._suffix_mask(23, 0, 0)

    def walk(cfg, layer):
        return np.asarray(llama.suffix_attn_step(
            cfg, layer, x, empty, empty, positions, valid)[0])

    want = np.asarray(ref.attention(config, ref._at(weights["attn"], 0),
                                    x[0], "highest"))
    got = walk(cfg, layer)
    np.testing.assert_allclose(got[0], want, **TOL)
    whole = dataclasses.replace(cfg, partial_rotary_factor=1.0)
    plain = dataclasses.replace(cfg, norm_offset=0.0)
    ungated = {**layer, "wq": layer["wq"].reshape(
        cfg.dim, cfg.n_heads, 2, cfg.head_dim)[:, :, 0].reshape(cfg.dim, -1)}
    for other in (walk(whole, layer), walk(plain, layer),
                  walk(cfg, ungated)):
        assert np.abs(other - got).max() > 1e-2
    # The rotary embedding turns the first quarter and passes the rest.
    q = jnp.asarray(np.random.default_rng(7).normal(size=(1, 5, 2, 16)),
                    jnp.float32)
    at = jnp.arange(5)[None] + 3
    turned = rope(q, at, 1e4, None, 4)
    np.testing.assert_array_equal(np.asarray(turned[..., 4:]),
                                  np.asarray(q[..., 4:]))
    np.testing.assert_array_equal(np.asarray(turned[..., :4]),
                                  np.asarray(rope(q[..., :4], at, 1e4)))
    np.testing.assert_array_equal(np.asarray(rope(q, at, 1e4, None, 16)),
                                  np.asarray(rope(q, at, 1e4)))


def _walk_as_it_was(cfg, layer, h, positions):
    """The projections as every attention walk of llama.py wrote them
    out before they read a gate and a rotary width."""
    dt = cfg.dtype
    B, T = h.shape[:2]
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ _w(layer["wq"], dt)).reshape(B, T, H, Hd)
    k = (h @ _w(layer["wk"], dt)).reshape(B, T, KV, Hd)
    v = (h @ _w(layer["wv"], dt)).reshape(B, T, KV, Hd)
    q, k = llama._qk_norm(cfg, layer, q, k)
    scaling = getattr(cfg, "rope_scaling", None)
    return (rope(q, positions, cfg.rope_theta, scaling),
            rope(k, positions, cfg.rope_theta, scaling), v)


@pytest.mark.parametrize("family, name, stack", [
    (llama, "llama_tiny", "layers"), (moe, "moe_tiny", "layers"),
    (lfm2, "lfm2_tiny", "attn"), (nemotron_h, "nemotron_h_tiny", "attn")])
def test_presets_without_a_gate_or_a_factor_walk_as_before_to_the_bit(
        family, name, stack):
    """A layer without a gate under a config without the factor takes
    the walks' old path: the same projections, norms and rotary
    embedding, the same residual, bit for bit, at the preset's own
    dtype."""
    cfg = family.CONFIGS[name]
    params = family.init(cfg, jax.random.key(SEED))["params"]
    layer = jax.tree.map(lambda leaf: leaf[0], params[stack])
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(2, 7, cfg.dim)), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(7, dtype=jnp.int32)[None] + 2,
                                 (2, 7))
    q, k, v, gate = llama._qkv(cfg, layer, h, positions)
    assert gate is None
    for got, want in zip((q, k, v), _walk_as_it_was(cfg, layer, h,
                                                    positions)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    attn = jnp.asarray(rng.normal(size=q.shape), cfg.dtype)
    np.testing.assert_array_equal(
        np.asarray(llama._attn_out(cfg, layer, h, attn, None)),
        np.asarray(h + attn.reshape(2, 7, -1) @ _w(layer["wo"], cfg.dtype)))


# --------------------------------------------------------- the experts
def _share(cfg, stack, first, count):
    return (_cfg(held_experts=(first, count)),
            {**stack, **{name: stack[name][:, first:first + count]
                         for name in ("w_gate", "w_up", "w_down")}})


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged-dot", "pallas-kernel-interpreted"])
def test_sorted_dispatch_of_gated_experts_is_the_dense_one(
        model, monkeypatch, kernel):
    """Sorted pairs and three grouped matmuls give what the one-hot
    buffers at the no-drop capacity give, for a share of the experts
    too: through ``ragged_dot``, this backend's, and through the chip's
    kernel."""
    monkeypatch.setattr(moe, "_grouped_kernel", lambda: kernel)
    cfg, params, _, _ = model
    tokens = jnp.asarray(np.random.default_rng(2).normal(size=(37, cfg.dim)),
                         jnp.float32)
    for first, count in ((0, 16), (4, 4), (12, 4)):
        share, part = _share(cfg, params["moe"], first, count)
        dense, onehot = qn.routed_experts(share, part, 1, tokens,
                                          sequence=False)
        ragged, _ = qn.routed_experts(share, part, 1, tokens, sequence=True)
        np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)
        assert onehot.shape == (37, cfg.experts_per_token, count)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        model):
    """The test that ties the share to the model: each of four chips
    holds a quarter of the experts and routes over all of them; their
    routed parts, with what every chip computes alike (the shared
    expert behind its gate) counted once, are the uncut reference's
    layer."""
    cfg, params, config, weights = model
    stack = params["moe"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 29, cfg.dim)),
                    jnp.float32)
    want = ref.expert_block(config, ref._at(weights["moe"], 2), x[0],
                            "highest")
    tokens = llama._norm(cfg, x, stack["moe_norm"][2])[0]
    quarter = cfg.n_experts // 4
    total = qn.shared_expert(cfg, stack, 2, tokens)
    for rank in range(4):
        share, part = _share(cfg, stack, rank * quarter, quarter)
        for sequence in (True, False):
            routed, _ = qn.routed_experts(share, part, 2, tokens, sequence)
            # The reference's share is the program's.
            theirs = ref.routed_part(
                _ref_config(share, rank),
                {**ref._at(weights["moe"], 2), **{
                    name: part[name][2]
                    for name in ("w_gate", "w_up", "w_down")}},
                tokens, "highest")
            np.testing.assert_allclose(np.asarray(routed),
                                       np.asarray(theirs), **TOL)
        total = total + routed
    np.testing.assert_allclose(np.asarray(x[0] + total), np.asarray(want),
                               **TOL)


def test_dense_cache_prefill_then_decode_matches_the_reference(model):
    cfg, params, config, weights = model
    tokens = jnp.asarray([_tokens(21), _tokens(21, 1)], jnp.int32)
    want = np.asarray(ref.logits(config, weights, tokens))
    logits, cache = qn.prefill(cfg, params, tokens[:, :13], 32)
    np.testing.assert_allclose(np.asarray(logits), want[:, 12], **TOL)
    for t in range(13, 21):
        logits, cache = qn.decode_step(cfg, params, cache, tokens[:, t], t)
        np.testing.assert_allclose(np.asarray(logits), want[:, t], **TOL)


def test_training_loss_and_gradients_are_finite(model):
    cfg, params, _, _ = model
    tokens = jnp.asarray([_tokens(16), _tokens(16, 1)], jnp.int32)

    def loss(p):
        return qn.apply(cfg, {"params": p, "state": {}},
                        {"tokens": tokens})[0]

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert float(jnp.linalg.norm(grads["moe"]["w_gate"])) > 0
    assert float(jnp.linalg.norm(grads["gdn"]["A_log"])) > 0


def test_family_is_registered_and_the_per_row_surface_is_one_place():
    from polyaxon_tpu import models

    assert qn in models.FAMILIES
    assert models.family_of("qwen3_next_tiny") is qn
    published = models.config_of("qwen3_next_80b_a3b")
    assert published.n_layers == 48
    assert qn.kind_counts(published) == {"gdn": 36, "attn": 12}
    assert qn.layer_plan(published)[:4] == (
        ("gdn", 0), ("gdn", 1), ("gdn", 2), ("attn", 0))
    rows = jax.eval_shape(lambda: qn.paged_init_rows(published, 2))
    assert rows["gdn"].shape == (36, 2, 32, 128, 128)
    assert rows["gdn"].dtype == jnp.float32
    assert rows["conv"].shape == (36, 2, 3, 8192)
    # Both families with a state a row import the same functions.
    for name in ("paged_insert_prefill", "paged_gather_prefix",
                 "paged_insert_suffix"):
        assert getattr(qn, name) is getattr(nemotron_h, name) \
            is getattr(plan, name)
