"""The plain reference against the program, at tiny widths on the CPU:
same seed, same model, without either handing the other an array."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import program
from reference import mistral, mixtral, plain

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny(name):
    with open(os.path.join(HERE, "fixtures", "dry", "configs",
                           f"{name}.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_the_seed_gives_both_sides_the_same_weights(seed):
    config = tiny("tiny_dense")
    family, cfg = program.build_model_config(config, "serve")
    theirs = family.init(cfg, jax.random.key(seed))["params"]
    ours = mistral.init_weights(config, cfg.n_layers, seed)
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(theirs[name]),
                                      np.asarray(ours[name]))
    for name, leaf in ours["layers"].items():
        np.testing.assert_array_equal(np.asarray(theirs["layers"][name]),
                                      np.asarray(leaf))


def test_moe_weights_match_too():
    config = tiny("tiny_moe")
    family, cfg = program.build_model_config(config, "train")
    theirs = family.init(cfg, jax.random.key(3))["params"]
    ours = mixtral.init_weights(config, cfg.n_layers, 3)
    assert set(ours["layers"]) == set(theirs["layers"])
    np.testing.assert_array_equal(np.asarray(theirs["layers"]["router"]),
                                  np.asarray(ours["layers"]["router"]))
    np.testing.assert_array_equal(np.asarray(theirs["layers"]["w_down"]),
                                  np.asarray(ours["layers"]["w_down"]))


def test_forward_agrees_with_the_programs_float32_forward():
    """In float32 the two are the same arithmetic: agreement to rounding
    says the reference's equations are the program's model."""
    import dataclasses

    config = tiny("tiny_dense")
    family, cfg = program.build_model_config(config, "serve")
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 48)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        theirs = family.forward(cfg32, family.init(
            cfg32, jax.random.key(9))["params"], tokens)
    ours = mistral.logits(config, mistral.init_weights(config, 2, 9), tokens)
    np.testing.assert_allclose(np.asarray(theirs), np.asarray(ours),
                               atol=2e-5)
    # causal: padding behind a row changes nothing before it
    padded = jnp.concatenate([tokens, jnp.zeros((2, 16), jnp.int32)], 1)
    again = mistral.logits(config, mistral.init_weights(config, 2, 9), padded)
    np.testing.assert_allclose(np.asarray(again[:, :48]), np.asarray(ours),
                               atol=2e-5)


def test_synthetic_batch_is_the_jobs_data():
    from polyaxon_tpu.runtime import data

    theirs = next(data.lm_synthetic(4, seq_len=64, vocab_size=512, seed=77,
                                    start_batch=2))["tokens"]
    np.testing.assert_array_equal(
        theirs, plain.synthetic_batch(77, 2, 4, 64, 512))


def test_int8_control_moves_the_logits_and_the_gradient():
    config = tiny("tiny_dense")
    weights = mistral.init_weights(config, 2, 4)
    tokens = jnp.asarray(plain.synthetic_batch(4, 0, 2, 64, 512))
    exact = np.asarray(mistral.logits(config, weights, tokens))
    low = np.asarray(mistral.logits(config, weights, tokens, "int8"))
    assert 1e-4 < np.abs(exact - low).max() < 0.5
    g = jax.grad(lambda w: plain.lm_loss(config, w, tokens)[0])(weights)
    g8 = jax.grad(lambda w: plain.lm_loss(config, w, tokens, "int8")[0])(weights)
    a, b = float(plain.global_norm(g)), float(plain.global_norm(g8))
    assert 1e-4 < abs(a - b) / a < 0.2


def test_expert_capacity_drops_in_token_order():
    config = dict(tiny("tiny_moe"))
    layer = jax.tree.map(lambda w: w[0],
                         mixtral.init_weights(config, 1, 0)["layers"])
    h = jnp.ones((64, 64))           # every token routes alike
    weights, aux, dropped = plain.route(config, layer, h, 1.25, "highest")
    # capacity = ceil(64 * 1.25 * 2 / 4) = 40 pairs an expert; 64 tokens
    # choose the same two experts, so 24 pairs of each are dropped
    assert float(dropped) == 48.0
    kept_rows = np.asarray((weights > 0).sum(1))
    assert (kept_rows[:40] == 2).all() and (kept_rows[40:] == 0).all()
    assert float(aux) > 1.0


def test_adamw_step_by_hand():
    w = {"a": jnp.asarray([1.0, -2.0])}
    g = {"a": jnp.asarray([3.0, 4.0])}          # norm 5: clipped to 1
    zeros = {"a": jnp.zeros(2)}
    new, m, v = plain.adamw_step(w, g, zeros, zeros, 0.0, lr=0.1, wd=0.01,
                                 clip=1.0)
    clipped = np.asarray([0.6, 0.8])
    np.testing.assert_allclose(np.asarray(m["a"]), 0.1 * clipped, rtol=1e-6)
    # first step: m_hat / sqrt(v_hat) = sign(g); decay 0.01 * w
    expect = np.asarray([1.0, -2.0]) - 0.1 * (1.0 + 0.01 * np.asarray([1.0, -2.0]))
    np.testing.assert_allclose(np.asarray(new["a"]), expect, rtol=1e-5)
