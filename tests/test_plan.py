"""`models/plan.py`: the walk over a static layer plan that lfm2,
nemotron_h, qwen3_next and smallthinker share. The plan helper on the
four published patterns, the two walks over a toy table against the
same layers applied by hand, and the names the engine and the benchmark
read off each family module. No engine and no tiny model: what a whole
family computes is held by its own test file against its reference."""

import dataclasses
import inspect
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu import models
from polyaxon_tpu.models import (lfm2, llama, nemotron_h, plan, qwen3_next,
                                 smallthinker)

FAMILIES = {"lfm2": lfm2, "nemotron_h": nemotron_h,
            "qwen3_next": qwen3_next, "smallthinker": smallthinker}


# ------------------------------------------------------------ the plan
def _one_period(family: str):
    """A config of one published period (lfm2: its tiny preset, whose
    `layer_types` and `n_dense_layers` are written out below)."""
    if family == "lfm2":
        return lfm2.CONFIGS["lfm2_tiny"]
    if family == "nemotron_h":
        return dataclasses.replace(nemotron_h.NemotronHConfig(),
                                   pattern="*EMEMEMEMEM")
    if family == "qwen3_next":
        return dataclasses.replace(qwen3_next.Qwen3NextConfig(), n_layers=4)
    return dataclasses.replace(smallthinker.SmallThinkerConfig(), n_layers=4,
                               rope_layout=None, window_layout=None)


PLANS = {
    # ("conv", "full_attention", "conv", "conv", "full_attention"), one
    # dense layer: (operator, its index, FFN, its index).
    "lfm2": (
        (("conv", 0, "dense", 0), ("attn", 0, "moe", 0), ("conv", 1, "moe", 1),
         ("conv", 2, "moe", 2), ("attn", 1, "moe", 3)),
        {"attn": 2, "conv": 3, "dense": 1, "moe": 4}),
    "nemotron_h": (
        (("attn", 0), ("moe", 0), ("ssm", 0), ("moe", 1), ("ssm", 1),
         ("moe", 2), ("ssm", 2), ("moe", 3), ("ssm", 3), ("moe", 4),
         ("ssm", 4)),
        {"ssm": 5, "attn": 1, "moe": 5}),
    "qwen3_next": (                                       # D D D A
        (("gdn", 0), ("gdn", 1), ("gdn", 2), ("attn", 0)),
        {"gdn": 3, "attn": 1}),
    "smallthinker": (                                     # G W W W
        (("full", 0, False), ("window", 0, True), ("window", 1, True),
         ("window", 2, True)),
        {"full": 1, "window": 3}),
}
# What the walks are handed for the same layers: (mixer kind or None,
# its index in its stack, FFN kind or None, its index in its stack).
LAYERS = {
    "lfm2": PLANS["lfm2"][0],
    "nemotron_h": (
        ("attn", 0, None, None), (None, None, "moe", 0),
        ("ssm", 0, None, None), (None, None, "moe", 1),
        ("ssm", 1, None, None), (None, None, "moe", 2),
        ("ssm", 2, None, None), (None, None, "moe", 3),
        ("ssm", 3, None, None), (None, None, "moe", 4),
        ("ssm", 4, None, None)),
    "qwen3_next": (("gdn", 0, "moe", 0), ("gdn", 1, "moe", 1),
                   ("gdn", 2, "moe", 2), ("attn", 0, "moe", 3)),
    "smallthinker": (("full", 0, "moe", 0), ("window", 1, "moe", 1),
                     ("window", 2, "moe", 2), ("window", 3, "moe", 3)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_published_period_gives_the_entries_the_family_gave(family):
    module, cfg = FAMILIES[family], _one_period(family)
    entries, counts = PLANS[family]
    assert module.layer_plan(cfg) == entries
    assert module.kind_counts(cfg) == counts
    assert module.FAMILY.layers(cfg) == LAYERS[family]
    # Every kind a layer names is in the family's table.
    for kind, _, ffn, _ in module.FAMILY.layers(cfg):
        assert kind is None or kind in module.FAMILY.mixers
        assert ffn is None or ffn in module.FAMILY.ffns


def test_indexed_counts_each_kind_apart():
    assert plan.indexed(("a", "b", "a", "a", "b")) == (
        ("a", 0), ("b", 0), ("a", 1), ("a", 2), ("b", 1))
    assert plan.indexed(()) == ()
    assert plan.kind_counts(("a", "b", "a"), ("a", "b", "c")) == {
        "a": 2, "b": 1, "c": 0}


# ------------------------------------------------------- the toy table
# Two mixers and an FFN small enough to apply by hand. "mix" stands for
# attention (its K and V are x itself, its one position goes through
# the cache's `attend`); "acc" carries the running sum of its inputs;
# the FFN picks expert 0 or 1 by the sign of a feature, adds what its
# `before` read of the layer's input, and hands the one-hot back.
D = 4
CFG = types.SimpleNamespace(
    dtype=jnp.float32, n_kv_heads=1, head_dim=D, norm_eps=1e-6,
    lm_logits_chunk=64, loss_chunk=8, experts_per_token=1)


def _mix(cfg, layer, x, i, behind):
    return x * layer["w"], {"k": x[:, :, None, :], "v": -x[:, :, None, :]}


def _acc(cfg, layer, x, i, behind):
    total = behind.carried["sum"][i][:, None, :] + jnp.cumsum(x, axis=1)
    return x + layer["w"] * total, {"sum": total[:, -1]}


def _acc_step(cfg, layer, x, i, state, started):
    B = x.shape[0]
    before = jnp.where(started[:, None], state["sum"][i, :B], 0)
    total = before + x[:, 0]
    return x + layer["w"] * total[:, None], {
        "sum": state["sum"].at[i, :B].set(total)}


def _experts(cfg, params, i, x, pre):
    B, S, _ = x.shape
    choice = (x[..., 0] > 0).astype(jnp.int32).reshape(B * S)
    onehot = jax.nn.one_hot(choice, 2)[:, None, :]
    scale = params["moe"]["scale"][i][choice].reshape(B, S, 1)
    return x * scale + (0.0 if pre is None else pre), onehot


TOY = plan.Family(
    name="tests.toy", configs={}, init=None, logical_axes=None,
    layers=lambda cfg: (("mix", 0, "moe", 0), ("acc", 0, None, None),
                        (None, None, "moe", 1), ("acc", 1, "moe", 2)),
    mixers={"mix": plan.Mixer("mix", _mix, None, "toy_mix"),
            "acc": plan.Mixer("acc", _acc, _acc_step, None)},
    ffns={"moe": plan.Ffn(
        lambda cfg, layer, x: 0.5 * layer["w"] * x, _experts)},
    init_rows=lambda cfg, rows: {"sum": jnp.zeros((2, rows, D))})


@pytest.fixture(scope="module")
def toy_params():
    keys = jax.random.split(jax.random.key(3), 6)
    return {"embed": jax.random.normal(keys[0], (11, D)),
            "mix": {"w": 1.0 + 0.1 * jax.random.normal(keys[1], (1, D))},
            "acc": {"w": 0.1 * jax.random.normal(keys[2], (2, D))},
            "moe": {"scale": 1.0 + 0.1 * jax.random.normal(keys[3], (3, 2))},
            "final_norm": jnp.ones((D,)),
            "lm_head": jax.random.normal(keys[4], (D, 11))}


def _by_hand(params, x, sums):
    """The toy plan's four layers written out over ``x`` [B, S, D].
    ``sums`` [2, B, D] is what the two "acc" layers carry in. Returns
    (x, the new sums, the three one-hots)."""
    scale, w_acc = params["moe"]["scale"], params["acc"]["w"]
    hots = []

    def experts(i, x, pre):
        choice = np.asarray(x[..., 0] > 0).astype(int)
        hots.append(np.eye(2)[choice.reshape(-1)])
        return x * np.asarray(scale[i])[choice][..., None] + pre

    def acc(i, x):
        total = sums[i][:, None, :] + np.cumsum(x, axis=1)
        return x + np.asarray(w_acc[i]) * total, total[:, -1]

    w_mix = np.asarray(params["mix"]["w"][0])
    x = np.asarray(x, np.float64)
    x = experts(0, x * w_mix, 0.5 * w_mix * x)     # layer 0: mix, experts
    x, first = acc(0, x)                            # layer 1: acc alone
    x = experts(1, x, 0.0)                          # layer 2: experts alone
    pre = 0.5 * np.asarray(w_acc[1]) * x            # layer 3: acc, experts
    x, second = acc(1, x)
    x = experts(2, x, pre)
    return x, np.stack([first, second]), hots


def test_the_sequence_walk_is_the_layers_applied_by_hand(toy_params):
    tokens = jnp.asarray([[1, 2, 3, 4, 5], [9, 8, 7, 6, 5]], jnp.int32)
    x, k, v, kept = plan.sequence_pass(TOY, CFG, toy_params, tokens)
    embedded = toy_params["embed"][tokens]
    want, sums, _ = _by_hand(toy_params, embedded, np.zeros((2, 2, D)))
    np.testing.assert_allclose(x, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(kept["sum"], sums, rtol=1e-5, atol=1e-5)
    # One attention layer: its K and V, [L_attn, B, S, KV, Hd].
    assert k.shape == v.shape == (1, 2, 5, 1, D)
    np.testing.assert_allclose(k[0, :, :, 0], embedded, rtol=1e-6)
    # `sequence_layers` hands the same back layer by layer.
    _, ks, vs, leaves = plan.sequence_layers(TOY, CFG, toy_params, tokens)
    assert len(ks) == len(vs) == 1 and len(leaves["sum"]) == 2
    # The logits are the head over the walk's hidden states.
    np.testing.assert_allclose(
        plan.forward(TOY, CFG, toy_params, tokens),
        plan._head(CFG, toy_params, x), rtol=1e-6)


def test_a_suffix_behind_what_the_prefix_carried_is_the_whole_pass(
        toy_params):
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    whole, _, _, carried = plan.sequence_pass(TOY, CFG, toy_params, tokens)
    _, k, v, first = plan.sequence_pass(TOY, CFG, toy_params, tokens[:, :2])
    tail, _, _, second = plan.sequence_pass(
        TOY, CFG, toy_params, tokens[:, 2:], k, v, first, 2)
    np.testing.assert_allclose(tail, whole[:, 2:], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(second["sum"], carried["sum"], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("elsewhere", [False, True])
def test_the_decode_walk_is_the_layers_applied_by_hand(toy_params,
                                                       elsewhere):
    """Row 0 is at position 3, row 1 at position 0 (it starts from
    zeros whatever the state holds), row 2 idle (its pairs are not
    counted)."""
    tokens = jnp.asarray([4, 7, 0], jnp.int32)
    pos = jnp.asarray([3, 0, -1], jnp.int32)
    held = jax.random.normal(jax.random.key(5), (2, 4, D))   # 4 rows >= B
    seen = []

    def attend(kind, i, layer, x):
        seen.append((kind, i))
        return x * layer["w"]

    counters = {"moe_expert_tokens": jnp.ones((3, 2), jnp.int32)}
    if elsewhere:
        counters["moe_pairs_elsewhere"] = jnp.zeros((3,), jnp.int32)
    logits, state, counted = plan.decode(
        TOY, CFG, toy_params, tokens, pos, attend, {"sum": held}, counters)
    assert seen == [("mix", 0)]

    sums = np.asarray(held[:, :3]).copy()
    sums[:, 1:] = 0                       # not started: zeros; idle: any
    x = toy_params["embed"][tokens][:, None, :]
    want, new, hots = _by_hand(toy_params, x, sums)
    np.testing.assert_allclose(
        logits[:2], plan._head(CFG, toy_params, jnp.asarray(
            want[:2, 0], jnp.float32)), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state["sum"][:, :2], new[:, :2], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(state["sum"][:, 3], held[:, 3])
    live = np.asarray([1, 1, 0])
    for layer, onehot in enumerate(hots):
        np.testing.assert_array_equal(
            counted["moe_expert_tokens"][layer], 1 + live @ onehot)
    assert set(counted) == set(counters)
    if elsewhere:      # one choice a live row, every expert held: none
        np.testing.assert_array_equal(counted["moe_pairs_elsewhere"], 0)


def test_a_cache_without_state_or_counters_is_walked_as_it_is(toy_params):
    only_mix = TOY._replace(
        layers=lambda cfg: (("mix", 0, "moe", 0),),
        init_rows=lambda cfg, rows: {})
    tokens = jnp.asarray([4, 7], jnp.int32)
    logits, state, counted = plan.decode(
        only_mix, CFG, toy_params, tokens, jnp.asarray([2, -1], jnp.int32),
        lambda kind, i, layer, x: x, {}, {})
    assert state == {} and counted == {}
    assert logits.shape == (2, 11)


def test_the_head_is_read_from_the_trees_keys(toy_params):
    x = jax.random.normal(jax.random.key(1), (3, D))
    untied = plan._head(CFG, toy_params, x)
    tied = {name: leaf for name, leaf in toy_params.items()
            if name != "lm_head"}
    tied["embed"] = toy_params["lm_head"].T
    np.testing.assert_allclose(plan._head(CFG, tied, x), untied, rtol=1e-5,
                               atol=1e-5)


def test_the_slot_cache_keeps_every_leaf_by_slot(toy_params):
    cache = plan.init_cache(TOY, CFG, 3, 8)
    assert {name: leaf.shape for name, leaf in cache.items()} == {
        "k": (1, 3, 8, 1, D), "v": (1, 3, 8, 1, D), "sum": (2, 3, D)}
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    logits, row = plan.prefill(TOY, CFG, toy_params, prompt, 8)
    assert logits.shape == (1, 11) and row["k"].shape == (1, 1, 8, 1, D)
    merged = plan.insert_cache_row(cache, row, 1)
    np.testing.assert_array_equal(merged["sum"][:, 1], row["sum"][:, 0])
    np.testing.assert_array_equal(merged["sum"][:, 0], 0)
    with pytest.raises(ValueError, match="exceeds cache length"):
        plan.prefill(TOY, CFG, toy_params, prompt, 2)


def test_apply_refuses_segments_under_the_familys_name(toy_params):
    batch = {"tokens": jnp.asarray([[1, 2, 3, 4]], jnp.int32)}
    loss, metrics, _ = plan.apply(
        TOY, CFG, {"params": toy_params, "state": {}}, batch)
    assert np.isfinite(loss) and metrics["loss"] == loss
    with pytest.raises(ValueError, match="toy models do not support packed"):
        plan.apply(TOY, CFG, {"params": toy_params, "state": {}},
                   {**batch, "segments": jnp.ones((1, 4), jnp.int32)})
    for module in FAMILIES.values():
        assert module.FAMILY.name == module.__name__
        assert module.FAMILY.configs is module.CONFIGS


# ------------------------------------------------- the engine's names
# What `serving/batching.py` finds by `hasattr` (its `required` tuples,
# the suffix and row surfaces) and what `benchmark/` reads off a family
# module, with the count of positional arguments each is called with.
# A family with a state a row is handed the row last; the window family
# the second page space's size, tables and K/V.
EVERY = {"init": 2, "forward": 3, "model_def": 1, "layer_plan": 1,
         "init_cache": 3, "prefill": 4, "decode_step": 5, "generate": 3,
         "decode_step_ragged": 5, "cb_init_cache": 3, "cb_prefill": 4,
         "cb_admission": 1, "cb_validate": 4, "insert_cache_row": 3,
         "decode_step_paged": 6, "paged_prefill_kv": 3, "apply": 3,
         "kind_counts": 1, "logical_axes": 1}
SURFACES = {
    "lfm2": {**EVERY, "paged_init_cache": 3, "paged_gather": 2,
             "paged_gather_prefix": 2, "paged_prefill_suffix_kv": 7,
             "paged_insert_suffix": 8, "paged_insert_prefill": 6},
    "rows": {**EVERY, "paged_init_cache": 3, "paged_init_rows": 2,
             "paged_gather": 2, "paged_gather_prefix": 3,
             "paged_prefill_suffix_kv": 8, "paged_insert_suffix": 9,
             "paged_insert_prefill": 7},
    # Two page spaces; behind a shared prefix the run's start and the
    # match are plain numbers (a pool with a window space matches whole
    # pages).
    "smallthinker": {**EVERY, "paged_init_cache": 4, "paged_window": 1,
                     "paged_insert_prefill": 8, "paged_gather_prefix": 2,
                     "paged_prefill_suffix_kv": 6, "paged_insert_suffix": 9},
}
SURFACES["nemotron_h"] = SURFACES["qwen3_next"] = SURFACES["rows"]
CONFIG_CLASSES = {"lfm2": "Lfm2Config", "nemotron_h": "NemotronHConfig",
                  "qwen3_next": "Qwen3NextConfig",
                  "smallthinker": "SmallThinkerConfig"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_name_the_engine_and_the_benchmark_read_is_on_the_module(
        family):
    module = FAMILIES[family]
    assert module in models.FAMILIES
    for name, count in SURFACES[family].items():
        fn = getattr(module, name)
        keywords = {"max_new_tokens": 1} if name == "generate" else {}
        inspect.signature(fn).bind(*range(count), **keywords)
    absent = set(SURFACES["rows"]) | set(SURFACES["smallthinker"])
    for name in absent - set(SURFACES[family]):
        assert not hasattr(module, name), name      # the engine asks by name
    assert not hasattr(module, "decode_chunk")      # no rollback of a state
    assert module.SEQ2SEQ is False
    assert isinstance(module.READ_AT_FLOAT32, frozenset)
    assert llama.HELD_TRANSPOSED <= module.HELD_TRANSPOSED
    tiny = next(name for name in module.CONFIGS if name.endswith("_tiny"))
    assert isinstance(module.CONFIGS[tiny],
                      getattr(module, CONFIG_CLASSES[family]))
    assert module.model_def(tiny).name == tiny
    assert module.cb_admission([5, 6, 7]) == (2, 7, [5, 6])
