"""The LFM2 configuration's own files: what its family file registers
in the program, field by field; its cut and its assumptions held
against the published keys; what the family refuses; the two expert
readers on recorded data; and a whole run of a tiny hybrid cell on the
CPU (sound: correct; the int8 control: outside the tiny limit)."""

import copy
import importlib.util
import json
import os

import jax.numpy as jnp
import pytest

import run
from harness import program, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DRY = os.path.join(FIXTURES, "dry_lfm2")
CELL = "lfm2_8b_a1b_serve_batchgen"

# LiquidAI/LFM2-8B-A1B config.json, as the catalog of public
# architectures holds it (model-configs guide, `architectures.jsonl`).
PERIOD = ["full_attention", "conv", "conv", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": (["conv", "conv"] + PERIOD * 4 + [
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"]),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
KEPT = ["conv"] + PERIOD            # published layer 1, then layers 2..5


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        config = json.load(fh)
    config["_path"] = os.path.join(BENCH, path)
    return config


REGISTERED = {
    "configs/lfm2_8b_a1b.json": dict(
        vocab_size=65536, dim=2048, n_layers=5, n_heads=32, n_kv_heads=8,
        ffn_dim=7168, moe_ffn_dim=1792, n_experts=32, experts_per_token=4,
        n_dense_layers=1, layer_types=tuple(KEPT), conv_kernel=3,
        router_score="sigmoid", norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, max_seq_len=2048, rope_theta=1e6,
        norm_eps=1e-5, dtype=jnp.bfloat16),
    "tests/fixtures/dry_lfm2/configs/tiny_lfm2.json": dict(
        vocab_size=512, dim=64, n_layers=5, n_heads=4, n_kv_heads=2,
        ffn_dim=128, moe_ffn_dim=32, n_experts=8, experts_per_token=2,
        n_dense_layers=1, layer_types=tuple(KEPT), conv_kernel=3,
        router_score="sigmoid", norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, max_seq_len=256, rope_theta=1e6,
        norm_eps=1e-5, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("path", sorted(REGISTERED))
def test_family_file_registers_these_fields(path):
    module, cfg = program.build_model_config(read(path), "serve")
    assert module.__name__ == "polyaxon_tpu.models.lfm2"
    assert type(cfg).__name__ == "Lfm2Config"
    for field, value in REGISTERED[path].items():
        assert getattr(cfg, field) == value, field
    assert cfg.head_dim * cfg.n_heads == cfg.dim


def test_cut_and_assumptions_against_the_published_keys():
    config = read("configs/lfm2_8b_a1b.json")
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "lfm2_8b_a1b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"] == list(config["reduced"])
    for key, value in PUBLISHED.items():        # every published key is there
        if key in config["reduced"]:
            assert config["reduced"][key]["source"] == value, key
            assert config["reduced"][key]["serve"] == config[key], key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    # Depth alone is cut: no width, head size, expert count or top-k.
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 5
    assert config["layer_types"] == KEPT == PUBLISHED["layer_types"][1:6]
    assert config["num_dense_layers"] == 1
    assert config["serve"]["num_hidden_layers"] == 5
    # Each key the published file lacks is accounted for.
    further = {"head_dim", "tie_embedding", "torch_dtype"}
    assert further <= set(config["assumed"]) and further <= set(config)
    assert {"expert_bias", "init"} <= set(config["assumed"])
    assert config["head_dim"] * config["num_attention_heads"] == \
        config["hidden_size"]
    assert config["deployment"] and config["dtype"] and config["check_why"]
    assert set(config["check"]["serve"]) == {"gap_mean", "gap_max"}


def broken(**changes):
    config = copy.deepcopy(read("configs/lfm2_8b_a1b.json"))
    config.update(changes)
    return config


@pytest.mark.parametrize("fault, said", [
    (dict(layer_types=KEPT[:4]), "layer_types names 4"),
    (dict(layer_types=["conv", "conv", "conv", "conv", "full_attention"]),
     "not the published layers"),
    (dict(layer_types=KEPT + ["full_attention", "conv"], num_hidden_layers=7,
          serve=dict(num_hidden_layers=7)), "whole periods"),
    (dict(num_dense_layers=2), "one of the leading dense layers"),
    (dict(conv_bias=True), "no bias"),
    (dict(head_dim=128), "head_dim"),
    (dict(tie_embedding=False), "ties its head"),
    (dict(serve=dict(num_hidden_layers=4)), "depth 4"),
], ids=["length", "order", "half-a-period", "dense-layers", "conv-bias",
        "head-size", "untied", "section-depth"])
def test_family_file_refuses(fault, said):
    with pytest.raises(ValueError, match=said):
        program.build_model_config(broken(**fault), "serve")


def test_cell_reports_what_cell_one_reports_and_the_two_expert_metrics():
    cell, one = spec.Cell(CELL), spec.Cell("mistral7b_serve_batchgen")
    assert (cell.chips, cell.kind, cell.entry["traffic"]) == (
        1, "serve", "batchgen_closed")
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in one.end_to_end]
    mine = [m["name"] for m in cell.per_layer]
    assert mine == [m["name"] for m in one.per_layer] + [
        "moe.expert_load_max_over_mean", "moe.busy_share_pct"]
    serve = cell.config["serve"]
    assert serve["slots"] * serve["max_len"] == 65536
    longest = (cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"])
    assert longest <= serve["max_len"]


# ------------------------------------------------------------ the readers
def reader(name: str):
    return spec.load_reader(name)


def recorded_trace():
    with open(os.path.join(FIXTURES, "moe_ops.json")) as fh:
        kept = json.load(fh)
    events, t = [], 0.0
    for ev in kept["events"]:
        events.append({"name": ev["name"], "start": t, "dur": ev["dur"]})
        t += ev["dur"]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events}]}]}
    return kept, trace


def test_expert_share_matches_the_recorded_names():
    from harness import trace_reduce

    kept, trace = recorded_trace()
    module = reader("moe.busy_share_pct")
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(trace)[0])
    mine = module.expert_ops(ops, kept["experts"], kept["hidden"],
                             kept["width"])
    assert sorted({f"{ev['op']} {ev['shape']}" for ev in mine}) == \
        kept["expert_block"]
    names = {ev["op"] for ev in mine}
    assert not names & {"paged_decode.1", "copy-done.16",
                        "convert_element_type.120", "convert.39"}
    config = {"num_experts": 32, "hidden_size": 2048,
              "moe_intermediate_size": 1792}
    ctx = {"kind": "serve", "trace": trace, "config": config,
           "busy": {"busy_s": kept["busy_s"]}}
    share = module.read(ctx)
    assert share == pytest.approx(
        100 * sum(ev["dur"] for ev in mine) / kept["busy_s"])
    assert 55 < share < 70
    # Nothing to read: no routed experts, no trace, no matching name.
    dense = {"hidden_size": 4096}
    assert module.read({**ctx, "config": dense}) is None
    assert module.read({**ctx, "trace": None}) is None
    assert module.read({**ctx, "config": {**config, "num_experts": 8}}) is None


def test_expert_load_reads_the_windows_edges():
    module = reader("moe.expert_load_max_over_mean")
    before = [[10, 10, 10, 10], [0, 0, 0, 0]]
    after = [[20, 20, 20, 20], [40, 20, 10, 10]]
    ctx = {"kind": "serve", "stats": {
        "open": {"moe_expert_tokens": before},
        "close": {"moe_expert_tokens": after}}}
    assert module.read(ctx) == pytest.approx((1.0 + 2.0) / 2)
    # A program without the counter, an untraced run, an idle window.
    assert module.read({"kind": "serve", "stats": {
        "open": {}, "close": {}}}) is None
    assert module.read({"kind": "serve", "stats": {"after": {}}}) is None
    assert module.read({"kind": "serve", "stats": {
        "open": {"moe_expert_tokens": before},
        "close": {"moe_expert_tokens": before}}}) is None


# -------------------------------------------------------------- a whole run
def test_tiny_hybrid_run_is_correct_and_the_control_is_not():
    cell = spec.Cell("tiny_lfm2_closed", spec.load_benchmark(DRY), DRY)
    seed = 3_000_000_011
    final = run.run_cell(cell, seed=seed, seconds=3, trace=True,
                         require_chip=False, control=True)
    assert final["correct"] is True and final["failed"] == 0
    # Off the chip the trace's reader finds nothing; the counter's does.
    assert set(final["metrics"]) == {"engine.avg_occupancy",
                                     "moe.expert_load_max_over_mean"}
    assert 1.0 <= final["metrics"]["moe.expert_load_max_over_mean"][
        "value"] < 4.0
    out_dir = os.path.join(run.ROOT, ".benchmark_out",
                           f"{cell.name}-{seed}-1")
    with open(os.path.join(out_dir, "reference.json")) as fh:
        ref = json.load(fh)
    with open(os.path.join(out_dir, "program.json")) as fh:
        ran = json.load(fh)
    limit = cell.config["check"]["serve"]["gap_mean"]
    # Readings at this size (three seeds): sound 0.0034-0.0042, the
    # control 0.0089-0.0115; routing near-ties make both large.
    assert ref["numbers"]["gap_mean"] < limit < ref["control"]["gap_mean"]
    assert ran["compiles_in_window"] == 0
    after = ran["stats"]["after"]
    assert after["kv_state_bytes_per_page"] == 4 * 2 * 64 * 2
    assert after["kv_page_bytes"] == 4 * 2 * 64 * 2 + 2 * 1 * 2 * 16 * 16 * 2
    assert after["kv_cow_forks"] == 0
    spans = next(iter(ran["timelines"].values()))
    assert "prefill" in spans
