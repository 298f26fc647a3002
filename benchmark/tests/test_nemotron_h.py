"""The Nemotron-3-Super configuration's own files: what its family file
registers in the program, field by field; its cut, its deployment and
its assumptions held against the published keys; what the family
refuses; its three readers and its kernel's counts on recorded data; and
a whole run of a tiny share on the CPU (sound: correct; the int8
control: outside the tiny limit)."""

import copy
import json
import os

import jax.numpy as jnp
import pytest

import run
from harness import program, spec, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DRY = os.path.join(FIXTURES, "dry_nemotron_h")
CELL = "nemotron3_super_serve_batchgen"
NAME = "nemotron3_super_120b_a12b"

# nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json, as the
# catalog of public architectures holds it (model-configs guide,
# `architectures.jsonl`).
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
KEPT = "*EMEMEMEMEM"            # published layers 25..35
CUT = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
       "vocab_size"]


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        config = json.load(fh)
    config["_path"] = os.path.join(BENCH, path)
    return config


REGISTERED = {
    f"configs/{NAME}.json": dict(
        vocab_size=32768, dim=4096, pattern=KEPT, n_layers=11, n_heads=32,
        n_kv_heads=2, head_dim=128, ssm_heads=128, ssm_head_dim=64,
        ssm_state=128, ssm_groups=8, conv_kernel=4, chunk_size=128,
        n_experts=512, experts_per_token=22, moe_latent_dim=1024,
        moe_ffn_dim=2688, shared_ffn_dim=5376, held_experts=(0, 128),
        router_score="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=5.0, rope_theta=None, max_seq_len=2048,
        norm_eps=1e-5, dtype=jnp.bfloat16),
    "tests/fixtures/dry_nemotron_h/configs/tiny_nemotron_h.json": dict(
        vocab_size=512, dim=64, pattern="*EMEM", n_layers=5, n_heads=4,
        n_kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=16,
        ssm_state=16, ssm_groups=2, conv_kernel=4, chunk_size=8,
        n_experts=16, experts_per_token=4, moe_latent_dim=32,
        moe_ffn_dim=48, shared_ffn_dim=96, held_experts=(4, 4),
        routed_scaling_factor=5.0, rope_theta=None, max_seq_len=128,
        dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("path", sorted(REGISTERED))
def test_family_file_registers_these_fields(path):
    module, cfg = program.build_model_config(read(path), "serve")
    assert module.__name__ == "polyaxon_tpu.models.nemotron_h"
    assert type(cfg).__name__ == "NemotronHConfig"
    for field, value in REGISTERED[path].items():
        assert getattr(cfg, field) == value, field
    assert cfg.ssm_heads * cfg.ssm_head_dim == 2 * cfg.dim


def test_cut_deployment_and_assumptions_against_the_published_keys():
    config = read(f"configs/{NAME}.json")
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["source"] == config["source"]
    assert entry["reduced"] == CUT == list(config["reduced"])
    for key, value in PUBLISHED.items():        # every published key is there
        if key in config["reduced"]:
            assert config["reduced"][key]["source"] == value, key
            assert config["reduced"][key]["serve"] == config[key], key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    # No width is cut: one whole period of the pattern, the chip's share
    # of the experts and of the vocabulary.
    assert config["hybrid_override_pattern"] == KEPT == PATTERN[25:36]
    assert config["num_hidden_layers"] == len(KEPT) == 11
    assert (KEPT.count("*"), KEPT.count("E"), KEPT.count("M")) == (1, 5, 5)
    assert PATTERN[36] == "*"                   # up to the next attention
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["rank"],
            deployment["first_layer"]) == (4, 0, 25)
    assert config["n_routed_experts"] * 4 == 512
    assert config["vocab_size"] * 4 == 131072
    assert config["n_routed_experts"] >= 8      # the guide's floors
    assert config["vocab_size"] * 8 >= 131072
    assert {"rope_theta", "router_input", "ssm_state_dtype", "init", "A_log",
            "dt_bias", "D", "e_score_correction_bias",
            "num_nextn_predict_layers", "torch_dtype"} <= set(
                config["assumed"])
    assert deployment["how"] and config["dtype"] and config["check_why"]
    assert set(config["check"]["serve"]) == {"gap_mean", "gap_max"}
    # What this chip holds, from the file's own keys: 4,648 M parameters.
    d, dl, f = 4096, config["moe_latent_size"], config["moe_intermediate_size"]
    d_in = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv = d_in + 2 * config["n_groups"] * config["ssm_state_size"]
    mamba = (d * (d_in + conv + 128) + d_in * d + conv * 5 + 3 * 128
             + d + d_in)
    attn = d + 2 * d * 4096 + 2 * d * 256
    beside = (d + d * 512 + 512 + 2 * d * dl
              + 2 * d * config["moe_shared_expert_intermediate_size"])
    total = (attn + 5 * mamba + 5 * (beside + 128 * 2 * dl * f)
             + 2 * d * config["vocab_size"] + d)
    assert (round(mamba / 1e6, 2), round(attn / 1e6, 2),
            round(beside / 1e6, 2)) == (109.64, 35.66, 54.53)
    assert round(total / 1e6) == 4648


def broken(**changes):
    config = copy.deepcopy(read(f"configs/{NAME}.json"))
    config.update(changes)
    return config


def deployed(**changes):
    return {"deployment": {**read(f"configs/{NAME}.json")["deployment"],
                           **changes}}


@pytest.mark.parametrize("fault, said", [
    (dict(hybrid_override_pattern=KEPT[:10]), "names 10"),
    (dict(hybrid_override_pattern="*EMEMEMEMME"), "not the published layers"),
    (dict(hybrid_override_pattern=KEPT[:9], num_hidden_layers=9,
          serve=dict(num_hidden_layers=9)), "whole periods"),
    (deployed(first_layer=26), "not the published layers"),
    (dict(n_routed_experts=64), "do not hold the router's 512"),
    (dict(vocab_size=65536), "vocabulary slice"),
    (dict(attention_bias=True), "no bias"),
    (dict(use_conv_bias=False), "convolution has a bias"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(n_group=2), "group limit"),
    (dict(mlp_hidden_act="silu"), "relu"),
    (dict(expand=4), "mixer's width"),
    (dict(serve=dict(num_hidden_layers=10)), "depth 10"),
], ids=["length", "order", "half-a-period", "other-slice", "experts-held",
        "vocabulary", "bias", "conv-bias", "tied", "group-limit",
        "activation", "expand", "section-depth"])
def test_family_file_refuses(fault, said):
    with pytest.raises(ValueError, match=said):
        program.build_model_config(broken(**fault), "serve")


def test_cell_reports_what_cell_four_reports_and_its_own_three():
    cell, four = spec.Cell(CELL), spec.Cell("lfm2_8b_a1b_serve_batchgen")
    assert (cell.chips, cell.kind, cell.entry["traffic"]) == (
        1, "serve", "batchgen_closed")
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in four.end_to_end]
    mine = [m["name"] for m in cell.per_layer]
    theirs = [m["name"] for m in four.per_layer]
    assert mine == [n for n in theirs if n != "moe.busy_share_pct"] + [
        "ssm.busy_share_pct", "ssm_update_roofline",
        "experts.busy_share_pct"]
    new = {m["name"]: m for m in cell.per_layer[-3:]}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: m["moves"] for n, m in new.items()} == {
        "ssm.busy_share_pct": "tpot_p50_ms",
        "ssm_update_roofline": "tpot_p50_ms",
        "experts.busy_share_pct": "out_tok_s"}
    serve = cell.config["serve"]
    assert (serve["slots"], serve["kv_pages"], serve["page_size"]) == (
        64, 8192, 16)
    # Every slot's whole context fits the pool at once.
    assert serve["slots"] * serve["max_len"] <= (
        serve["kv_pages"] * serve["page_size"])
    longest = (cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"])
    assert longest <= serve["max_len"]


def test_flops_count_the_share():
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    share = family.forward_flops_per_token(config, 11, 512)
    whole = copy.deepcopy(config)
    whole["n_routed_experts"] = 512
    del whole["reduced"]["n_routed_experts"]
    # All four shares' routed pairs: three more quarters of 22 pairs a
    # token in each of the five expert layers.
    pair = 2 * 2 * 1024 * 2688
    assert family.forward_flops_per_token(whole, 11, 512) - share == \
        pytest.approx(5 * 22 * 0.75 * pair)
    assert 2.2e9 < share < 2.5e9


# ---------------------------------------------------- the kernel's counts
def test_ssm_update_needs_each_live_rows_state_once_each_way():
    kernel = spec.load_kernel("ssm_update")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    state = 128 * 64 * 128 * 4
    assert kernel.bytes_moved(64, 128, 64, 128) == 2 * 64 * state
    assert kernel.bytes_moved(0, 128, 64, 128) == 0
    assert kernel.BOUND == "bytes"
    assert kernel.least_seconds(peaks, 64, 128, 64, 128) == pytest.approx(
        2 * 64 * state / 819e9)
    assert kernel.least_seconds(peaks, 64, 128, 64, 128) > (
        kernel.flops(64, 128, 64, 128) / 197e12)


# -------------------------------------------------------------- a whole run
def test_tiny_share_run_is_correct_and_the_control_is_not():
    cell = spec.Cell("tiny_nemotron_h_closed", spec.load_benchmark(DRY), DRY)
    seed = 3_000_000_011
    final = run.run_cell(cell, seed=seed, seconds=3, trace=True,
                         require_chip=False, control=True)
    assert final["correct"] is True and final["failed"] == 0
    # Off the chip the trace's readers find nothing; the counter's does.
    assert set(final["metrics"]) == {"engine.avg_occupancy",
                                     "moe.expert_load_max_over_mean"}
    out_dir = os.path.join(run.ROOT, ".benchmark_out",
                           f"{cell.name}-{seed}-1")
    with open(os.path.join(out_dir, "reference.json")) as fh:
        ref = json.load(fh)
    with open(os.path.join(out_dir, "program.json")) as fh:
        ran = json.load(fh)
    limit = cell.config["check"]["serve"]["gap_mean"]
    # Readings at this size (three seeds): sound 0.0009-0.0011, the
    # control 0.0054-0.0063.
    assert ref["numbers"]["gap_mean"] < limit < ref["control"]["gap_mean"]
    assert ran["compiles_in_window"] == 0
    after = ran["stats"]["after"]
    # A row: two Mamba-2 layers of 8 x 16 x 16 float32 state and 3 x 192
    # bfloat16 convolution inputs; a page: 16 tokens of one attention
    # layer's K and V.
    assert after["kv_state_bytes_per_slot"] == 2 * (8 * 16 * 16 * 4
                                                    + 3 * 192 * 2)
    assert after["kv_state_bytes_per_page"] == 0
    assert after["kv_page_bytes"] == 2 * 1 * 2 * 16 * 16 * 2
    assert after["prefill_tokens_skipped"] == 0
    assert after["kv_radix"]["pages"] == 0
    held = [sum(row) for row in after["moe_expert_tokens"]]
    assert len(after["moe_expert_tokens"][0]) == 4
    # Rank 1 of four holds a quarter of the experts: about a quarter of
    # the 4 pairs a token land here, the rest elsewhere.
    for here, elsewhere in zip(held, after["moe_pairs_elsewhere"]):
        assert 0.1 < here / (here + elsewhere) < 0.5
    spans = next(iter(ran["timelines"].values()))
    assert "prefill" in spans


# ------------------------------------------------------------ the readers
def recorded_trace():
    """One decode step and one prefill program of the kept trace, laid
    end to end under a module event each."""
    with open(os.path.join(FIXTURES, "nemotron_h_ops.json")) as fh:
        kept = json.load(fh)
    events, modules, t = [], [], 0.0
    for prog in kept["programs"]:
        start = t
        for ev in prog["events"]:
            events.append({"name": ev["name"], "start": t, "dur": ev["dur"]})
            t += ev["dur"]
        modules.append({"name": prog["module"], "start": start,
                        "dur": t - start})
        t += 1e-4                               # the host between programs
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events},
        {"name": "XLA Modules", "events": modules}]}]}
    return kept, trace


def label(ev: dict) -> str:
    return f"{ev['op']} {ev['opcode']} {ev['shape']}"


def test_recurrence_and_expert_shares_match_the_recorded_names():
    kept, trace = recorded_trace()
    config = kept["config"]
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(trace)[0])
    ssm = spec.load_reader("ssm.busy_share_pct")
    experts = spec.load_reader("experts.busy_share_pct")
    mine = ssm.recurrence_ops(ops, config)
    theirs = experts.expert_ops(ops, config["moe_latent_size"],
                                config["moe_intermediate_size"])
    assert sorted({label(ev) for ev in mine}) == kept["recurrence"]
    assert sorted({label(ev) for ev in theirs}) == kept["experts"]
    assert not {ev["name"] for ev in mine} & {ev["name"] for ev in theirs}
    # The state's in-place update (five layers), its read for y, and
    # the grouped matmuls of the prefill (two a layer) are all there;
    # the paged kernel, the head and the projections are not.
    names = {ev["op"] for ev in mine} | {ev["op"] for ev in theirs}
    # (a sixth whole-leaf result is the prefill's write of its row)
    assert sum(ev["shape"] == "f32[5,64,128,64,128]" for ev in mine) == 6
    assert sum(ev["op"].startswith("ragged-dot") for ev in theirs) == 10
    assert not names & {"paged_decode.1", "fusion.384", "fusion.243"}
    busy = trace_reduce.busy(trace)
    ctx = {"kind": "serve", "trace": trace, "config": config, "busy": busy}
    assert ssm.read(ctx) == pytest.approx(
        100 * sum(ev["dur"] for ev in mine) / busy["busy_s"])
    assert experts.read(ctx) == pytest.approx(
        100 * sum(ev["dur"] for ev in theirs) / busy["busy_s"])
    # One decode step beside one prefill (the window holds four steps a
    # prefill, so the recurrence's share there is larger: PERF.md §5).
    assert 10 < ssm.read(ctx) < 25 and 60 < experts.read(ctx) < 90
    # Nothing to read: another family, no trace, other widths.
    dense = {"hidden_size": 4096, "moe_intermediate_size": 2688}
    assert ssm.read({**ctx, "config": dense}) is None
    assert experts.read({**ctx, "config": dense}) is None
    assert ssm.read({**ctx, "trace": None}) is None
    assert experts.read({**ctx, "trace": None}) is None
    assert ssm.read({**ctx, "config": {
        **config, "ssm_state_size": 64, "mamba_head_dim": 32,
        "chunk_size": 64}}) is None
    assert experts.read({**ctx, "config": {
        **config, "moe_intermediate_size": 1792}}) is None


def test_update_roofline_reads_the_decode_steps_state_operations():
    kept, trace = recorded_trace()
    config = kept["config"]
    module = spec.load_reader("ssm_update_roofline")
    plane = trace_reduce.device_planes(trace)[0]
    steps = trace_reduce.module_events(trace, r"^jit_decode_step")
    assert len(steps) == 1
    mine = module.update_ops(trace_reduce.leaf_ops(plane), steps, config)
    # A layer's update and the read of the new state for y, five layers;
    # the prefill's chunk states lie outside the decode program.
    assert sorted(ev["shape"] for ev in mine) == (
        ["f32[5,64,128,64,128]"] * 5 + ["f32[64,128,64]"] * 5)
    busy = trace_reduce.busy(trace)
    rows = [{"token_times": [0.0, 10.0], "n_out": 2, "max_new": 8,
             "prompt_len": 100, "error": None} for _ in range(64)]
    ctx = {"kind": "serve", "trace": trace, "config": config, "busy": busy,
           "trace_wall_t0": 1.0 + busy["t0"], "records": rows,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    least = 5 * 2 * 64 * 128 * 64 * 128 * 4 / 819e9
    assert module.read(ctx) == pytest.approx(
        100 * least / sum(ev["dur"] for ev in mine))
    assert 50 < module.read(ctx) < 60          # the new state is read back
    half = {**ctx, "records": rows[:32]}
    assert module.read(half) == pytest.approx(module.read(ctx) / 2)
    assert module.read({**ctx, "trace": None}) is None
    assert module.read({**ctx, "config": {"hidden_size": 4096}}) is None
