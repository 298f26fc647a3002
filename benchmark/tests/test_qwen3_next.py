"""The Qwen3-Next configuration's own files: what its family file
registers in the program, field by field; its cut, its deployment and
its assumptions held against the published keys, with the parameter
count reckoned from them; what the family refuses; its cell, its
traffic, its three readers and its kernel's counts on recorded data; and
a whole run of a tiny share on the CPU (sound: correct; the int8
control: outside the tiny limit)."""

import copy
import json
import os
import re

import jax.numpy as jnp
import pytest

import run
from harness import program, spec, trace_reduce, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DRY = os.path.join(FIXTURES, "dry_qwen3_next")
CELL = "qwen3_next_serve_longgen"
NAME = "qwen3_next_80b_a3b"

# Qwen/Qwen3-Next-80B-A3B-Instruct config.json, as the catalog of public
# architectures holds it (model-configs guide, `architectures.jsonl`).
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        config = json.load(fh)
    config["_path"] = os.path.join(BENCH, path)
    return config


REGISTERED = {
    f"configs/{NAME}.json": dict(
        vocab_size=37984, dim=2048, n_layers=8, full_attention_interval=4,
        n_heads=16, n_kv_heads=2, head_dim=256, partial_rotary_factor=0.25,
        rope_theta=1e7, gdn_key_heads=16, gdn_value_heads=32,
        gdn_key_dim=128, gdn_value_dim=128, conv_kernel=4, chunk_size=64,
        n_experts=512, experts_per_token=10, moe_ffn_dim=512,
        shared_ffn_dim=512, held_experts=(0, 128), norm_offset=1.0,
        norm_eps=1e-6, max_seq_len=3072, dtype=jnp.bfloat16),
    "tests/fixtures/dry_qwen3_next/configs/tiny_qwen3_next.json": dict(
        vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8,
        gdn_value_dim=8, n_experts=16, experts_per_token=4, moe_ffn_dim=32,
        shared_ffn_dim=32, held_experts=(4, 4), max_seq_len=128,
        dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("path", sorted(REGISTERED))
def test_family_file_registers_these_fields(path):
    module, cfg = program.build_model_config(read(path), "serve")
    assert module.__name__ == "polyaxon_tpu.models.qwen3_next"
    assert type(cfg).__name__ == "Qwen3NextConfig"
    for field, value in REGISTERED[path].items():
        assert getattr(cfg, field) == value, field
    kinds = [kind for kind, _ in module.layer_plan(cfg)]
    assert kinds[:4] == ["gdn", "gdn", "gdn", "attn"]


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
    # No width is cut: two whole periods, the chip's share of the
    # experts and of the vocabulary.
    assert config["num_hidden_layers"] == 8 == 2 * config[
        "full_attention_interval"]
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["rank"],
            deployment["first_layer"]) == (4, 0, 0)
    assert config["num_experts"] * 4 == 512
    assert config["vocab_size"] * 4 == 151936
    assert config["num_experts"] >= 8           # the guide's floors
    assert config["vocab_size"] * 8 >= 151936
    assert config["num_hidden_layers"] >= 4
    assert {"mtp", "intermediate_size", "gdn_state_dtype",
            "projection_layout", "out_norm", "init", "A_log", "dt_bias",
            "torch_dtype"} <= set(config["assumed"])
    assert deployment["how"] and config["dtype"] and config["check_why"]
    assert set(config["check"]["serve"]) == {"gap_mean", "gap_max"}


def test_parameters_here_from_the_files_keys_are_3667_million():
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    n = family.parameters(config)
    assert {k: round(v / 1e6, 2) for k, v in n.items()} == {
        "gdn": 33.72, "attn": 27.27, "beside": 4.20, "expert": 3.15,
        "table": 77.79}
    assert round(family.parameters_here(config, 8) / 1e6) == 3667
    # The same count of the whole published model: 80B-A3B.
    whole = {**{k: v for k, v in config.items() if k not in (
        "reduced", "deployment")}, **PUBLISHED}
    total = family.parameters_here(whole, 48)
    assert round(total / 1e9, 1) == 79.7
    m = family.parameters(whole)
    active = (36 * m["gdn"] + 12 * m["attn"]
              + 48 * (m["beside"] + 10 * m["expert"]) + 2 * m["table"])
    assert round(active / 1e9, 1) == 3.9
    # What a row carries, and a page: the serve section's arithmetic.
    state = 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert round(state / 1e6, 1) == 12.9
    serve = config["serve"]
    page = 2 * 2 * 2 * serve["page_size"] * 256 * 2
    assert page == 65536
    held = (2 * family.parameters_here(config, 8) + serve["slots"] * state
            + serve["kv_pages"] * page)
    assert 10.2e9 < held < 10.4e9


def broken(**changes):
    config = copy.deepcopy(read(f"configs/{NAME}.json"))
    config.update(changes)
    return config


def recut(key, **changes):
    config = read(f"configs/{NAME}.json")
    return {"reduced": {**config["reduced"],
                        key: {**config["reduced"][key], **changes}}}


@pytest.mark.parametrize("fault, said", [
    (dict(num_hidden_layers=6, serve=dict(num_hidden_layers=6)),
     "whole periods"),
    (dict(num_experts=64), "`reduced.num_experts` says 128"),
    ({**recut("num_experts", serve=64), "num_experts": 64},
     "do not hold the router's 512"),
    ({**recut("vocab_size", serve=75968), "vocab_size": 75968},
     "vocabulary slice"),
    (dict(deployment=dict(chips_sharing_a_layer=2, rank=0)),
     "do not hold the router's 512"),
    (dict(decoder_sparse_step=2), "expert block in every layer"),
    (dict(mlp_only_layers=[0]), "expert block in every layer"),
    (dict(hidden_act="relu2"), "SwiGLU"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(use_sliding_window=True), "sliding window"),
    (dict(norm_topk_prob=False), "renormalises"),
    (dict(linear_num_value_heads=24), "not a multiple"),
    (dict(partial_rotary_factor=0.3), "even number"),
    (dict(serve=dict(num_hidden_layers=4)), "depth 4"),
], ids=["half-a-period", "reduced-disagrees", "experts-held", "vocabulary",
        "deployment", "sparse-step", "dense-layers", "activation", "tied",
        "window", "topk-norm", "value-heads", "rotary", "section-depth"])
def test_family_file_refuses(fault, said):
    with pytest.raises(ValueError, match=said):
        program.build_model_config(broken(**fault), "serve")


def test_cell_is_found_by_name_and_reports_cell_fives_metrics_and_its_own():
    cell, five = spec.Cell(CELL), spec.Cell("nemotron3_super_serve_batchgen")
    assert (cell.chips, cell.kind, cell.entry["traffic"]) == (
        1, "serve", "longgen_closed")
    assert cell.config["family"] == "qwen3_next"
    assert spec.load_family(cell.config).__name__ == "family_qwen3_next"
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in five.end_to_end]
    mine = [m["name"] for m in cell.per_layer]
    theirs = [m["name"] for m in five.per_layer]
    own = ["gdn.busy_share_pct", "gdn_update_roofline",
           "experts_routed.busy_share_pct"]
    assert mine == [n for n in theirs if n not in (
        "ssm.busy_share_pct", "ssm_update_roofline",
        "experts.busy_share_pct")] + own
    assert "moe.busy_share_pct" not in mine
    new = {m["name"]: m for m in cell.per_layer[-3:]}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["better"]) for n, m in new.items()} == {
        "gdn.busy_share_pct": ("tpot_p50_ms", "lower"),
        "gdn_update_roofline": ("tpot_p50_ms", "higher"),
        "experts_routed.busy_share_pct": ("out_tok_s", "lower")}
    for name in own:
        assert callable(spec.load_reader(name).read)
    assert spec.load_kernel("gdn_update").BOUND == "bytes"
    serve = cell.config["serve"]
    assert (serve["slots"], serve["kv_pages"], serve["page_size"],
            serve["max_len"]) == (128, 20480, 16, 3072)
    # Every slot's longest request fits the pool at once.
    longest = (cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"])
    assert longest <= serve["max_len"]
    assert serve["slots"] * -(-longest // serve["page_size"]) <= \
        serve["kv_pages"]


def test_longgen_traffic_is_batchgens_prompts_and_longer_outputs():
    mine = spec.load_traffic("longgen_closed")
    theirs = spec.load_traffic("batchgen_closed")
    assert mine["prompt"] == theirs["prompt"]
    assert mine["output"] == {"dist": "lognormal", "median": 640,
                              "sigma": 0.45, "min": 256, "max": 1536}
    assert (mine["kind"], mine["clients_per_slot"], mine["block_per_slot"],
            mine["check_sample"], mine["lead_in"]) == (
        "closed", 2, 2, 24, {"new_from": 8, "new_to": 512})
    stream = traffic.Stream(mine, 3_300_000_007, 128, 37984)
    block = stream.totals(0)
    assert block["requests"] == 256
    assert block == traffic.Stream(mine, 5, 128, 37984).totals(0)
    # Answers several times the prompt; the same twelve prefill programs.
    assert 1.5 < block["output_tokens"] / block["prompt_tokens"] < 1.7
    assert stream.shapes == theirs["prompt"]["grid"]
    lead = stream.lead_in()
    assert (len(lead), lead[0].max_new, lead[-1].max_new) == (128, 8, 512)


def test_flops_count_the_share():
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    share = family.forward_flops_per_token(config, 8, 512)
    whole = copy.deepcopy(config)
    whole["num_experts"] = 512
    del whole["reduced"]["num_experts"]
    # All four shares' routed pairs: three more quarters of 10 pairs a
    # token in each of the eight layers.
    pair = 2 * 3 * 2048 * 512
    assert family.forward_flops_per_token(whole, 8, 512) - share == \
        pytest.approx(8 * 10 * 0.75 * pair)
    assert 0.8e9 < share < 1.0e9


# ---------------------------------------------------- the kernel's counts
def test_gdn_update_needs_each_live_rows_state_once_each_way():
    kernel = spec.load_kernel("gdn_update")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    state = 32 * 128 * 128 * 4
    assert state == 2_097_152                  # 2.10 MB a layer a row
    assert kernel.bytes_moved(128, 32, 128, 128) == 2 * 128 * state
    assert kernel.bytes_moved(0, 32, 128, 128) == 0
    assert kernel.flops(1, 32, 128, 128) == 7 * 32 * 128 * 128
    assert kernel.least_seconds(peaks, 128, 32, 128, 128) == pytest.approx(
        2 * 128 * state / 819e9)
    assert kernel.least_seconds(peaks, 128, 32, 128, 128) > (
        kernel.flops(128, 32, 128, 128) / 197e12)


# -------------------------------------------------------------- a whole run
def test_tiny_share_run_is_correct_and_the_control_is_not():
    cell = spec.Cell("tiny_qwen3_next_closed", spec.load_benchmark(DRY), DRY)
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
    # Readings at this size (three seeds): sound 0.0009-0.0020, the
    # control 0.0047-0.0108.
    assert ref["numbers"]["gap_mean"] < limit < ref["control"]["gap_mean"]
    assert ran["compiles_in_window"] == 0
    after = ran["stats"]["after"]
    # A row: three delta layers of 4 x 8 x 8 float32 state and 3 x 64
    # bfloat16 convolution inputs; a page: 16 tokens of one attention
    # layer's K and V.
    assert after["kv_state_bytes_per_slot"] == 3 * (4 * 8 * 8 * 4
                                                    + 3 * 64 * 2)
    assert after["kv_state_bytes_per_page"] == 0
    assert after["kv_page_bytes"] == 2 * 1 * 2 * 16 * 16 * 2
    assert after["prefill_tokens_skipped"] == 0
    assert after["kv_radix"]["pages"] == 0
    held = [sum(row) for row in after["moe_expert_tokens"]]
    assert len(held) == 4 and len(after["moe_expert_tokens"][0]) == 4
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
    with open(os.path.join(FIXTURES, "qwen3_next_ops.json")) as fh:
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


def test_recurrence_and_routed_expert_shares_match_the_recorded_names():
    kept, trace = recorded_trace()
    config = kept["config"]
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(trace)[0])
    gdn = spec.load_reader("gdn.busy_share_pct")
    routed = spec.load_reader("experts_routed.busy_share_pct")
    any_stack = spec.load_reader("experts.busy_share_pct")
    mine = gdn.recurrence_ops(ops, config)
    theirs = routed.expert_ops(ops, 2048, 512, 128, 8)
    assert sorted({label(ev) for ev in mine}) == kept["recurrence"]
    assert sorted({label(ev) for ev in theirs}) == kept["experts_routed"]
    assert not {ev["name"] for ev in mine} & {ev["name"] for ev in theirs}
    # The decode step: a read of a layer's rows for both products with
    # the state and the in-place update, six delta layers; three batched
    # matmuls an expert block. The prefill: the triangular solve (six,
    # on the system's diagonal blocks), the carried state, and three
    # grouped matmuls a block whose output something reads (seven of
    # eight: the last feeds nothing the program returns).
    assert sum(ev["shape"] == "f32[6,128,32,128,128]" for ev in mine) == 7
    assert sum(ev["shape"] == "f32[128,32,2,128]" for ev in mine) == 6
    assert sum(ev["shape"] == "f32[1,5,32,1,64,64]"
               and ev["opcode"] == "custom-call" for ev in mine) == 6
    assert sum(ev["op"].startswith("ragged-dot-none") for ev in theirs) == 21
    assert sum(ev["shape"] in ("bf16[128,128,512]", "bf16[128,2048,1]")
               for ev in theirs) == 24
    # The shared expert has the routed experts' own 2,048 x 512 (and
    # the gated attention's query projection is 16 heads of 512 x
    # 2,048): the reader that asks for a stack alone counts their
    # matmuls too, which is why this cell lists the one that asks for
    # the experts' count.
    both = any_stack.expert_ops(ops, 2048, 512)
    assert sorted({label(ev) for ev in both}) == kept["experts_any_stack"]
    assert {ev["name"] for ev in theirs} < {ev["name"] for ev in both}
    shared = [ev for ev in both if ev["name"] not in
              {e["name"] for e in theirs}]
    assert len(shared) > 30
    assert not any(re.search(r"bf16\[(\d+,)*(128|1024),(2048,512|512,2048)\]",
                             ev["name"]) for ev in shared)
    assert any("bf16[16,512,2048]" in ev["name"] for ev in shared)
    assert any("bf16[2,2048,512]" in ev["name"] for ev in shared)
    names = {ev["op"] for ev in mine} | {ev["op"] for ev in theirs}
    assert not names & {"paged_decode.2", "paged_decode.3", "fusion.561",
                        "slice_bitcast_fusion"}
    busy = trace_reduce.busy(trace)
    ctx = {"kind": "serve", "trace": trace, "config": config, "busy": busy}
    assert gdn.read(ctx) == pytest.approx(
        100 * sum(ev["dur"] for ev in mine) / busy["busy_s"])
    assert routed.read(ctx) == pytest.approx(
        100 * sum(ev["dur"] for ev in theirs) / busy["busy_s"])
    # One decode step beside one prefill (the window holds about five
    # steps a prefill: PERF.md §5).
    assert 15 < gdn.read(ctx) < 30 and 35 < routed.read(ctx) < 50
    assert any_stack.read({**ctx, "config": {
        **config, "n_routed_experts": 128}}) > routed.read(ctx)
    # Nothing to read: another family, no trace, other widths.
    dense = {"hidden_size": 2048, "moe_intermediate_size": 512}
    assert gdn.read({**ctx, "config": dense}) is None
    assert routed.read({**ctx, "config": dense}) is None
    assert gdn.read({**ctx, "trace": None}) is None
    assert routed.read({**ctx, "trace": None}) is None
    assert gdn.read({**ctx, "config": {
        **config, "linear_num_value_heads": 16}}) is None
    assert routed.read({**ctx, "config": {**config, "num_experts": 64,
                                          "serve": {}}}) is None


def test_update_roofline_reads_the_decode_steps_state_operations():
    kept, trace = recorded_trace()
    config = kept["config"]
    module = spec.load_reader("gdn_update_roofline")
    assert module.delta_layers(config) == 6
    plane = trace_reduce.device_planes(trace)[0]
    steps = trace_reduce.module_events(trace, r"^jit_decode_step")
    assert len(steps) == 1
    mine = module.update_ops(trace_reduce.leaf_ops(plane), steps, config)
    # A layer's read and its in-place update, six layers; the prefill's
    # write of its row lies outside the decode program.
    assert sorted(ev["shape"] for ev in mine) == (
        ["f32[128,32,2,128]"] * 6 + ["f32[6,128,32,128,128]"] * 6)
    busy = trace_reduce.busy(trace)
    rows = [{"token_times": [0.0, 10.0], "n_out": 2, "max_new": 8,
             "prompt_len": 100, "error": None} for _ in range(128)]
    ctx = {"kind": "serve", "trace": trace, "config": config, "busy": busy,
           "trace_wall_t0": 1.0 + busy["t0"], "records": rows,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    least = 6 * 2 * 128 * 32 * 128 * 128 * 4 / 819e9
    assert module.read(ctx) == pytest.approx(
        100 * least / sum(ev["dur"] for ev in mine))
    assert 50 < module.read(ctx) < 60      # three passes where two would do
    half = {**ctx, "records": rows[:64]}
    assert module.read(half) == pytest.approx(module.read(ctx) / 2)
    assert module.read({**ctx, "trace": None}) is None
    assert module.read({**ctx, "config": {"hidden_size": 2048}}) is None
