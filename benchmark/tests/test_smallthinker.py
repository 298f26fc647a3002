"""The SmallThinker configuration's own files: what its family file
registers in the program, field by field; its cut, its deployment and
its assumptions held against the published keys, with the parameter
count reckoned from them; what the family refuses; its cell, its
traffic, its kernel's counts against a hand count, its four readers on
recorded data; and a whole run of the tiny model on the CPU (sound:
correct; the int8 control: outside the tiny limit)."""

import copy
import json
import os

import jax.numpy as jnp
import pytest

import run
from harness import program, spec, trace_reduce, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DRY = os.path.join(FIXTURES, "dry_smallthinker")
CELL = "smallthinker_21b_serve_longctx"
NAME = "smallthinker_21b_a3b"
PERIOD = [0, 1, 1, 1]

# PowerInfer/SmallThinker-21BA3B-Instruct config.json, as the catalog of
# public architectures holds it (model-configs guide,
# `architectures.jsonl`).
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
CUT = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        config = json.load(fh)
    config["_path"] = os.path.join(BENCH, path)
    return config


REGISTERED = {
    f"configs/{NAME}.json": dict(
        vocab_size=151936, dim=2560, n_layers=8, n_heads=28, n_kv_heads=4,
        head_dim=128, rope_theta=1.5e6, rope_layout=tuple(PERIOD * 2),
        window_layout=tuple(PERIOD * 2), sliding_window=4096, n_experts=64,
        experts_per_token=6, moe_ffn_dim=768, norm_eps=1e-6,
        max_seq_len=16384, dtype=jnp.bfloat16),
    "tests/fixtures/dry_smallthinker/configs/tiny_smallthinker.json": dict(
        vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, sliding_window=32, n_experts=8, experts_per_token=2,
        moe_ffn_dim=32, max_seq_len=256, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("path", sorted(REGISTERED))
def test_family_file_registers_these_fields(path):
    module, cfg = program.build_model_config(read(path), "serve")
    assert module.__name__ == "polyaxon_tpu.models.smallthinker"
    assert type(cfg).__name__ == "SmallThinkerConfig"
    for field, value in REGISTERED[path].items():
        assert getattr(cfg, field) == value, field
    assert [(kind, rotary) for kind, _, rotary in module.layer_plan(cfg)[:4]
            ] == [("full", False), ("window", True), ("window", True),
                  ("window", True)]
    assert module.paged_window(cfg) == cfg.sliding_window


def test_cut_deployment_and_assumptions_against_the_published_keys():
    config = read(f"configs/{NAME}.json")
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == CUT == list(config["reduced"])
    for key, value in PUBLISHED.items():        # every published key is there
        if key in config["reduced"]:
            assert config["reduced"][key]["source"] == value, key
            assert config["reduced"][key]["serve"] == config[key], key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    # No width, no expert and no row of the vocabulary is cut: two whole
    # periods of the layouts, each layer whole on one chip.
    assert config["num_hidden_layers"] == 8
    assert config["rope_layout"] == config["sliding_window_layout"] == \
        PERIOD * 2
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"],
            deployment["first_layer"]) == (1, 0)
    assert {"router_tap", "expert_activation", "bias_and_qk_norm",
            "rotary_dim", "window", "secondary_experts", "router_precision",
            "init", "torch_dtype"} <= set(config["assumed"])
    assert deployment["how"] and config["dtype"] and config["check_why"]
    assert set(config["check"]["serve"]) == {"gap_mean", "gap_max"}


def test_parameters_from_the_files_keys_are_3967_million():
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    n = family.parameters(config)
    assert n == {"attn": 20_971_520, "router": 163_840, "expert": 5_898_240,
                 "norms": 5_120, "table": 388_956_160}
    layer = n["attn"] + n["router"] + n["norms"] + 64 * n["expert"]
    assert layer == 398_627_840
    here = family.parameters_here(config, 8)
    assert here == 8 * layer + 2 * n["table"] + 2560 == 3_966_937_600
    assert round(2 * here / 1e9, 2) == 7.93
    # The same count of the whole published model: 21B-A3B.
    assert round(family.parameters_here(config, 52) / 1e9, 1) == 21.5
    active = family.parameters_here(config, 52, active=True) - n["table"]
    assert round(active / 1e9, 1) == 3.3        # the table is looked up
    # The serve section's arithmetic: both page spaces beside the weights.
    serve = config["serve"]
    token = 2 * config["num_key_value_heads"] * config["head_dim"] * 2
    assert token == 2048
    full = serve["kv_pages"] * 2 * serve["page_size"] * token
    rows = config["sliding_window_size"] // serve["page_size"] + 1
    window = serve["slots"] * rows * 6 * serve["page_size"] * token
    assert (rows, round(full / 1e9, 2), round(window / 1e9, 2)) == (
        257, 1.61, 1.62)
    assert 11.0e9 < 2 * here + full + window < 11.3e9
    one_space = serve["kv_pages"] * 8 * serve["page_size"] * token
    assert round(one_space / 1e9, 1) == 6.4


def broken(**changes):
    config = copy.deepcopy(read(f"configs/{NAME}.json"))
    config.update(changes)
    return config


@pytest.mark.parametrize("fault, said", [
    (dict(num_hidden_layers=6), "names 8 layers"),
    (dict(num_hidden_layers=6, rope_layout=PERIOD + [0, 1],
          sliding_window_layout=PERIOD + [0, 1]), "whole periods"),
    (dict(rope_layout=[1] * 8), "`reduced.rope_layout` says"),
    (dict(sliding_window_layout=[0] * 8), "side by side"),
    (dict(moe_primary_router_apply_softmax=False), "softmax"),
    (dict(norm_topk_prob=False), "renormalises"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(rope_scaling={"factor": 2.0}), "rope scaling"),
    (dict(num_attention_heads=30), "not a multiple"),
    (dict(deployment=dict(chips_sharing_a_layer=4)), "whole on one chip"),
    (dict(serve=dict(num_hidden_layers=4)), "depth 4"),
], ids=["layouts-and-depth", "half-a-period", "reduced-disagrees",
        "no-window-layer", "router", "topk-norm", "tied", "rope-scaling",
        "heads", "deployment", "section-depth"])
def test_family_file_refuses(fault, said):
    with pytest.raises(ValueError, match=said):
        program.build_model_config(broken(**fault), "serve")


def test_cell_is_found_by_name_and_reports_these_metrics():
    cell, six = spec.Cell(CELL), spec.Cell("qwen3_next_serve_longgen")
    assert (cell.chips, cell.kind, cell.entry["traffic"]) == (
        1, "serve", "longctx_closed")
    assert cell.config["family"] == "smallthinker"
    assert spec.load_family(cell.config).__name__ == "family_smallthinker"
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}
    own = {"window.busy_share_pct", "window_decode_roofline",
           "kv.window_roll_us_per_step", "experts_primary.busy_share_pct"}
    mine = {m["name"] for m in cell.per_layer}
    theirs = {m["name"] for m in six.per_layer}
    assert mine == (theirs - {"gdn.busy_share_pct", "gdn_update_roofline",
                              "experts_routed.busy_share_pct"}) | own
    assert not mine & {"moe.busy_share_pct", "kv.prefix_hit_pct",
                       "ssm.busy_share_pct"}
    new = {m["name"]: m for m in cell.per_layer if m["name"] in own}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["better"], m["unit"]) for n, m in new.items()
            } == {
        "window.busy_share_pct": ("tpot_p50_ms", "lower", "%"),
        "window_decode_roofline": ("tpot_p50_ms", "higher", "%"),
        "kv.window_roll_us_per_step": ("tpot_p50_ms", "lower", "us"),
        "experts_primary.busy_share_pct": ("out_tok_s", "lower", "%")}
    for name in own:
        assert callable(spec.load_reader(name).read)
    assert spec.load_kernel("window_decode").BOUND == "bytes"
    serve = cell.config["serve"]
    assert (serve["slots"], serve["kv_pages"], serve["page_size"],
            serve["max_len"]) == (32, 24576, 16, 16384)
    longest = cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"]
    assert longest == 14336 <= serve["max_len"]
    assert len(cell.entry["why"]) <= 200


def test_longctx_traffic_is_several_windows_of_prompt_and_a_page_of_answer():
    mine = spec.load_traffic("longctx_closed")
    assert mine["prompt"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.55, "min": 2048,
        "max": 12288, "grid": [2048, 2560, 3072, 4096, 5120, 6144, 7168,
                               8192, 9216, 10240, 11264, 12288]}
    assert mine["output"] == {"dist": "lognormal", "median": 768,
                              "sigma": 0.45, "min": 256, "max": 2048}
    assert (mine["kind"], mine["clients_per_slot"], mine["block_per_slot"],
            mine["lead_in"], mine["warmup_new"], mine["shared_prefix"],
            mine["eos_tokens"], mine["temperature"]) == (
        "closed", 2, 2, {"new_from": 8, "new_to": 512}, 2, None, [], 0.0)
    assert mine["check_sample"] >= 8
    stream = traffic.Stream(mine, 3_300_000_007, 32, 151936)
    block = stream.totals(0)
    assert block["requests"] == 64
    assert block == traffic.Stream(mine, 5, 32, 151936).totals(0)
    assert stream.shapes == mine["prompt"]["grid"]       # twelve programs
    # Inputs several times the answers; most rows pass the window.
    assert 7 < block["prompt_tokens"] / block["output_tokens"] < 9
    lens = [len(r.tokens) for r in stream.block(0)]
    assert sum(n > 4096 for n in lens) > 0.6 * len(lens)
    assert sum(n < 4096 for n in lens) > 0.1 * len(lens)
    # The longest 32 requests of a block at once, grown to their ends,
    # fit the full space.
    pages = sorted((-(-(len(r.tokens) + r.max_new) // 16)
                    for r in stream.block(0)), reverse=True)[:32]
    assert sum(pages) < 24576


def test_flops_count_the_keys_a_mask_leaves():
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    short = family.forward_flops_per_token(config, 8, 2048)
    long_ = family.forward_flops_per_token(config, 8, 12288)
    q = 28 * 128
    # Past the window only the two full layers see more keys.
    assert long_ - short == pytest.approx(
        4 * q * (2 * (12288 - 2048) + 6 * (4096 - 2048)))
    assert 1.8e9 < short < 2.0e9     # 0.78e9 of it the head


# ---------------------------------------------------- the kernel's counts
def test_window_decode_needs_the_windows_pages_and_nothing_behind_them():
    kernel = spec.load_kernel("window_decode")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    page_bytes = 4 * 16 * 128 * 2                # one of K or V: 16 KB
    assert page_bytes == 16384
    qo = 2 * 28 * 128 * 2
    # By hand. 100 positions: pages 0..6. 4,096: all 256 pages. 4,097:
    # positions 1..4096, pages 0..256: 257. 7,000: positions 2904..6999,
    # pages 181..437: 257. 4,112: positions 16..4111, pages 1..256: 256.
    for n, pages in [(100, 7), (4096, 256), (4097, 257), (7000, 257),
                     (4112, 256), (1, 1), (0, 0)]:
        assert kernel.window_pages(n, 16, 4096) == pages, n
    assert kernel.bytes_moved([7000], 16, 4, 28, 128, 4096) == (
        2 * 257 * page_bytes + qo)
    assert kernel.bytes_moved([100, 0, 7000, -1], 16, 4, 28, 128, 4096) == (
        2 * (7 + 257) * page_bytes + 2 * qo)
    assert kernel.flops([100, 7000], 28, 128, 4096) == (
        4 * (100 + 4096) * 28 * 128)
    # The same rows through the full layers' count read every page.
    full = spec.load_kernel("paged_decode")
    assert full.bytes_moved([7000], 16, 4, 28, 128) == (
        2 * 438 * page_bytes + qo)
    least = kernel.least_seconds(peaks, [7000] * 32, 16, 4, 28, 128, 4096)
    assert least == pytest.approx(32 * (2 * 257 * page_bytes + qo) / 819e9)
    assert least > kernel.flops([7000] * 32, 28, 128, 4096) / 197e12


# -------------------------------------------------------------- a whole run
def test_tiny_run_is_correct_and_the_control_is_not():
    cell = spec.Cell("tiny_smallthinker_closed", spec.load_benchmark(DRY),
                     DRY)
    seed = 3_000_000_019
    final = run.run_cell(cell, seed=seed, seconds=3, trace=True,
                         require_chip=False, control=True)
    assert final["correct"] is True and final["failed"] == 0
    # Off the chip the trace's readers find nothing; the counters' do.
    assert set(final["metrics"]) == {
        "engine.avg_occupancy", "moe.expert_load_max_over_mean",
        "kv.window_roll_us_per_step"}
    out_dir = os.path.join(run.ROOT, ".benchmark_out",
                           f"{cell.name}-{seed}-1")
    with open(os.path.join(out_dir, "reference.json")) as fh:
        ref = json.load(fh)
    with open(os.path.join(out_dir, "program.json")) as fh:
        ran = json.load(fh)
    limit = cell.config["check"]["serve"]["gap_mean"]
    # Readings at this size: sound 0.0010, the control 0.0023.
    assert ref["numbers"]["gap_mean"] < limit < ref["control"]["gap_mean"]
    assert ran["compiles_in_window"] == 0
    # The longest compared request is several windows long.
    assert max(r["prompt_len"] + r["served"] for r in ref["requests"]) > 96
    after = ran["stats"]["after"]
    # A full page: one layer's K and V; a window page: three layers'.
    assert after["kv_page_bytes"] == 2 * 1 * 2 * 16 * 16 * 2
    assert after["kv_window_page_bytes"] == 2 * 3 * 2 * 16 * 16 * 2
    assert after["kv_window"] == 32
    assert after["kv_window_pages_total"] == 4 * 3
    assert after["kv_window_row_pages_max"] == 3
    assert after["kv_window_pages_released"] > 0
    assert after["kv_window_pages_live"] == 0
    assert after["kv_invariant_violations"] == 0
    assert after["prefill_tokens_skipped"] == 0
    assert after["kv_radix"]["pages"] == 0
    assert after["tick_phase_ns"]["step.window"] > 0
    held = after["moe_expert_tokens"]
    assert len(held) == 4 and len(held[0]) == 8
    spans = next(iter(ran["timelines"].values()))
    assert "prefill" in spans


# ------------------------------------------------------------ the readers
def recorded_trace():
    """One decode step and one prefill program of the kept trace, laid
    end to end under a module event each."""
    with open(os.path.join(FIXTURES, "smallthinker_ops.json")) as fh:
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


def test_window_and_expert_shares_match_the_recorded_names():
    kept, trace = recorded_trace()
    config = kept["config"]
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(trace)[0])
    window = [ev for ev in ops if ev["op"].startswith("window_decode")]
    full = [ev for ev in ops if ev["op"].startswith("paged_decode")]
    # Six window layers and two full ones a decode program, told apart
    # by the call's name alone: the full layers' readers see two calls.
    assert sorted(label(ev) for ev in window) == kept["window_decode"]
    assert sorted(label(ev) for ev in full) == kept["paged_decode"]
    assert (len(window), len(full)) == (6, 2)
    assert trace_reduce.seconds_matching(trace, r"^paged_decode")[1] == 2
    assert trace_reduce.seconds_matching(trace, r"^window_decode")[1] == 6
    assert len(trace_reduce.modules_running(trace, r"^paged_decode")) == 1
    routed = spec.load_reader("experts_routed.busy_share_pct")
    mine = routed.expert_ops(ops, 2560, 768, 64, 8)
    assert sorted({label(ev) for ev in mine}) == kept["experts_primary"]
    # The decode step: three batched matmuls an expert block over a
    # layer's slice of the stack bf16[8,64,2560,768] / [8,64,768,2560],
    # eight layers. The prefill: three grouped matmuls a block over the
    # stack handed whole, bf16[512,2560,768] / [512,768,2560], seven of
    # eight blocks (the last feeds nothing the program returns).
    assert sum("bf16[8,64," in ev["name"] for ev in mine) == 24
    grouped = [ev for ev in mine if ev["op"].startswith("grouped_matmul")]
    assert len(grouped) == 21 and len(mine) == 45
    assert all("bf16[512," in ev["name"] for ev in grouped)
    assert sum(ev["op"].startswith("flash_fwd") for ev in ops) == 7
    assert not {ev["name"] for ev in mine} & {
        ev["name"] for ev in window + full}
    busy = trace_reduce.busy(trace)
    ctx = {"kind": "serve", "trace": trace, "config": config, "busy": busy}
    share = spec.load_reader("window.busy_share_pct")
    experts = spec.load_reader("experts_primary.busy_share_pct")
    assert share.read(ctx) == pytest.approx(
        100 * sum(ev["dur"] for ev in window) / busy["busy_s"])
    assert experts.read(ctx) == pytest.approx(
        100 * sum(ev["dur"] for ev in mine) / busy["busy_s"])
    # One decode step beside one prefill of 2,047 tokens (the traced
    # window held about eleven steps a prefill and read 18.4 and 48.9:
    # PERF.md §5).
    assert 5 < share.read(ctx) < 7 and 40 < experts.read(ctx) < 46
    assert spec.load_reader("paged.busy_share_pct").read(ctx) < share.read(
        ctx)
    # Nothing to read: no trace, another family's keys, other widths,
    # a program without the windowed call.
    assert share.read({**ctx, "trace": None}) is None
    assert experts.read({**ctx, "trace": None}) is None
    assert experts.read({**ctx, "config": {"hidden_size": 2560}}) is None
    assert experts.read({**ctx, "config": {
        **config, "moe_ffn_hidden_size": 512}}) is None
    plain = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": line["name"], "events": [
            ev for ev in line["events"]
            if not ev["name"].startswith("%window_decode")]}
        for line in trace["planes"][0]["lines"]]}]}
    assert share.read({**ctx, "trace": plain}) is None
    assert spec.load_reader("window_decode_roofline").read(
        {**ctx, "trace": plain}) is None


def test_window_roofline_counts_a_rows_window_and_not_its_length():
    kept, trace = recorded_trace()
    config = kept["config"]
    module = spec.load_reader("window_decode_roofline")
    busy = trace_reduce.busy(trace)
    window = [ev for ev in trace_reduce.leaf_ops(
        trace_reduce.device_planes(trace)[0])
        if ev["op"].startswith("window_decode")]

    def rows(n, count=32):
        return [{"token_times": [0.0, 10.0], "n_out": 2, "max_new": 8,
                 "prompt_len": n - 1, "error": None} for _ in range(count)]

    ctx = {"kind": "serve", "trace": trace, "config": config, "busy": busy,
           "trace_wall_t0": 1.0 + busy["t0"], "records": rows(7000),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    page = 4 * 16 * 128 * 2
    least = 6 * 32 * (2 * 257 * page + 2 * 28 * 128 * 2) / 819e9
    assert module.read(ctx) == pytest.approx(
        100 * least / sum(ev["dur"] for ev in window))
    # 32 rows past the window keep the kernel at about what the traced
    # window read (59.6) and under its roofline.
    assert 50 < module.read(ctx) < 70
    # Twice as long a row needs no more; a row inside the window less.
    assert module.read({**ctx, "records": rows(14008)}) == pytest.approx(
        module.read(ctx))
    assert module.read({**ctx, "records": rows(2048)}) == pytest.approx(
        module.read(ctx) * (2 * 128 * page + 14336) / (2 * 257 * page + 14336))
    # The full layers' reader counts the same rows whole, two calls.
    full = spec.load_reader("paged_decode_roofline").read(ctx)
    paged = [ev for ev in trace_reduce.leaf_ops(
        trace_reduce.device_planes(trace)[0])
        if ev["op"].startswith("paged_decode")]
    assert full == pytest.approx(
        100 * 2 * 32 * (2 * 438 * page + 14336) / 819e9
        / sum(ev["dur"] for ev in paged))
    assert module.read({**ctx, "trace": None}) is None
    assert module.read({**ctx, "config": {"hidden_size": 2560}}) is None


def test_window_roll_reads_its_phase_over_the_windows_steps():
    module = spec.load_reader("kv.window_roll_us_per_step")

    def stats(steps, roll=None):
        phases = {"step.emit": 10 * steps, "admit.match": 5}
        if roll is not None:
            phases["step.window"] = roll
        return {"decode_steps": steps, "tick_phase_ns": phases}

    ctx = {"kind": "serve", "stats": {"open": stats(100, 4_000_000),
                                      "close": stats(2100, 96_000_000)}}
    assert module.read(ctx) == pytest.approx(46.0)
    # An engine without a window space has no such phase; a timed run
    # reads no edges; a window without a step has nothing to divide by.
    assert module.read({"kind": "serve", "stats": {
        "open": stats(100), "close": stats(2100)}}) is None
    assert module.read({"kind": "serve", "stats": {"after": stats(5, 1)}}
                       ) is None
    assert module.read({"kind": "serve", "stats": {
        "open": stats(100, 1), "close": stats(100, 2)}}) is None
    assert module.read({"kind": "train", "stats": {}}) is None
