"""The Kimi-K2.6 configuration's own files: what its family file
registers in the program, field by field; its cut, its deployment and
its assumptions held against the published keys, with the parameter
count reckoned from them and counted again off the program's own tree;
what the family refuses; its cell and its traffic as the issue gives
them; its kernel's counts against a hand count; its three readers on
recorded data; and a whole run of the tiny model on the CPU (sound:
correct, every request behind a cached document; the int8 control:
outside the tiny limit)."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import pytest

import run
from harness import program, spec, trace_reduce, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DRY = os.path.join(FIXTURES, "dry_kimi_k2")
CELL = "kimi_k2_serve_docqa"
NAME = "kimi_k2_6"

# moonshotai/Kimi-K2.6 config.json, as the catalog of public
# architectures holds it (model-configs guide, `architectures.jsonl`).
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "kimi_k2", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
CUT = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        config = json.load(fh)
    config["_path"] = os.path.join(BENCH, path)
    return config


REGISTERED = {
    f"configs/{NAME}.json": dict(
        vocab_size=20480, dim=7168, n_layers=5, n_heads=64, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=50000.0, rope_factor=64.0,
        rope_original_max=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
        ffn_dim=18432, first_dense=1, n_experts=384, experts_per_token=8,
        moe_ffn_dim=2048, routed_scaling_factor=2.827,
        held_experts=(0, 12), norm_eps=1e-5, max_seq_len=18432,
        dtype=jnp.bfloat16, latent_width=576, latent_pad=640),
    "tests/fixtures/dry_kimi_k2/configs/tiny_kimi_k2.json": dict(
        vocab_size=512, dim=64, n_layers=3, n_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_factor=4.0, ffn_dim=96, n_experts=16,
        experts_per_token=4, moe_ffn_dim=32, held_experts=(4, 4),
        max_seq_len=128, dtype=jnp.bfloat16, latent_pad=128),
}


@pytest.mark.parametrize("path", sorted(REGISTERED))
def test_family_file_registers_these_fields(path):
    module, cfg = program.build_model_config(read(path), "serve")
    assert module.__name__ == "polyaxon_tpu.models.kimi_k2"
    assert type(cfg).__name__ == "KimiK2Config"
    for field, value in REGISTERED[path].items():
        assert getattr(cfg, field) == value, field
    kinds = [(kind, ffn) for kind, _, ffn, _ in module.FAMILY.layers(cfg)]
    assert kinds[:3] == [("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    assert abs(cfg.softmax_scale - (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
        * (0.1 * jnp.log(cfg.rope_factor) + 1) ** 2) < 1e-6


def test_cut_deployment_and_assumptions_against_the_published_keys():
    config = read(f"configs/{NAME}.json")
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-K2.6/blob/main/config.json")
    assert entry["reduced"] == CUT == list(config["reduced"])
    for key, value in PUBLISHED.items():        # every published key is there
        if key in config["reduced"]:
            assert config["reduced"][key]["source"] == value, key
            assert config["reduced"][key]["serve"] == config[key], key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    # No width is cut: the depth, the chip's share of the experts and of
    # the vocabulary.
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 12, 20480)
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["rank"],
            deployment["vocab_shards"], deployment["first_layer"]) == (
        32, 0, 8, 0)
    assert 12 * 32 == 384 and 20480 * 8 == 163840
    assert "56 layers" in deployment["how"]
    assert {"vision_tower", "rotary_pairs", "kv_b_split",
            "norm_sum_epsilon", "latent_padding"} <= set(config["assumed"])
    assert config["dtype"] and config["check_why"] and config["serve"]["why"]
    assert set(config["check"]["serve"]) == {"gap_mean", "gap_max"}


def test_parameters_from_the_files_keys_are_3497_million():
    """By part from the file's keys, and the same total counted off the
    shapes the program's own draw gives. (ISSUE 47's sum, 3,496,774,144,
    counts q_a's and kv_a's norm gains, 2,048 a layer, twice.)"""
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    n = family.parameters(config)
    assert n == {"attn": 101_124_096, "dense": 396_361_728,
                 "beside": 2_752_896 + 44_040_192, "expert": 44_040_192,
                 "norms": 14_336, "table": 146_800_640}
    dense = n["attn"] + n["norms"] + n["dense"]
    expert = n["attn"] + n["norms"] + n["beside"] + 12 * n["expert"]
    assert (dense, expert) == (497_500_160, 676_413_824)
    here = family.parameters_here(config, 5)
    assert here == dense + 4 * expert + 2 * n["table"] + 7168 == 3_496_763_904
    assert here == 3_496_774_144 - 5 * 2_048
    assert round(2 * here / 1e9, 2) == 6.99
    module, cfg = program.build_model_config(config, "serve")
    shapes = jax.eval_shape(lambda: module.init(cfg, jax.random.key(0)))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == here
    # The same count of the whole published model: 1.03 T, 32 B active.
    whole = copy.deepcopy(config)
    whole.update(num_hidden_layers=61, n_routed_experts=384,
                 vocab_size=163840, reduced={}, deployment={})
    assert round(family.parameters_here(whole, 61) / 1e12, 2) == 1.03
    active = family.parameters_here(whole, 61, active=True) - n["table"] * 8
    assert round(active / 1e9) == 32            # the table is looked up
    # The serve section's arithmetic: the latent pages beside the weights.
    serve = config["serve"]
    token = 5 * 640 * 2
    assert token == 6400 and 5 * 576 * 2 == 5760
    pool = (serve["kv_pages"] + 1) * serve["page_size"] * token
    assert round(pool / 1e9, 2) == 3.36
    assert 10.3e9 < 2 * here + pool < 10.4e9
    documents = 24 * 16384 // serve["page_size"]
    rows = serve["slots"] * -(-1280 // serve["page_size"])
    assert (documents, rows) == (24576, 5120)
    assert documents + rows <= serve["kv_pages"]


def broken(**changes):
    config = copy.deepcopy(read(f"configs/{NAME}.json"))
    config.update(changes)
    return config


@pytest.mark.parametrize("fault, said", [
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(n_group=8), "n_group"),
    (dict(n_shared_experts=2), "one shared expert"),
    (dict(rope_scaling=None), "yarn"),
    (dict(rope_scaling={**PUBLISHED["rope_scaling"], "mscale": 0.7}), "yarn"),
    (dict(num_key_value_heads=8), "a key and a value a head"),
    (dict(n_routed_experts=16), "reduced.n_routed_experts"),
    (dict(num_hidden_layers=1, reduced={}, n_routed_experts=384,
          vocab_size=163840, deployment={}), "an expert layer"),
    (dict(deployment={"chips_sharing_a_layer": 16, "rank": 0}),
     "do not hold"),
    (dict(tie_word_embeddings=True), "untied"),
])
def test_family_file_refuses(fault, said):
    with pytest.raises(ValueError, match=said):
        program.build_model_config(broken(**fault), "serve")


def test_cell_is_found_by_name_and_reports_these_metrics():
    cell, seven = spec.Cell(CELL), spec.Cell("smallthinker_21b_serve_longctx")
    assert (cell.chips, cell.kind, cell.entry["traffic"]) == (
        1, "serve", "docqa_closed")
    assert cell.config["family"] == "kimi_k2"
    assert spec.load_family(cell.config).__name__ == "family_kimi_k2"
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}
    own = {"mla.busy_share_pct", "mla_decode_roofline",
           "experts_k2.busy_share_pct"}
    mine = {m["name"] for m in cell.per_layer}
    theirs = {m["name"] for m in seven.per_layer}
    # Cell 7's, less the readers of kernels this program does not run,
    # with the radix cache's hit share and the three of its own.
    assert mine == (theirs - {
        "window.busy_share_pct", "window_decode_roofline",
        "kv.window_roll_us_per_step", "experts_primary.busy_share_pct",
        "paged.busy_share_pct", "paged_decode_roofline",
        "model.decode_step_ms"}      # finds its program by `paged_decode`
        ) | own | {"kv.prefix_hit_pct"}
    assert {"moe.expert_load_max_over_mean", "grouped.busy_share_pct",
            "kv.admit_match_us", "engine.idle_admit_ms"} <= mine
    new = {m["name"]: m for m in cell.per_layer if m["name"] in own}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["better"], m["unit"]) for n, m in new.items()
            } == {
        "mla.busy_share_pct": ("tpot_p50_ms", "lower", "%"),
        "mla_decode_roofline": ("tpot_p50_ms", "higher", "%"),
        "experts_k2.busy_share_pct": ("out_tok_s", "lower", "%")}
    for name in own:
        assert callable(spec.load_reader(name).read)
    assert spec.load_kernel("mla_decode").BOUND == "bytes"
    serve = cell.config["serve"]
    assert (serve["slots"], serve["kv_pages"], serve["page_size"],
            serve["max_len"]) == (64, 32768, 16, 18432)
    bench = spec.load_benchmark()
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 6
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert len(cell.entry["why"]) <= 200


def test_docqa_traffic_is_the_issues_letter_for_letter():
    cell = spec.Cell(CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["clients_per_slot"], mix["block_per_slot"]) == (
        "closed", 2, 2)
    assert mix["shared_prefix"] == {"count": 24, "tokens": 16384, "zipf": 1.0}
    assert mix["prompt"] == {"dist": "loguniform", "min": 32, "max": 256,
                             "grid": [32, 48, 64, 96, 128, 192, 256]}
    assert mix["output"] == {"dist": "lognormal", "median": 320,
                             "sigma": 0.45, "min": 96, "max": 1024}
    assert (mix["warmup_new"], mix["lead_in"], mix["check_sample"],
            mix["temperature"], mix["eos_tokens"]) == (
        2, {"new_from": 8, "new_to": 640}, 8, 0.0, [])
    stream = traffic.Stream(mix, 3_000_000_019, cell.config["serve"]["slots"],
                            cell.config["vocab_size"])
    block = stream.block(0)
    assert len(block) == 128
    assert all(len(r.tokens) - 16384 in mix["prompt"]["grid"] for r in block)
    longest = max(len(r.tokens) + r.max_new for r in block)
    assert longest <= 16384 + 256 + 1024 <= cell.config["serve"]["max_len"]
    # Every document is asked about in every block, the first most.
    uses = [sum(r.prefix == k for r in block) for k in range(24)]
    assert min(uses) >= 1 and uses[0] == max(uses) >= 30
    # Every request's first own token is its own: no page forks.
    firsts = [r.tokens[16384] for r in block + stream.block(1)]
    assert len(set(firsts)) == len(firsts)
    warm = stream.warmup()
    assert len(warm) == 24 + 7
    assert {len(r.tokens) for r in warm[:24]} == {16384 + 32}


def test_mla_decode_needs_the_published_latent_and_nothing_made_of_it():
    kernel = spec.load_kernel("mla_decode")
    rows = [16_700] * 64 + [0, -1]
    moved = kernel.bytes_moved(rows, 64, 576, 512)
    assert moved == 64 * (16_700 * 1152 + 64 * (576 + 512) * 2)
    assert round(5 * moved / 1e9, 2) == 6.2       # a step's five calls
    assert round(5 * 64 * 16_700 * 1152 / 1e9, 2) == 6.16     # the latents'
    work = kernel.flops(rows, 64, 576, 512)
    assert work == 64 * 2 * 16_700 * 64 * 1088
    assert round(5 * work / 1e12, 2) == 0.74
    assert round(work / (64 * 16_700 * 1152)) == 121     # a byte of latent
    assert work / moved < 240                     # under the chip's ridge
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    least = kernel.least_seconds(peaks, rows, 64, 576, 512)
    assert least == moved / 819e9 > work / 197e12
    assert round(5e3 * least, 1) == 7.6           # ms a step


# -------------------------------------------------------------- a whole run
def test_tiny_run_is_correct_and_the_control_is_not():
    cell = spec.Cell("tiny_kimi_k2_docqa", spec.load_benchmark(DRY), DRY)
    seed = 3_000_000_019
    final = run.run_cell(cell, seed=seed, seconds=3, trace=True,
                         require_chip=False, control=True)
    assert final["correct"] is True and final["failed"] == 0
    # Off the chip the trace's readers find nothing; the counters' do.
    assert set(final["metrics"]) == {
        "engine.avg_occupancy", "moe.expert_load_max_over_mean",
        "kv.prefix_hit_pct"}
    out_dir = os.path.join(run.ROOT, ".benchmark_out",
                           f"{cell.name}-{seed}-1")
    with open(os.path.join(out_dir, "reference.json")) as fh:
        ref = json.load(fh)
    with open(os.path.join(out_dir, "program.json")) as fh:
        ran = json.load(fh)
    limit = cell.config["check"]["serve"]["gap_mean"]
    # Readings at this size over four seeds: sound 0.0008-0.0013, the
    # control 0.0018-0.0029 on the same requests (which requests finish
    # follows the run's timing, so the control is held to the run's own
    # sound reading, not to the limit).
    sound = ref["numbers"]["gap_mean"]
    assert sound < limit and ref["control"]["gap_mean"] > 1.5 * sound
    assert ran["compiles_in_window"] == 0
    # Every measured request stood behind a cached document.
    assert final["metrics"]["kv.prefix_hit_pct"]["value"] > 50
    after = ran["stats"]["after"]
    assert after["kv_token_bytes"] == 3 * 128 * 2
    assert after["kv_page_bytes"] == 16 * after["kv_token_bytes"]
    assert after["kv_state_bytes_per_slot"] == 0
    assert after["kv_invariant_violations"] == 0
    assert after["kv_cow_forks"] == 0
    assert after["prefill_tokens_skipped"] > 0
    assert after["mla_decode_positions"] > after["decode_steps"]
    held = after["moe_expert_tokens"]
    assert len(held) == 2 and len(held[0]) == 4
    assert len(after["moe_pairs_elsewhere"]) == 2
    spans = next(iter(ran["timelines"].values()))
    assert "prefill" in spans


# ------------------------------------------------------------ the readers
def recorded_trace():
    """The decode step and the suffix prefill program of the kept trace,
    laid end to end under a module event each."""
    with open(os.path.join(FIXTURES, "kimi_k2_ops.json")) as fh:
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


def test_mla_and_expert_shares_match_the_recorded_names():
    kept, trace = recorded_trace()
    config = kept["config"]
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(trace)[0])
    mla = [ev for ev in ops if ev["op"].startswith("mla_decode")]
    assert len(mla) == 5                  # one call a layer a decode step
    assert trace_reduce.seconds_matching(trace, r"^mla_decode")[1] == 5
    rule = spec.load_reader("experts_routed.busy_share_pct")
    mine = rule.expert_ops(ops, 7168, 2048, 12, 4)
    names = sorted({ev["name"] for ev in mine})
    assert names == kept["experts_k2"]
    # Stacks of 12 or 4 x 12 experts; the shared expert's [7168, 2048]
    # and the dense layer's [7168, 18432] are not among them, and with
    # the depth where the expert layers belong (5 x 12) the grouped
    # matmul's stack is missed.
    assert all("bf16[7168,2048]" not in n.split("=")[0] for n in names)
    assert len(rule.expert_ops(ops, 7168, 2048, 12, 5)) < len(mine)
    busy = sum(ev["dur"] for ev in ops)
    ctx = {"kind": "serve", "trace": trace, "config": config,
           "busy": {"busy_s": busy, "t0": 0.0}}
    share = spec.load_reader("experts_k2.busy_share_pct").read(ctx)
    assert share == pytest.approx(
        100.0 * sum(ev["dur"] for ev in mine) / busy)
    assert spec.load_reader("mla.busy_share_pct").read(ctx) == pytest.approx(
        100.0 * sum(ev["dur"] for ev in mla) / busy)
    # A configuration without these keys has nothing to read.
    other = {**ctx, "config": {"num_experts": 128}}
    assert spec.load_reader("experts_k2.busy_share_pct").read(other) is None
    assert spec.load_reader("mla_decode_roofline").read(other) is None


def test_mla_roofline_counts_the_rows_live_when_the_step_starts():
    kept, trace = recorded_trace()
    step = next(m for m in trace["planes"][0]["lines"][1]["events"]
                if "decode_step" in m["name"])
    records = [{"token_times": [step["start"] - 1.0, step["start"] + 9.0],
                "n_out": 1, "max_new": 8, "prompt_len": 16_500 + 10 * i}
               for i in range(64)]
    seconds = trace_reduce.seconds_matching(trace, r"^mla_decode")[0]
    ctx = {"kind": "serve", "trace": trace, "config": kept["config"],
           "records": records, "trace_wall_t0": 0.0,
           "busy": {"busy_s": 1.0, "t0": 0.0},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    got = spec.load_reader("mla_decode_roofline").read(ctx)
    kernel = spec.load_kernel("mla_decode")
    live = [r["prompt_len"] + 1 for r in records]
    least = 5 * kernel.least_seconds(ctx["peaks"], live, 64, 576, 512)
    assert got == pytest.approx(100.0 * least / seconds)
    assert 0 < got <= 90.0        # the pool reads 640 where 576 are counted
