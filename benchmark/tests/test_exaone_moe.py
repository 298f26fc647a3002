"""The K-EXAONE-236B-A23B configuration's own files: what its family file
registers in the program, field by field; its cut, its deployment and
its assumptions held against the published keys, with the parameter
count reckoned from them and counted again off the program's own tree;
what the family refuses; its cell, its entries (found by name, never by
their place in a list) and its traffic as the issue gives them; its
three readers on recorded data; and a whole run of the tiny model on the
CPU (sound: correct, every measured request behind a shared prefix under
a window; the int8 control: outside the run's own sound reading)."""

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
DRY = os.path.join(FIXTURES, "dry_exaone_moe")
CELL = "k_exaone_serve_agentloop"
NAME = "k_exaone_236b_a23b"
SOURCE = ("https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/"
          "config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

# LGAI-EXAONE/K-EXAONE-236B-A23B config.json, as the catalog of public
# architectures holds it (model-configs guide, `architectures.jsonl`).
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": PERIOD * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
    "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
CUT = ["num_hidden_layers", "num_experts", "vocab_size", "layer_types",
       "mlp_layer_types", "sliding_windows"]
OWN = {"kv.prefix_recompute_pct": ("tpot_p50_ms", "lower", "%"),
       "suffix_prefill.busy_share_pct": ("tpot_p50_ms", "lower", "%"),
       "experts_exaone.busy_share_pct": ("out_tok_s", "lower", "%")}


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        config = json.load(fh)
    config["_path"] = os.path.join(BENCH, path)
    return config


def named(entries: list, name: str) -> dict:
    found = [entry for entry in entries if entry["name"] == name]
    assert len(found) == 1, name
    return found[0]


REGISTERED = {
    f"configs/{NAME}.json": dict(
        vocab_size=19200, dim=6144, n_layers=8, n_heads=64, n_kv_heads=8,
        head_dim=128, rope_theta=1e6, sliding_window=128,
        window_layout=(1, 1, 1, 0, 1, 1, 1, 0),
        rope_layout=(1, 1, 1, 0, 1, 1, 1, 0), ffn_dim=18432, first_dense=1,
        n_experts=128, experts_per_token=8, moe_ffn_dim=2048,
        routed_scaling_factor=2.5, held_experts=(0, 8), norm_eps=1e-5,
        max_seq_len=14336, dtype=jnp.bfloat16),
    "tests/fixtures/dry_exaone_moe/configs/tiny_exaone_moe.json": dict(
        vocab_size=512, dim=64, n_layers=4, n_heads=8, n_kv_heads=2,
        head_dim=16, sliding_window=16, window_layout=(1, 1, 1, 0),
        ffn_dim=96, n_experts=16, experts_per_token=4, moe_ffn_dim=32,
        held_experts=(4, 4), max_seq_len=256, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("path", sorted(REGISTERED))
def test_family_file_registers_these_fields(path):
    module, cfg = program.build_model_config(read(path), "serve")
    assert module.__name__ == "polyaxon_tpu.models.exaone_moe"
    assert type(cfg).__name__ == "ExaoneMoEConfig"
    for field, value in REGISTERED[path].items():
        assert getattr(cfg, field) == value, field
    kinds = [(kind, ffn) for kind, _, ffn, _ in module.FAMILY.layers(cfg)]
    assert kinds[:4] == [("window", "dense"), ("window", "moe"),
                         ("window", "moe"), ("full", "moe")]
    # The engine asks the module for a window and gives it two spaces.
    assert module.paged_window(cfg) == cfg.sliding_window
    for name in ("paged_gather_prefix", "paged_prefill_suffix_kv",
                 "paged_insert_suffix", "decode_step_paged"):
        assert callable(getattr(module, name)), name


def test_cut_deployment_and_assumptions_against_the_published_keys():
    config = read(f"configs/{NAME}.json")
    entry = named(spec.load_benchmark()["configs"], NAME)
    assert entry["source"] == config["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == CUT == list(config["reduced"])
    for key, value in PUBLISHED.items():        # every published key is there
        if key in config["reduced"]:
            assert config["reduced"][key]["source"] == value, key
            assert config["reduced"][key]["serve"] == config[key], key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    # No width is cut: the depth (and the three per-layer lists with it),
    # the chip's share of the experts and of the vocabulary.
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 8, 19200)
    assert config["layer_types"] == PERIOD * 2
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert config["sliding_windows"] == [128, 128, 128, 0] * 2
    for key in CUT[3:]:
        assert len(config["reduced"][key]["source"]) == 48
        assert len(config["reduced"][key]["serve"]) == 8
    # The floors: a whole period and four expert layers behind the dense
    # one, 8 routed experts a layer, an eighth of the vocabulary.
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 == 153600
    deployment = config["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["rank"],
            deployment["vocab_shards"], deployment["first_layer"]) == (
        16, 0, 8, 0)
    assert 8 * 16 == 128 and "40 layers" in deployment["how"]
    assert {"multi_token_prediction", "norm_placement", "qk_norm",
            "rotary_layers", "window", "norm_sum_epsilon",
            "sliding_window_size"} <= set(config["assumed"])
    # The readers' name for the window, beside the published key.
    assert config["sliding_window_size"] == config["sliding_window"] == 128
    assert config["dtype"] and config["check_why"] and config["serve"]["why"]
    assert set(config["check"]["serve"]) == {"gap_mean", "gap_max"}


def test_parameters_from_the_files_keys_are_3865_million():
    """By part from the file's keys, and the same total counted off the
    shapes the program's own draw gives (ISSUE 52: 3,865,313,280 beside
    the norm gains)."""
    config = read(f"configs/{NAME}.json")
    family = spec.load_family(config)
    n = family.parameters(config)
    assert n == {"attn": 113_246_208, "dense": 339_738_624,
                 "beside": 786_432 + 37_748_736, "expert": 37_748_736,
                 "norms": 2 * 6144 + 2 * 128, "bias": 128,
                 "table": 117_964_800}
    dense = n["attn"] + n["dense"]
    expert = n["attn"] + n["beside"] + 8 * n["expert"]
    assert (dense, expert) == (452_984_832, 453_771_264)
    matrices = family.parameters_here(config, 8, gains=False)
    assert matrices == dense + 7 * expert + 2 * n["table"] == 3_865_313_280
    gains = 8 * n["norms"] + 6144 + 7 * n["bias"]
    assert gains == 106_496 + 896
    here = family.parameters_here(config, 8)
    assert here == matrices + gains
    assert round(2 * matrices / 1e9, 2) == 7.73
    module, cfg = program.build_model_config(config, "serve")
    shapes = jax.eval_shape(lambda: module.init(cfg, jax.random.key(0)))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == here
    # The same count of the whole published model: 236.6 B, about 23 B
    # active a token (the table is looked up, not multiplied).
    whole = copy.deepcopy(config)
    whole.update(num_hidden_layers=48, num_experts=128, vocab_size=153600,
                 layer_types=PUBLISHED["layer_types"],
                 mlp_layer_types=PUBLISHED["mlp_layer_types"],
                 sliding_windows=PUBLISHED["sliding_windows"],
                 reduced={}, deployment={})
    assert round(family.parameters_here(whole, 48, gains=False) / 1e9,
                 1) == 236.6
    active = (family.parameters_here(whole, 48, active=True, gains=False)
              - 6144 * 153600)
    assert 22e9 < active < 24e9
    # The serve section's arithmetic: both page spaces beside the weights.
    serve = config["serve"]
    token = 2 * 8 * 128 * 2 * 2
    assert token == 8192
    full = (serve["kv_pages"] + 1) * serve["page_size"] * token
    assert round(full / 1e9, 2) == 3.22
    window = (serve["slots"] * (128 // serve["page_size"] + 1) + 1) * (
        6 * 8 * 128 * 2 * 2 * serve["page_size"])
    assert round(window / 1e9, 2) == 0.23
    assert 11.1e9 < 2 * here + full + window < 11.3e9
    prefixes = 16 * 12288 // serve["page_size"]
    rows = serve["slots"] * -(-1280 // serve["page_size"])
    assert (prefixes, rows) == (12288, 5120)
    assert prefixes + rows <= serve["kv_pages"]
    # What a match computes again: 128 positions a window layer.
    assert 6 * 128 == 768 and 36 * 128 == 4608


def broken(**changes):
    config = copy.deepcopy(read(f"configs/{NAME}.json"))
    config.update(changes)
    return config


@pytest.mark.parametrize("fault, said", [
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(n_group=8), "n_group"),
    (dict(num_shared_experts=2), "one shared expert"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope scaling"),
    (dict(num_key_value_heads=7), "multiple"),
    (dict(num_experts=16), "reduced.num_experts"),
    (dict(layer_types=PERIOD + ["full_attention"] * 4,
          sliding_windows=[128, 128, 128, 0, 0, 0, 0, 0]),
     "reduced.layer_types"),
    (dict(layer_types=["sliding_attention"] * 8,
          reduced={}, num_experts=128, vocab_size=153600, deployment={}),
     "side by side"),
    (dict(mlp_layer_types=["sparse"] * 8, reduced={}, num_experts=128,
          vocab_size=153600, deployment={}), "dense layers"),
    (dict(sliding_windows=[128] * 8, reduced={}, num_experts=128,
          vocab_size=153600, deployment={}), "sliding_windows"),
    (dict(deployment={"chips_sharing_a_layer": 8, "rank": 0}),
     "do not hold"),
    (dict(tie_word_embeddings=True), "untied"),
])
def test_family_file_refuses(fault, said):
    with pytest.raises(ValueError, match=said):
        program.build_model_config(broken(**fault), "serve")


def test_cell_and_entries_are_found_by_name():
    bench = spec.load_benchmark()
    cell = spec.Cell(CELL)
    entry = named(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "agentloop_closed", 1)
    assert len(entry["why"]) <= 200 and "4,608" in entry["why"]
    assert (cell.chips, cell.kind) == (1, "serve")
    assert cell.config["family"] == "exaone_moe"
    assert spec.load_family(cell.config).__name__ == "family_exaone_moe"
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}
    for name in ("out_tok_s", "tpot_p50_ms"):
        assert CELL in named(bench["end_to_end"], name)["workloads"]
    mine = {m["name"] for m in cell.per_layer}
    seven = {m["name"] for m in
             spec.Cell("smallthinker_21b_serve_longctx").per_layer}
    eight = {m["name"] for m in spec.Cell("kimi_k2_serve_docqa").per_layer}
    # What cells 7 and 8 both carry, the paged and the windowed kernel's
    # and the window space's (cell 7's), the radix cache's hit share
    # (cell 8's), and the three of its own.
    assert mine == (seven & eight) | {
        "model.decode_step_ms", "paged.busy_share_pct",
        "paged_decode_roofline", "window.busy_share_pct",
        "window_decode_roofline", "kv.window_roll_us_per_step",
        "kv.prefix_hit_pct"} | set(OWN)
    assert {"moe.expert_load_max_over_mean", "grouped.busy_share_pct",
            "kv.admit_match_us", "engine.idle_admit_ms",
            "model.prefill_share_pct"} <= mine
    for name, (moves, better, unit) in OWN.items():
        metric = named(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert (metric["moves"], metric["better"], metric["unit"]) == (
            moves, better, unit)
        assert callable(spec.load_reader(name).read)
    serve = cell.config["serve"]
    assert (serve["slots"], serve["kv_pages"], serve["page_size"],
            serve["max_len"]) == (64, 24576, 16, 14336)
    # Seven configurations, nine cells, none on four chips; the older
    # entries are where they were.
    assert [c["name"] for c in bench["configs"]][-1] == NAME
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert (len(bench["configs"]), len(bench["workloads"])) == (7, 9)
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_agentloop_traffic_is_the_issues_letter_for_letter():
    cell = spec.Cell(CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["clients_per_slot"], mix["block_per_slot"]) == (
        "closed", 2, 2)
    assert mix["shared_prefix"] == {"count": 16, "tokens": 12288, "zipf": 1.0}
    assert mix["prompt"] == {"dist": "loguniform", "min": 64, "max": 512,
                             "grid": [64, 96, 128, 192, 256, 384, 512]}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.45, "min": 64, "max": 768}
    assert (mix["warmup_new"], mix["lead_in"], mix["check_sample"],
            mix["temperature"], mix["eos_tokens"]) == (
        2, {"new_from": 8, "new_to": 512}, 8, 0.0, [])
    stream = traffic.Stream(mix, 3_000_000_019, cell.config["serve"]["slots"],
                            cell.config["vocab_size"])
    block = stream.block(0)
    assert len(block) == 128
    assert all(len(r.tokens) - 12288 in mix["prompt"]["grid"] for r in block)
    longest = max(len(r.tokens) + r.max_new for r in block)
    assert longest <= 12288 + 512 + 768 <= cell.config["serve"]["max_len"]
    # Every prefix is used in every block, the first most.
    uses = [sum(r.prefix == k for r in block) for k in range(16)]
    assert min(uses) >= 1 and uses[0] == max(uses) >= 30
    # Every request's first own token is its own: a match ends with the
    # prefix, on a page boundary (768 pages of 16).
    firsts = [r.tokens[12288] for r in block + stream.block(1)]
    assert len(set(firsts)) == len(firsts)
    warm = stream.warmup()
    assert len(warm) == 16 + 7
    assert {len(r.tokens) for r in warm[:16]} == {12288 + 64}
    # The suffix programs the tails ask for (serving/batching.py
    # `_suffix_bucket`: the 768 recomputed, a power of two over the novel
    # ones, whole flash tiles of 256): two shapes.
    shapes = set()
    for own in mix["prompt"]["grid"]:
        novel = own - 1
        bucket = 768 + max(8, 1 << (novel - 1).bit_length())
        shapes.add(-(-bucket // 256) * 256)
    assert shapes == {1024, 1280}


# -------------------------------------------------------------- a whole run
def test_tiny_run_is_correct_and_the_control_is_not():
    cell = spec.Cell("tiny_exaone_agentloop", spec.load_benchmark(DRY), DRY)
    seed = 3_000_000_019
    final = run.run_cell(cell, seed=seed, seconds=3, trace=True,
                         require_chip=False, control=True)
    assert final["correct"] is True and final["failed"] == 0
    # Off the chip the trace's readers find nothing; the counters' do.
    assert set(final["metrics"]) == {
        "engine.avg_occupancy", "moe.expert_load_max_over_mean",
        "kv.prefix_hit_pct", "kv.prefix_recompute_pct",
        "kv.window_roll_us_per_step"}
    out_dir = os.path.join(run.ROOT, ".benchmark_out",
                           f"{cell.name}-{seed}-1")
    with open(os.path.join(out_dir, "reference.json")) as fh:
        ref = json.load(fh)
    with open(os.path.join(out_dir, "program.json")) as fh:
        ran = json.load(fh)
    limit = cell.config["check"]["serve"]["gap_mean"]
    # Readings at this size over three seeds: sound 0.0005-0.0014, the
    # control 0.0016-0.0025 on the same requests (which requests finish
    # follows the run's timing, so the control is held to the run's own
    # sound reading, not to the limit).
    sound = ref["numbers"]["gap_mean"]
    assert sound < limit and ref["control"]["gap_mean"] > 1.3 * sound
    assert ran["compiles_in_window"] == 0
    # Every measured request stood behind a prefix of 96 tokens (6 pages
    # of 16) and computed 48 of them again: 3 window layers of 16.
    assert final["metrics"]["kv.prefix_recompute_pct"]["value"] == 50.0
    assert 35 < final["metrics"]["kv.prefix_hit_pct"]["value"] < 48
    after = ran["stats"]["after"]
    assert after["prefill_tokens_recomputed"] * 2 == after[
        "prefill_tokens_matched"]
    assert after["prefill_tokens_skipped"] == (
        after["prefill_tokens_matched"] - after["prefill_tokens_recomputed"])
    assert after["kv_token_bytes"] == 1 * 2 * 16 * 2 * 2    # one full layer
    assert after["kv_window"] == 16
    assert after["kv_window_page_bytes"] == 3 * 2 * 16 * 16 * 2 * 2
    assert after["kv_window_row_pages_max"] <= 2
    assert after["kv_invariant_violations"] == 0
    assert after["kv_cow_forks"] == 0
    held = after["moe_expert_tokens"]
    assert len(held) == 3 and len(held[0]) == 4
    assert len(after["moe_pairs_elsewhere"]) == 3
    spans = next(iter(ran["timelines"].values()))
    assert "prefill" in spans


# ------------------------------------------------------------ the readers
def recorded_trace():
    """The decode step and a suffix prefill program of the kept trace,
    laid end to end under a module event each."""
    with open(os.path.join(FIXTURES, "exaone_moe_ops.json")) as fh:
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


def test_expert_and_suffix_shares_match_the_recorded_names():
    kept, trace = recorded_trace()
    config = kept["config"]
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(trace)[0])
    rule = spec.load_reader("experts_routed.busy_share_pct")
    mine = rule.expert_ops(ops, 6144, 2048, 8, 7)
    names = sorted({ev["name"] for ev in mine})
    assert names == kept["experts_exaone"] and names
    # Stacks of 8 or 7 x 8 experts; the shared expert's [6144, 2048] and
    # the dense layer's [6144, 18432] are not among them, and with the
    # depth where the expert layers belong (8 x 8) the grouped matmul's
    # stack is missed.
    assert all("bf16[6144,2048]" not in n.split("=")[0] for n in names)
    assert len(rule.expert_ops(ops, 6144, 2048, 8, 8)) < len(mine)
    busy = sum(ev["dur"] for ev in ops)
    ctx = {"kind": "serve", "trace": trace, "config": config,
           "busy": {"busy_s": busy, "t0": 0.0}}
    share = spec.load_reader("experts_exaone.busy_share_pct").read(ctx)
    assert share == pytest.approx(
        100.0 * sum(ev["dur"] for ev in mine) / busy)
    # The suffix program is told from a whole-prompt one by its module's
    # name, which `model.prefill_share_pct` finds too.
    suffix = [m for m in trace["planes"][0]["lines"][1]["events"]
              if m["name"].startswith("jit_run.suffix")]
    assert len(suffix) == 1
    assert spec.load_reader("suffix_prefill.busy_share_pct").read(
        ctx) == pytest.approx(100.0 * suffix[0]["dur"] / busy)
    assert spec.load_reader("model.prefill_share_pct").read(
        ctx) == pytest.approx(100.0 * suffix[0]["dur"] / busy)
    # Both kinds of decode attention ran, one call a layer of its kind.
    assert trace_reduce.seconds_matching(trace, r"^paged_decode")[1] == 2
    assert trace_reduce.seconds_matching(trace, r"^window_decode")[1] == 6
    # A configuration without these keys, or a program without such a
    # module (the parent's), has nothing to read.
    other = {**ctx, "config": {"n_routed_experts": 12}}
    assert spec.load_reader("experts_exaone.busy_share_pct").read(
        other) is None
    plain = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": []},
        {"name": "XLA Modules", "events": [
            {"name": "jit_run(1)", "start": 0.0, "dur": 1.0}]}]}]}
    assert spec.load_reader("suffix_prefill.busy_share_pct").read(
        {**ctx, "trace": plain}) is None


def test_recompute_share_reads_the_two_counters_or_nothing():
    reader = spec.load_reader("kv.prefix_recompute_pct")
    edges = {"open": {"prefill_tokens_matched": 12288 * 3,
                      "prefill_tokens_recomputed": 768 * 3},
             "close": {"prefill_tokens_matched": 12288 * 40,
                       "prefill_tokens_recomputed": 768 * 40}}
    assert reader.read({"kind": "serve", "stats": edges}) == 6.25
    # The parent's `/v1/stats` has neither counter; a timed run no edges;
    # a window in which nothing matched no share.
    older = {edge: {"prefill_tokens_skipped": 5} for edge in edges}
    assert reader.read({"kind": "serve", "stats": older}) is None
    assert reader.read({"kind": "serve", "stats": {"after": {}}}) is None
    assert reader.read({"kind": "serve", "stats": {
        "open": edges["open"], "close": edges["open"]}}) is None
    assert reader.read({"kind": "train", "stats": edges}) is None
