"""Driven by data: a dry added cell, configuration, family, traffic mix
and per-layer metric are each picked up by name, with no edit to a file
that is there."""

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def checkout(tmp_path):
    """(root, BENCHMARK.json's object, every file's bytes): the
    benchmark as a later PR finds it."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in (root / "benchmark").rglob("*") if p.is_file()}
    return root, bench, before


def added(root, before) -> list:
    """The files that are new, once every file that was there is seen to
    be byte for byte the same."""
    after = {str(p.relative_to(root)): p.read_bytes()
             for p in (root / "benchmark").rglob("*") if p.is_file()
             and "__pycache__" not in str(p)}
    assert {k: v for k, v in after.items() if k in before} == before
    return sorted(set(after) - set(before))


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    root, bench, before = checkout(tmp_path)

    # What the later PR brings: four new files and four new entries.
    config = json.loads((root / "benchmark/configs/mistral_7b_v03.json")
                        .read_text())
    config.update(name="dry_model", num_hidden_layers=3)
    (root / "benchmark/configs/dry_model.json").write_text(json.dumps(config))
    (root / "benchmark/traffic/dry_mix.json").write_text(json.dumps({
        "kind": "closed", "why": "a dry mix for the discovery test only",
        "clients_per_slot": 1, "block_per_slot": 1,
        "prompt": {"dist": "fixed", "value": 64},
        "output": {"dist": "fixed", "value": 8},
        "shared_prefix": None, "eos_tokens": [], "temperature": 0.0}))
    (root / "benchmark/layer_metrics/dry.requests_seen.py").write_text(
        '"""Requests the client finished (a dry reader)."""\n\n\n'
        'def read(ctx):\n    return len(ctx["records"]) or None\n')
    bench["configs"].append({
        "name": "dry_model", "source": "dry", "reduced": ["num_hidden_layers"],
        "file": "benchmark/configs/dry_model.json", "why": "dry"})
    bench["workloads"].append({
        "name": "dry_cell", "config": "dry_model", "traffic": "dry_mix",
        "chips": 1, "why": "dry"})
    bench["per_layer"].append({
        "name": "dry.requests_seen", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "client (benchmark's generator)",
        "moves": "out_tok_s", "workloads": ["dry_cell"]})
    for metric in bench["end_to_end"]:
        if metric["name"] in ("out_tok_s", "tpot_p50_ms"):
            metric["workloads"].append("dry_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    probe = (
        "import json, sys\n"
        "sys.path.insert(0, 'benchmark')\n"
        "from harness import spec, traffic, layers\n"
        "cell = spec.Cell('dry_cell')\n"
        "stream = traffic.Stream(cell.traffic, 1, cell.config['serve']['slots'], 512)\n"
        "got = layers.read_all([m['name'] for m in cell.per_layer\n"
        "                       if m['name'].startswith('dry.')],\n"
        "                      {'records': [1, 2, 3]})\n"
        "print(json.dumps({'kind': cell.kind,\n"
        "  'layers': cell.config['num_hidden_layers'],\n"
        "  'e2e': [m['name'] for m in cell.end_to_end],\n"
        "  'per_layer': [m['name'] for m in cell.per_layer],\n"
        "  'block': [len(r.tokens) for r in stream.block(0)][:3],\n"
        "  'read': got,\n"
        "  'old': [m['name'] for m in spec.Cell('mistral7b_serve_batchgen').per_layer]}))\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=root, check=True,
                         capture_output=True, text=True)
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen["kind"] == "serve" and seen["layers"] == 3
    assert seen["e2e"] == ["out_tok_s", "tpot_p50_ms", "setup_s"]
    assert seen["per_layer"] == ["dry.requests_seen"]
    assert seen["block"] == [64, 64, 64]
    assert seen["read"] == {"dry.requests_seen": 3.0}
    assert "dry.requests_seen" not in seen["old"]
    assert added(root, before) == [
        "benchmark/configs/dry_model.json",
        "benchmark/layer_metrics/dry.requests_seen.py",
        "benchmark/traffic/dry_mix.json"]


DRY_FAMILY = '''"""A family the harness has never seen: another published file's keys
(no head_dim, no hidden_act, norm_eps, a layer pattern), onto the dense
decoder's dataclass; its own flop count."""


def build(config, role):
    import jax.numpy as jnp

    from polyaxon_tpu.models import llama

    section = config.get(role, {})
    assert set(config["layer_types"]) == {"full_attention"}
    return llama, llama.LlamaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=int(section.get("num_hidden_layers",
                                 config["num_hidden_layers"])),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["block_ff_dim"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16}[config["dtype"]],
        max_seq_len=int(section.get("max_len",
                                    config["max_position_embeddings"])))


def forward_flops_per_token(config, layers, seq_len):
    return 1000.0 * layers + seq_len
'''


def test_a_later_pr_adds_a_family_the_harness_has_never_seen(tmp_path):
    """Its files include a family file and a configuration whose keys
    `families/llama.py` cannot read; the harness registers what the new
    family's file builds, and counts flops as that file does."""
    root, bench, before = checkout(tmp_path)
    (root / "benchmark/families/dry_family.py").write_text(DRY_FAMILY)
    (root / "benchmark/configs/dry_model.json").write_text(json.dumps({
        "name": "dry_model", "family": "dry_family",
        "reference": "reference/mistral.py", "hidden_size": 64,
        "block_ff_dim": 160, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 4,
        "layer_types": ["full_attention"] * 4, "vocab_size": 512,
        "max_position_embeddings": 1024, "rope_theta": 1000000.0,
        "norm_eps": 1e-06, "dtype": "bfloat16",
        "serve": {"num_hidden_layers": 3, "max_len": 256}}))
    bench["configs"].append({
        "name": "dry_model", "source": "dry", "reduced": ["num_hidden_layers"],
        "file": "benchmark/configs/dry_model.json", "why": "dry"})
    bench["workloads"].append({
        "name": "dry_cell", "config": "dry_model",
        "traffic": "batchgen_closed", "chips": 1, "why": "dry"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    probe = (
        "import dataclasses, json, sys\n"
        "sys.path.insert(0, 'benchmark')\n"
        "from harness import flops, program, spec\n"
        "config = spec.Cell('dry_cell').config\n"
        "try:\n"
        "    spec.load_family({'family': 'llama'}).build(config, 'serve')\n"
        "    llama_reads_it = True\n"
        "except KeyError:\n"
        "    llama_reads_it = False\n"
        "name, family, cfg = program.register(config, 'serve')\n"
        "train = program.build_model_config(config, 'train')[1]\n"
        "fields = dataclasses.asdict(cfg)\n"
        "fields['dtype'] = fields['dtype'].__name__\n"
        "print(json.dumps({'llama_reads_it': llama_reads_it,\n"
        "  'family': family.__name__, 'class': type(cfg).__name__,\n"
        "  'registered': family.CONFIGS[name] == cfg, 'fields': fields,\n"
        "  'train': [train.n_layers, train.max_seq_len],\n"
        "  'flops': flops.train_flops_per_token(config, 3, 4096),\n"
        "  'old_flops': flops.train_flops_per_token(\n"
        "      spec.load_config('mistral_7b_v03'), 2, 4096)}))\n")
    out = subprocess.run(
        [sys.executable, "-c", probe], cwd=root, check=True,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen["llama_reads_it"] is False
    assert seen["family"] == "polyaxon_tpu.models.llama"
    assert seen["class"] == "LlamaConfig" and seen["registered"] is True
    assert {k: seen["fields"][k] for k in (
        "vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads", "ffn_dim",
        "norm_eps", "dtype", "max_seq_len")} == {
        "vocab_size": 512, "dim": 64, "n_layers": 3, "n_heads": 4,
        "n_kv_heads": 2, "ffn_dim": 160, "norm_eps": 1e-06,
        "dtype": "bfloat16", "max_seq_len": 256}
    assert seen["train"] == [4, 1024]
    assert seen["flops"] == 3 * (3000.0 + 4096)
    assert seen["old_flops"] == 3623878656.0
    assert added(root, before) == ["benchmark/configs/dry_model.json",
                                   "benchmark/families/dry_family.py"]


def test_every_metric_named_has_a_reader_and_every_cell_its_files():
    sys.path.insert(0, BENCH)
    from harness import spec

    bench = spec.load_benchmark()
    for metric in bench["per_layer"]:
        assert callable(spec.load_reader(metric["name"]).read)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for workload in bench["workloads"]:
        cell = spec.Cell(workload["name"])
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        for metric in cell.per_layer:
            assert metric["moves"] in e2e
            assert metric["moves"] in {m["name"] for m in cell.end_to_end}
        assert os.path.exists(os.path.join(
            BENCH, cell.config["reference"]))


def test_run_refuses_without_a_chip():
    """On this CPU the entry point exits non-zero and prints no result."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b_train_seq4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    assert "TPU" in done.stderr
