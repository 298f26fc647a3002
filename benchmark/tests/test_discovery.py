"""Driven by data: a dry added cell, configuration, traffic mix and
per-layer metric are each picked up by name, with no edit to a file that
is there."""

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in (root / "benchmark").rglob("*") if p.is_file()}

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
    after = {str(p.relative_to(root)): p.read_bytes()
             for p in (root / "benchmark").rglob("*") if p.is_file()
             and "__pycache__" not in str(p)}
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/dry_model.json",
        "benchmark/layer_metrics/dry.requests_seen.py",
        "benchmark/traffic/dry_mix.json"]


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
