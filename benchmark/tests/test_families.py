"""A configuration's family is found by name, and nothing is read
differently for it: what the harness registers in the program for the
two configuration files and the two dry fixtures, both roles, and the
flop counts of the two published models, pinned as literals. Written
against the parent's ``program.build_model_config`` before its two
branches moved to ``benchmark/families/``."""

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

from harness import flops, program, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MISTRAL = dict(vocab_size=32768, dim=4096, n_heads=32, n_kv_heads=8,
               ffn_dim=14336, rope_theta=1e6, norm_eps=1e-5,
               dtype=jnp.bfloat16, sliding_window=None, rope_scaling=None)
MIXTRAL = dict(vocab_size=32000, dim=4096, n_heads=32, n_kv_heads=8,
               ffn_dim=14336, rope_theta=1e6, norm_eps=1e-5,
               dtype=jnp.bfloat16, n_experts=8, experts_per_token=2,
               router_aux_coef=0.02, capacity_factor=1.25)
TINY_DENSE = dict(vocab_size=512, dim=64, n_heads=4, n_kv_heads=2,
                  ffn_dim=128, rope_theta=1e6, norm_eps=1e-5,
                  dtype=jnp.bfloat16, sliding_window=None, rope_scaling=None)
TINY_MOE = dict(vocab_size=512, dim=64, n_heads=4, n_kv_heads=2, ffn_dim=128,
                rope_theta=1e6, norm_eps=1e-5, dtype=jnp.bfloat16,
                n_experts=4, experts_per_token=2, router_aux_coef=0.02,
                capacity_factor=1.25)

# (configuration's file, role): the family's module and class, and every
# field the harness states (the rest are the dataclass's own defaults).
REGISTERED = {
    ("configs/mistral_7b_v03.json", "serve"): (
        "llama", "LlamaConfig", dict(MISTRAL, n_layers=8, max_seq_len=4096)),
    ("configs/mistral_7b_v03.json", "train"): (
        "llama", "LlamaConfig", dict(MISTRAL, n_layers=2, max_seq_len=32768)),
    ("configs/mixtral_8x7b_v01.json", "serve"): (
        "moe", "MoEConfig", dict(MIXTRAL, n_layers=1, max_seq_len=32768)),
    ("configs/mixtral_8x7b_v01.json", "train"): (
        "moe", "MoEConfig", dict(MIXTRAL, n_layers=1, max_seq_len=32768)),
    ("tests/fixtures/dry/configs/tiny_dense.json", "serve"): (
        "llama", "LlamaConfig", dict(TINY_DENSE, n_layers=2, max_seq_len=256)),
    ("tests/fixtures/dry/configs/tiny_dense.json", "train"): (
        "llama", "LlamaConfig", dict(TINY_DENSE, n_layers=2, max_seq_len=512)),
    ("tests/fixtures/dry/configs/tiny_moe.json", "serve"): (
        "moe", "MoEConfig", dict(TINY_MOE, n_layers=1, max_seq_len=512)),
    ("tests/fixtures/dry/configs/tiny_moe.json", "train"): (
        "moe", "MoEConfig", dict(TINY_MOE, n_layers=1, max_seq_len=512)),
}


def read(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("path,role", sorted(REGISTERED))
def test_what_is_registered_for_a_configuration_is_pinned(path, role):
    module, klass, fields = REGISTERED[path, role]
    family, cfg = program.build_model_config(read(path), role)
    assert family.__name__ == f"polyaxon_tpu.models.{module}"
    assert type(cfg).__name__ == klass and type(cfg) is getattr(family, klass)
    for name, value in fields.items():
        assert getattr(cfg, name) == value, name
        assert type(getattr(cfg, name)) is type(value), name
    # field by field: what the harness does not state is the default
    assert dataclasses.asdict(cfg) == dataclasses.asdict(type(cfg)(**fields))


@pytest.mark.parametrize("path,layers,train_flops", [
    ("configs/mistral_7b_v03.json", 2, 3623878656.0),
    ("configs/mistral_7b_v03.json", 8, 12079595520.0),
    ("configs/mixtral_8x7b_v01.json", 1, 3252879360.0),
    ("tests/fixtures/dry/configs/tiny_dense.json", 2, 3784704.0),
    ("tests/fixtures/dry/configs/tiny_moe.json", 1, 2139648.0),
])
def test_flop_counts_are_pinned_to_the_flop(path, layers, train_flops):
    config = read(path)
    assert flops.train_flops_per_token(config, layers, 4096) == train_flops
    assert flops.forward_flops_per_token(config, layers, 4096) * 3 == \
        train_flops


def test_an_unknown_familys_error_names_the_files_looked_for(tmp_path):
    config = dict(read("configs/mistral_7b_v03.json"), family="hyena")
    with pytest.raises(spec.SpecError) as err:
        program.build_model_config(config, "serve")
    assert os.path.join(BENCH, "families", "hyena.py") in str(err.value)
    # a configuration of another tree: beside that tree first
    (tmp_path / "configs").mkdir()
    config["_path"] = str(tmp_path / "configs" / "x.json")
    with pytest.raises(spec.SpecError) as err:
        flops.train_flops_per_token(config, 2, 4096)
    assert str(tmp_path / "families" / "hyena.py") in str(err.value)
    assert os.path.join(BENCH, "families", "hyena.py") in str(err.value)
    with pytest.raises(spec.SpecError, match="names no family"):
        spec.load_family({"name": "bare"})


def test_a_fixture_tree_carries_a_family_of_its_own(tmp_path):
    """Beside the configuration's own tree first, as a cell's traffic
    is; then under the benchmark's."""
    for sub in ("configs", "families", "traffic"):
        (tmp_path / sub).mkdir()
    (tmp_path / "families" / "toy.py").write_text(
        "def build(config, role):\n    return 'toy', (config['name'], role)\n")
    (tmp_path / "families" / "moe.py").write_text(
        "def build(config, role):\n    return 'shadow', role\n")
    (tmp_path / "traffic" / "t.json").write_text('{"kind": "closed"}')
    bench = {"configs": [], "workloads": [], "end_to_end": [],
             "per_layer": []}
    for name, family in (("a", "toy"), ("b", "moe"), ("c", "llama")):
        (tmp_path / "configs" / f"{name}.json").write_text(json.dumps(
            dict(read("tests/fixtures/dry/configs/tiny_dense.json"),
                 name=name, family=family)))
        bench["configs"].append({"name": name, "file": f"configs/{name}.json"})
        bench["workloads"].append({"name": name, "config": name,
                                   "traffic": "t", "chips": 1})
    cells = {n: spec.Cell(n, bench, str(tmp_path)) for n in "abc"}
    assert program.build_model_config(cells["a"].config, "serve") == \
        ("toy", ("a", "serve"))
    assert program.build_model_config(cells["b"].config, "train") == \
        ("shadow", "train")
    family, cfg = program.build_model_config(cells["c"].config, "serve")
    assert family.__name__ == "polyaxon_tpu.models.llama" and cfg.dim == 64
    # the plan a phase is handed keeps what the lookup needs
    import run
    plan = run.make_plan(cells["a"], seed=1, seconds=1, trace=False,
                         root=str(tmp_path))
    plan = json.loads(json.dumps(plan))
    assert program.build_model_config(plan["config"], "serve")[0] == "toy"
    bench["workloads"].append({"name": "d", "config": "a", "traffic": "t",
                               "chips": 1})
    os.remove(tmp_path / "families" / "toy.py")
    with pytest.raises(spec.SpecError, match="toy.py"):
        spec.Cell("d", bench, str(tmp_path))
