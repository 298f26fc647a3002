"""Finding a cell's files by the names BENCHMARK.json gives.

A cell names a configuration and a traffic mix; a configuration names
its family and its reference; a metric names its reader. Each lives in a
file of its own under the benchmark's directory, so a later PR adds a
cell, a configuration, a family, a mix or a metric by adding files and
entries, and edits none that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SpecError(f"BENCHMARK.json names no {what} `{name}`; it has "
                    f"{sorted(e['name'] for e in entries)}")


def load_config(name: str, bench: dict | None = None, root: str = ROOT) -> dict:
    """The configuration's file, as BENCHMARK.json's entry points to it
    (or ``configs/<name>.json`` for a name it does not list yet)."""
    path = os.path.join(BENCH_DIR, "configs", f"{name}.json")
    if bench is not None:
        path = os.path.join(root, _named(bench["configs"], name,
                                         "configuration")["file"])
    with open(path) as fh:
        config = json.load(fh)
    config["_path"] = path
    return config


def load_traffic(name: str, traffic_dir: str | None = None) -> dict:
    """A traffic mix is a data file of parameters, found by its name."""
    path = os.path.join(traffic_dir or os.path.join(BENCH_DIR, "traffic"),
                        f"{name}.json")
    if not os.path.exists(path):
        raise SpecError(f"no traffic file {path}")
    with open(path) as fh:
        return json.load(fh)


def _tree(config: dict) -> str:
    """The directory whose ``configs/`` holds the configuration's file:
    the benchmark's own, or a fixture tree's."""
    path = config.get("_path")
    return os.path.dirname(os.path.dirname(path)) if path else BENCH_DIR


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, workload: str, bench: dict | None = None,
                 root: str = ROOT):
        self.bench = bench if bench is not None else load_benchmark(root)
        self.entry = _named(self.bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = load_config(self.entry["config"], self.bench, root)
        load_family(self.config)        # a family with no file fails here
        self.traffic = load_traffic(
            self.entry["traffic"],
            os.path.join(_tree(self.config), "traffic"))
        self.kind = {"closed": "serve", "open": "serve",
                     "train": "train"}[self.traffic["kind"]]

    def _reported(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self._reported(m)]

    @property
    def per_layer(self) -> list[dict]:
        """Per-layer metrics this cell reports: those that list it, and
        those with no list whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end}
        out = []
        for metric in self.bench["per_layer"]:
            if "workloads" in metric:
                if self.name in metric["workloads"]:
                    out.append(metric)
            elif metric["moves"] in mine:
                out.append(metric)
        return out


def _module(path: str, name: str):
    modspec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(module)
    return module


def load_reader(metric_name: str):
    """``layer_metrics/<name>.py``: a module with ``read(ctx)`` that
    returns the value, or None where it finds nothing to read."""
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{metric_name}.py")
    if not os.path.exists(path):
        raise SpecError(f"per-layer metric `{metric_name}` has no reader "
                        f"at {path}")
    return _module(path, "layer_metric_" + metric_name)


def load_kernel(kernel_name: str):
    """``kernels/<kernel>.py``: the operations and bytes the algorithm
    needs for one call, from its shapes."""
    return _module(os.path.join(BENCH_DIR, "kernels", f"{kernel_name}.py"),
                   "kernel_" + kernel_name)


def load_family(config: dict):
    """``families/<family>.py``, by the configuration's ``family`` key:
    a module with ``build(config, role)`` and, where its layers are not
    all alike, ``forward_flops_per_token`` (the contract is in
    ``harness/program.py``). Looked for beside the configuration's own
    tree first, as its traffic is, then under the benchmark's."""
    family = config.get("family")
    if not family:
        raise SpecError(f"configuration `{config.get('name')}` names no "
                        "family")
    paths = [os.path.join(tree, "families", f"{family}.py")
             for tree in dict.fromkeys([_tree(config), BENCH_DIR])]
    for path in paths:
        if os.path.exists(path):
            return _module(path, "family_" + family)
    raise SpecError(f"configuration `{config.get('name')}` is of family "
                    f"`{family}`, which has no file at "
                    + " or ".join(paths))


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind the table lacks is an error."""
    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table["kinds"]:
        raise SpecError(f"device kind `{device_kind}` is not in "
                        f"harness/peaks.json: {sorted(table['kinds'])}")
    return table["kinds"][device_kind]
