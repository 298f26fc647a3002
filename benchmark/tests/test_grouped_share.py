"""`grouped.busy_share_pct` (ISSUE 37) against the names of a kept trace
of this tree: a prefill program of each of the two cells that run the
kernel (fixtures/grouped_matmul_ops.json), the older kept traces (whose
dispatch went to the compiler's ``ragged-dot``) for a program that has
none, and the entry as the issue gives it. The two expert readers are
held against the same names: they find the kernel's calls by the stack
the call is handed whole."""

import json
import os

import pytest

from harness import layers, spec, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
NAME = "grouped.busy_share_pct"
CELLS = ["nemotron3_super_serve_batchgen", "qwen3_next_serve_longgen"]
# cell -> (grouped matmuls a prefill: two a held-expert layer of five;
# three a block of seven, the eighth block feeds nothing a prefill
# returns, the stack's leading dimension L·E)
CALLS = {"nemotron3_super_serve_batchgen": (10, 640),
         "qwen3_next_serve_longgen": (21, 1024)}


def _trace(programs):
    """The kept programs laid end to end under a module event each."""
    events, modules, t = [], [], 0.0
    for prog in programs:
        start = t
        for ev in prog["events"]:
            events.append({"name": ev["name"], "start": t, "dur": ev["dur"]})
            t += ev["dur"]
        modules.append({"name": prog["module"], "start": start,
                        "dur": t - start})
        t += 1e-4
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events},
        {"name": "XLA Modules", "events": modules}]}]}


def _ctx(kept):
    trace = _trace(kept["programs"])
    return {"kind": "serve", "trace": trace, "config": kept["config"],
            "busy": trace_reduce.busy(trace)}


@pytest.fixture(scope="module")
def kept():
    with open(os.path.join(FIXTURES, "grouped_matmul_ops.json")) as fh:
        return json.load(fh)["cells"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_share_is_the_kernels_calls_over_busy_time(kept, cell):
    ctx = _ctx(kept[cell])
    ops = trace_reduce.leaf_ops(trace_reduce.device_planes(ctx["trace"])[0])
    mine = [ev for ev in ops if ev["op"].startswith("grouped_matmul")]
    calls, groups = CALLS[cell]
    assert len(mine) == calls
    assert all(ev["opcode"] == "custom-call" for ev in mine)
    # No grouped matmul of the compiler's is left beside them.
    assert not [ev for ev in ops if ev["op"].startswith("ragged-dot")]
    want = 100 * sum(ev["dur"] for ev in mine) / ctx["busy"]["busy_s"]
    assert layers.read_all([NAME], ctx) == pytest.approx({NAME: want})
    assert 5 < want < 60
    # The stack is handed whole, so the call's text carries its type
    # and the expert readers count the call as they counted ragged-dot.
    config = kept[cell]["config"]
    d = config.get("moe_latent_size") or config["hidden_size"]
    f = config["moe_intermediate_size"]
    for ev in mine:
        assert (f"bf16[{groups},{d},{f}]" in ev["name"]
                or f"bf16[{groups},{f},{d}]" in ev["name"])
    if cell == "nemotron3_super_serve_batchgen":
        theirs = spec.load_reader("experts.busy_share_pct").expert_ops(
            ops, d, f)
    else:
        theirs = spec.load_reader("experts_routed.busy_share_pct").expert_ops(
            ops, d, f, config["num_experts"], config["num_hidden_layers"])
    assert {ev["name"] for ev in mine} <= {ev["name"] for ev in theirs}


@pytest.mark.parametrize("older", ["nemotron_h_ops.json",
                                   "qwen3_next_ops.json"])
def test_a_program_without_the_kernel_leaves_the_metric_out(older):
    """The parent's traces: sorted pairs went to ``ragged-dot``."""
    with open(os.path.join(FIXTURES, older)) as fh:
        ctx = _ctx(json.load(fh))
    assert layers.read_all([NAME], ctx) == {}
    assert layers.read_all([NAME], {**ctx, "trace": None}) == {}
    assert layers.read_all([NAME], {**ctx, "kind": "train"}) == {}


def test_the_entry_is_as_the_issue_gives_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace",
        "layer": "grouped matmul kernel (ops/grouped_matmul.py)",
        "moves": "out_tok_s", "workloads": CELLS}
    assert len(bench["per_layer"]) == 43
    for cell in (w["name"] for w in bench["workloads"]):
        named = [m["name"] for m in spec.Cell(cell).per_layer]
        assert (NAME in named) == (cell in CELLS)
