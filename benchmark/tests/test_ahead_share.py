"""`engine.ahead_share_pct` (ISSUE 34): on hand-made counters, on a
program without the counter, as the issue gives its entry, and on the
engine's own `stats()` so that the name the program writes is the name
read."""

import json
import os
import sys

import pytest

from harness import layers, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "engine.ahead_share_pct"
SERVING = ["mistral7b_serve_batchgen", "mistral7b_serve_sharedprefix",
           "lfm2_8b_a1b_serve_batchgen", "nemotron3_super_serve_batchgen",
           "qwen3_next_serve_longgen"]


def _ctx(opened, closed, kind="serve"):
    return {"kind": kind, "trace": None,
            "stats": {"open": opened, "close": closed}}


def _stats(steps, ahead=None):
    out = {"decode_steps": steps, "tick_phase_ns": {"sweep": 1}}
    if ahead is not None:
        out["decode_steps_ahead"] = ahead
    return out


def test_by_hand():
    # 400 steps in the window, 394 of them launched ahead
    got = layers.read_all([NAME], _ctx(_stats(100, 97), _stats(500, 491)))
    assert got == pytest.approx({NAME: 98.5})


@pytest.mark.parametrize("ctx", [
    _ctx(_stats(100), _stats(500)),                # the parent: no counter
    _ctx(_stats(100, 97), _stats(100, 97)),        # no step in the window
    _ctx(_stats(100, 97), _stats(500, 491), kind="train"),
    {"kind": "serve", "trace": None, "stats": {"after": {}}},  # untraced
    _ctx({"decode_steps": 1}, {"decode_steps": 9}),  # before ISSUE 24
], ids=["no_counter", "no_step", "train", "no_edges", "no_phases"])
def test_nothing_to_read_leaves_the_metric_out(ctx):
    assert layers.read_all([NAME], ctx) == {}


def test_the_entry_is_as_the_issue_gives_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "engine (serving/batching.py)", "moves": "tpot_p50_ms",
        "workloads": SERVING}
    for cell in SERVING:
        assert NAME in [m["name"] for m in spec.Cell(cell).per_layer]
    assert NAME not in [m["name"] for m in
                        spec.Cell("mistral7b_train_seq4k").per_layer]


def test_the_program_writes_the_name_the_reader_reads():
    """The engine itself, tiny and on the CPU: one request of 40 tokens
    runs ahead at every step but its first."""
    sys.path.insert(0, ROOT)
    from polyaxon_tpu.serving import load_params
    from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

    cfg, params = load_params("llama_tiny", seed=0)
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2,
                                      max_len=64, kv="paged", page_size=4)
    try:
        opened = engine.stats()
        engine.generate([[5, 6, 7]], max_new_tokens=40, timeout=300)
        closed = engine.stats()
    finally:
        engine.stop()
    got = layers.read_all([NAME], _ctx(opened, closed))
    assert got == pytest.approx({NAME: 100.0 * 39 / 40})
    assert closed["decode_tokens_dropped"] == 0
