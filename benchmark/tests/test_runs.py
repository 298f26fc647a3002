"""A whole run on the CPU at tiny widths (everything but the look for a
chip): sound runs come out correct, and a timed path broken underneath
comes out not correct. The lower-precision control is read here too, at
a size a test run can hold; on the chip it was read at the cells' own
sizes (PERF.md, section 2): it fails one limit of every cell."""

import json
import os

import pytest

from rehearsal import dry_cell, rehearse


def numbers(out_dir):
    with open(os.path.join(out_dir, "reference.json")) as fh:
        return json.load(fh)


def test_closed_serving_run_is_correct_and_counts_by_the_token():
    got = rehearse("tiny_closed", seed=3_000_000_011, seconds=3, control=True)
    final = got["final"]
    assert final["correct"] is True and final["failed"] == 0
    assert set(final["metrics"]) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    assert set(final) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    with open(os.path.join(got["out_dir"], "program.json")) as fh:
        program = json.load(fh)
    assert program["compiles_in_window"] == 0
    t0, t1 = program["t_open"], program["t_close"]
    inside = sum(1 for r in program["records"] for t in r["token_times"]
                 if t0 <= t < t1)
    assert final["metrics"]["out_tok_s"]["value"] == pytest.approx(
        inside / (t1 - t0))
    assert all(r["n_out"] == r["max_new"] for r in program["records"]
               if r["finished"])            # no stop token: all it asked
    ref = numbers(got["out_dir"])
    assert ref["tokens_compared"] > 300
    assert ref["control"]["gap_mean"] > 2 * ref["numbers"]["gap_mean"]
    assert ref["control"]["gap_mean"] > 0.00008      # fails the tiny limit


def test_open_serving_run_reports_due_time_tails_and_hits():
    got = rehearse("tiny_open", seed=77, seconds=4, trace=True)
    final = got["final"]
    assert final["correct"] is True
    assert {"client.late_p99_ms", "engine.queue_wait_p50_ms",
            "kv.prefix_hit_pct"} <= set(final["metrics"])
    assert final["metrics"]["kv.prefix_hit_pct"]["value"] > 50


def test_altered_token_is_caught():
    final = rehearse("tiny_closed", seed=79, seconds=2,
                     break_path="token")["final"]
    assert final["correct"] is False


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_broken_train_step_is_caught(fault):
    final = rehearse("tiny_train", seed=78, seconds=2,
                     break_path=fault)["final"]
    assert final["correct"] is False


def test_train_run_is_correct_and_the_control_is_not():
    """The int8 control is read beside the program's numbers. It passes
    on the scalars the loop hands out (rounding moves a loss or a global
    norm at second order, with either sign; PERF.md, section 2) and
    fails on the parameters' change by the worst leaf, which is read
    from the job's own state."""
    got = rehearse("tiny_train", seed=77, seconds=2, control=True)
    assert got["final"]["correct"] is True
    assert set(got["final"]["metrics"]) == {"train_tok_s_chip", "setup_s"}
    ref = numbers(got["out_dir"])
    limits = dry_cell("tiny_train").config["check"]["train"]
    assert set(limits) <= set(ref["numbers"]) == set(ref["control"])
    assert all(ref["numbers"][name] <= limit
               for name, limit in limits.items())
    assert ref["control"]["update_leaf_rel"] > \
        2 * limits["update_leaf_rel"] > 4 * ref["numbers"]["update_leaf_rel"]
    assert len(ref["reference"]["steps"]) == 3
    assert set(ref["leaf_norms"]["grad0"]) == \
        set(ref["reference"]["grad0_leaf"]) == set(ref["leaf_norms"]["update"])


def test_expert_parallel_run_matches_the_one_program_reference():
    got = rehearse("tiny_moe_train", seed=77, seconds=2)
    assert got["final"]["correct"] is True
    assert got["final"]["device"]["count"] == 4


def test_state_watch_reads_a_leaf_block_by_block():
    """The norms `StateWatch` takes in blocks are the whole leaf's."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from harness import train_phase

    starts, sizes = train_phase.blocks((2, 8, 6), 10)
    assert sizes == (1, 1, 6) and len(starts) == 16
    assert train_phase.blocks((4, 6), 12)[1] == (2, 6)
    assert train_phase.blocks((5,), 100) == ([(0,)], (5,))

    rng = np.random.default_rng(0)
    p0, p1, mu = (rng.standard_normal((2, 8, 6)).astype(np.float32)
                  for _ in range(3))
    watch = train_phase.StateWatch(check_steps=1, limit=10)
    state1 = {"params": {"layers": {"w": jnp.asarray(p1)}},
              "opt_state": (types.SimpleNamespace(
                  mu={"layers": {"w": jnp.asarray(mu)}}),)}
    watch.start = {"layers/w": [np.asarray(b) for b in
                                watch._blocks_of(jnp.asarray(p0))]}
    watch.after(state1)
    assert watch.grad0["layers/w"] == pytest.approx(
        np.linalg.norm(mu) / 0.1, rel=1e-6)
    assert watch.update["layers/w"] == pytest.approx(
        np.linalg.norm(p1 - p0), rel=1e-6)
