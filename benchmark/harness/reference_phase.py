#!/usr/bin/env python3
"""The comparison that decides `correct`: a process of its own.

Runs after the program's process has exited (the chip is free and the
program's peak memory is already recorded), imports nothing of the
program, makes the weights again from the seed, and compares what the
timed path produced with the plain float32 reference at the cell's own
sizes:

- serving: a sample, drawn from the seed, of the requests the run
  finished (the longest among them); the reference runs once over each
  prompt with its served tokens, and for every served token reads how
  far its logit lies below the reference's best. Greedy tokens only.
- training: the reference follows the job's first steps on the same
  data; each step's loss and the gradient's global norm are compared,
  and by the worst leaf the norm of the first gradient as Adam got it
  and of the parameters' change after those steps.

With ``control`` set it also computes the control: the same arithmetic
one precision down (int8 matmul inputs), which has to fail the limits.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import common  # noqa: E402

PAD = 512


def load_reference(config: dict):
    import importlib

    module = config["reference"][:-3].replace("/", ".")
    return importlib.import_module(module)


def pick_sample(records: list[dict], seed: int, n: int) -> list[dict]:
    done = [r for r in records if r["finished"] and r.get("tokens")
            and r["phase"] != "warmup"]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + r["n_out"],
                                       r["index"]))
    rest = [r for r in done if r is not longest]
    random.Random(f"check:{seed}").shuffle(rest)
    return [longest] + rest[:max(n - 1, 0)]


def serve_numbers(plan: dict, program: dict, ref) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    config = plan["config"]
    layers = program["model"]["layers"]
    weights = ref.init_weights(config, layers, plan["seed"])
    sample = pick_sample(program["records"], plan["seed"],
                         int(plan["traffic"].get("check_sample", 3)))
    if not sample:
        return {"error": "the run finished no request to compare"}

    fns = {}

    def run(tokens, precision):
        if precision not in fns:
            fns[precision] = jax.jit(
                lambda w, t: ref.logits(config, w, t, precision))
        return fns[precision](weights, tokens)

    # One padded length for the whole cell (its longest request), so the
    # reference is one compiled program whatever the sample holds.
    tr = plan["traffic"]
    longest = (int(tr["prompt"]["max"]) + int(tr["output"]["max"])
               + int((tr.get("shared_prefix") or {}).get("tokens", 0)))
    width = -(-longest // PAD) * PAD
    gaps, control_gaps, rows = [], [], []
    for record in sample:
        prompt, served = record["prompt"], record["tokens"]
        seq = prompt + served[:-1]
        padded = seq + [0] * (width - len(seq))
        tokens = jnp.asarray([padded], jnp.int32)
        at = slice(len(prompt) - 1, len(seq))
        lg = np.asarray(run(tokens, "highest")[0, at])          # [n, V]
        best = lg.max(-1)
        gap = best - lg[np.arange(len(served)), np.asarray(served)]
        gaps.extend(gap.tolist())
        row = {"index": record["index"], "prompt_len": len(prompt),
               "served": len(served), "gap_mean": float(gap.mean()),
               "gap_max": float(gap.max()),
               "argmax_agree": float((gap == 0).mean())}
        if plan.get("control"):
            low = np.asarray(run(tokens, "int8")[0, at]).argmax(-1)
            cgap = best - lg[np.arange(len(served)), low]
            control_gaps.extend(cgap.tolist())
            row.update(control_gap_mean=float(cgap.mean()),
                       control_gap_max=float(cgap.max()))
        rows.append(row)
    out = {"requests": rows, "tokens_compared": len(gaps),
           "numbers": {"gap_mean": float(np.mean(gaps)),
                       "gap_max": float(np.max(gaps))}}
    if control_gaps:
        out["control"] = {"gap_mean": float(np.mean(control_gaps)),
                          "gap_max": float(np.max(control_gaps))}
    return out


def train_numbers(plan: dict, program: dict, ref) -> dict:
    import jax

    config, train = plan["config"], plan["config"]["train"]
    n = int(plan["traffic"]["check_steps"])
    shardings = None
    if plan["chips"] > 1 and len(jax.devices()) >= plan["chips"]:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import numpy as np

        mesh = Mesh(np.asarray(jax.devices()[:plan["chips"]]), ("x",))

        def shardings(tree):
            # Placement only: leaves with an expert axis are split over
            # the chips along it, the rest are whole on each.
            def place(w):
                if w.ndim == 4:
                    return NamedSharding(mesh, P(None, "x"))
                return NamedSharding(mesh, P())
            return jax.tree.map(place, tree)

    def follow(precision):
        return ref.train_steps(
            config, program["layers"], plan["seed"], steps=n,
            batch=int(train["global_batch_size"]),
            seq_len=program["seq_len"], lr=train["learning_rate"],
            wd=train["weight_decay"], clip=train["grad_clip_norm"],
            precision=precision,
            capacity_factor=float(train.get("capacity_factor", 1.25)),
            shardings=shardings)

    theirs = {s["step"]: s["vals"] for s in program["steps"]}

    def worst_leaf(got: dict, want: dict) -> float:
        """The widest gap between a leaf's norm and the reference's,
        against the reference's norm of that leaf or of its median leaf,
        whichever is larger (a norm gain's gradient is all but zero)."""
        floor = sorted(want.values())[len(want) // 2]
        return max(abs(got[name] - norm) / max(norm, floor)
                   for name, norm in want.items())

    def gaps(steps, leaves, reference):
        """Step 0 is read at the seeded weights, where rounding enters
        once: its loss shows a batch cut short, its gradient norm the
        precision (rounding noise adds to the norm in quadrature). The
        later steps follow Adam's first updates, which move every weight
        by the learning rate whatever its gradient's size, so they
        amplify rounding and are held loosely: they are there to catch a
        state that does not move. By the leaf, the parameters' change
        shows the precision: Adam divides a uniform scale out of the
        gradient and leaves each element's rounding (PERF.md, 2)."""
        rel = {key: [abs(got[key] - row[key]) / abs(row[key])
                     for got, row in zip(steps, reference["steps"])]
               for key in ("loss", "grad_norm")}
        return {"loss0_rel": rel["loss"][0], "grad0_rel": rel["grad_norm"][0],
                "later_loss_rel": max(rel["loss"][1:] or [0.0]),
                "later_grad_rel": max(rel["grad_norm"][1:] or [0.0]),
                "grad0_leaf_rel": worst_leaf(leaves["grad0"],
                                             reference["grad0_leaf"]),
                "update_leaf_rel": worst_leaf(leaves["update"],
                                              reference["update_leaf"])}

    def control():
        low = follow("int8")
        return low, gaps(low["steps"], {"grad0": low["grad0_leaf"],
                                        "update": low["update_leaf"]},
                         reference)

    reference = follow("highest")
    out = {"reference": reference,
           "program": [{"step": i, "loss": theirs[i]["loss"],
                        "grad_norm": theirs[i]["grad_norm"]}
                       for i in range(n)],
           "leaf_norms": program["leaf_norms"],
           "numbers": gaps([theirs[i] for i in range(n)],
                           program["leaf_norms"], reference)}
    if plan.get("control"):
        low, out["control"] = control()
        out["control_steps"] = low["steps"]
        out["control_leaf_norms"] = {"grad0": low["grad0_leaf"],
                                     "update": low["update_leaf"]}
    return out


def main() -> None:
    with open(sys.argv[1]) as fh:
        plan = json.load(fh)
    with open(os.path.join(plan["out_dir"], "program.json")) as fh:
        program = json.load(fh)
    try:
        device = common.device_info(plan["chips"], plan["require_chip"])
    except common.NoChip as exc:
        common.fail(str(exc), code=3)
    common.say({"phase": "reference", "note": "comparing"}, device)
    t0 = time.time()
    ref = load_reference(plan["config"])
    if program["kind"] == "serve":
        out = serve_numbers(plan, program, ref)
    else:
        out = train_numbers(plan, program, ref)
    out["seconds"] = time.time() - t0
    out["device"] = device
    common.write_json(os.path.join(plan["out_dir"], "reference.json"), out)


if __name__ == "__main__":
    main()
