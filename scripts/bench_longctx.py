#!/usr/bin/env python
"""Long-context proof points (VERDICT r3 #8).

Two modes:

``--cpu-mesh``
    The multi-device half, runnable anywhere: ring attention (zigzag
    causal, dp=1 x cp=8 → 2048 local rows per device) AND ulysses
    (all-to-all head-parallel, dp=2 x cp=4 — the 4-head tiny model
    caps the head-sharded axis at 4) training at seq 16k on an
    8-device virtual CPU mesh. Proves both sequence-parallel schedules
    compile, execute, and are differentiable at long context without
    chip access — and that the two schedules' losses agree at real
    length, not just the seq-64 dryrun (VERDICT r4 item 8).

default (chip)
    Single-chip flash training at seq 8k and 16k (llama_200m, Pallas
    flash fwd+bwd, remat dots) with device memory telemetry: flash
    never materializes the S^2 score matrix, so peak memory between
    8k and 16k should scale ~O(S) (activations), not O(S^2). Reports
    tokens/sec/chip + peak bytes per point.

Each point prints one JSON line; results land in
``bench_longctx_results.json`` (merged across invocations, config-keyed
like perf_sweep).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "bench_longctx_results.json")


def _merge_result(entry: dict) -> None:
    data = []
    try:
        with open(RESULTS) as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError):
        pass
    data = [d for d in data if d.get("name") != entry.get("name")]
    data.append(entry)
    with open(RESULTS, "w") as fh:
        json.dump(data, fh, indent=2)


def _peak_bytes() -> int | None:
    """Max ``peak_bytes_in_use`` across local devices (PJRT memory
    stats; None where the backend doesn't report them)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", lambda: None)() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"])
    return max(peaks) if peaks else None


def run_point(name: str, *, model: str, seq: int, batch: int, steps: int,
              mesh_axes: dict | None, attention: str, remat: str) -> dict:
    import jax

    from polyaxon_tpu.polyflow import V1JAXJob
    from polyaxon_tpu.runtime import run_jaxjob

    spec = {
        "kind": "jaxjob",
        **({"mesh": {"axes": mesh_axes}} if mesh_axes else {}),
        "runtime": {
            "model": model, "dataset": "lm_synthetic", "steps": steps,
            "global_batch_size": batch, "seq_len": seq,
            "log_every": 10**9, "remat": remat,
            "attention_impl": attention,
        },
    }
    t0 = time.perf_counter()
    result = run_jaxjob(V1JAXJob.from_dict(spec))
    wall = time.perf_counter() - t0
    n_chips = jax.device_count()
    entry = {
        "name": name,
        "model": model, "seq": seq, "batch": batch, "steps": steps,
        "attention": attention, "remat": remat,
        "mesh": mesh_axes or {"dp": 1},
        "loss": float(result.final_metrics.get("loss", float("nan"))),
        "tokens_per_sec_per_chip": round(
            result.throughput / max(n_chips, 1), 2),
        "wall_s": round(wall, 1),
        "peak_bytes_per_device": _peak_bytes(),
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
    }
    print(json.dumps(entry), flush=True)
    _merge_result(entry)
    return entry


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu-mesh", action="store_true",
                        help="ring @ 16k on an 8-device virtual CPU mesh")
    parser.add_argument("--ab-mesh", action="store_true",
                        help="ring vs ulysses on the SAME dp2xcp4 mesh "
                             "(the VERDICT r5 #4 attribution A/B: equal "
                             "mesh, data, steps — wall-time deltas are "
                             "schedule-only)")
    parser.add_argument("--seq", type=int, default=None,
                        help="--ab-mesh sequence length (default 2048)")
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--model", default=None)
    args = parser.parse_args()

    if args.ab_mesh:
        from polyaxon_tpu.utils import cpu_mesh_xla_flags

        cpu_mesh_xla_flags(8)
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
        seq = args.seq or 2048
        entries = []
        for attention in ("ring", "ulysses"):
            entries.append(run_point(
                f"{attention}-cpu8-dp2cp4-seq{seq}",
                model=args.model or "llama_tiny", seq=seq, batch=4,
                steps=args.steps or 4, mesh_axes={"dp": 2, "cp": 4},
                attention=attention, remat="none"))
        losses = [e["loss"] for e in entries]
        agree = (all(l == l for l in losses)
                 and abs(losses[0] - losses[1]) < 5e-3)
        ring_e, uly_e = entries
        print(json.dumps({
            "summary": f"ring vs ulysses @{seq} on the SAME dp2xcp4 mesh",
            "losses": {"ring": losses[0], "ulysses": losses[1]},
            "ring_over_ulysses_throughput": round(
                ring_e["tokens_per_sec_per_chip"]
                / max(uly_e["tokens_per_sec_per_chip"], 1e-9), 2),
            "ok": bool(agree),
        }))
        return 0 if agree else 1

    if args.cpu_mesh:
        from polyaxon_tpu.utils import cpu_mesh_xla_flags

        cpu_mesh_xla_flags(8)
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
        entries = []
        # Ulysses shards HEADS over the cp axis (heads % axis == 0), so
        # the 4-head tiny model takes cp=4 with dp=2 — same global
        # batch/data/steps, so the losses stay directly comparable.
        for attention, mesh_axes in (("ring", {"dp": 1, "cp": 8}),
                                     ("ulysses", {"dp": 2, "cp": 4})):
            entries.append(run_point(
                f"{attention}-cpu8-seq16k",
                model=args.model or "llama_tiny", seq=16384, batch=2,
                steps=args.steps or 2, mesh_axes=mesh_axes,
                attention=attention, remat="none"))
        losses = [e["loss"] for e in entries]
        finite = all(l == l for l in losses)
        # Same data/init/steps: the two SP schedules compute the same
        # math, so their losses must agree to float tolerance.
        agree = finite and abs(losses[0] - losses[1]) < 5e-3
        print(json.dumps({
            "summary": "ring + ulysses @16k on 8-dev cp mesh",
            "losses": {"ring": losses[0], "ulysses": losses[1]},
            "ok": bool(agree),
        }))
        return 0 if agree else 1

    # Chip mode: flash at 8k then 16k; the O(S) claim is the ratio.
    model = args.model or "llama_200m"
    points = []
    for seq in (8192, 16384):
        points.append(run_point(
            f"flash-{model}-seq{seq}",
            model=model, seq=seq, batch=1, steps=args.steps or 10,
            mesh_axes=None, attention="flash", remat="dots"))
    p8, p16 = points
    if p8["peak_bytes_per_device"] and p16["peak_bytes_per_device"]:
        ratio = p16["peak_bytes_per_device"] / p8["peak_bytes_per_device"]
        print(json.dumps({
            "summary": "peak-memory scaling 8k->16k",
            "ratio": round(ratio, 2),
            "interpretation": ("~2x = O(S) flash/activations; ~4x would "
                               "mean an S^2 tensor materialized"),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
