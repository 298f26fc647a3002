"""AOT topology-only TPU compilation probe.

Answers, without a chip attached: can this image's toolchain compile
real programs against a TPU *topology description*
(``jax.experimental.topologies.get_topology_desc``) and hand back TPU
HLO + cost-model stats? Yes, once ``TPU_SKIP_MDS_QUERY=1`` is set.
Without it, libtpu's init path blocks ~4 minutes querying GCP instance
metadata (30 retries against a 403ing endpoint). The kernels of the
main path are held to this compiler on every test run
(``tests/test_aot_tpu_compile.py``); this module is the wider probe
(tile candidates, whole train steps, overlap audits).

Probe stages, each recorded independently per topology candidate:

1. topology description (device count / kind),
2. AOT compile of a dp-sharded matmul + cost/memory analysis,
3. flash-attention Pallas forward at the sweep's tile candidates with
   ``interpret=False`` — Mosaic compiles for real, so a tile set that
   blows VMEM fails HERE instead of in the next measurement window,
4. (``--train-step``) the real ``build_train_step`` program for a
   standard audit point, compiled for the topology and collective-
   censused (``audit.audit_point_aot``) — TPU HLO evidence for a sweep
   point with no chip time spent.

Every probe runs in a strictly-timeouted subprocess: libtpu init is
exactly the thing that can hang, and a hung probe must cost a timeout
entry in the artifact, never a wedged CI run. SIGTERM first, SIGKILL
only after a grace period. libtpu admits one loading process at a time
(a second aborts on its lock file), so the candidates run one after
another, and never from a process that holds a chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

PROBE_TIMEOUT_S = 300.0

# Topology names tried in order: the v5e shape matching the 8-device
# audit meshes first, then a v4 spelling as an API-liveness control.
TOPOLOGY_CANDIDATES = ("v5e:2x4", "v4:2x2x1")

# Flash fwd tile candidates from the staged sweep (VERDICT r4 item 3),
# probed at llama_200m attention shapes. (256, 256) is the safety
# floor: if the bigger tiles blow VMEM on some topology, the pick
# table still records a workable choice.
FLASH_TILES = ((512, 512), (1024, 1024), (256, 256))

_CHILD_FLAG = "--_probe-child"


def flash_pick(tiles: dict) -> Optional[dict]:
    """The per-topology tile pick from a probe's candidate records: the
    largest (block_q, block_k) Mosaic actually compiled — compilation
    IS the VMEM-fit evidence (a tile set that doesn't fit fails with
    RESOURCE_EXHAUSTED at compile, not at run time). Kept in the
    probe's report; the tiles a default call takes are one rule in
    ``ops/flash.py auto_blocks``."""
    best = None
    for tag, rec in tiles.items():
        if not rec.get("compiled"):
            continue
        bq, bk = (int(p) for p in tag.split("x"))
        if best is None or bq * bk > best[0] * best[1]:
            best = (bq, bk)
    if best is None:
        return None
    return {"block_q": best[0], "block_k": best[1]}


def _flash_vmem_stage(topology, entry: dict) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from polyaxon_tpu.ops.flash import flash_attention

    devices = list(topology.devices)
    mesh = Mesh(np.array(devices[:1]).reshape(1), ("dp",))
    repl = NamedSharding(mesh, P())
    b, s, h, kv, d = 8, 2048, 16, 8, 64  # llama_200m @ the sweep's seq
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=repl)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=repl)
    v = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=repl)
    tiles = {}
    entry["flash_tiles"] = tiles
    for bq, bk in FLASH_TILES:
        tag = f"{bq}x{bk}"
        fn = jax.jit(functools.partial(
            flash_attention, causal=True, block_q=bq, block_k=bk,
            interpret=False))
        try:
            compiled = fn.lower(q, k, v).compile()
            rec = {"compiled": True}
            try:
                mem = compiled.memory_analysis()
                rec["temp_size_bytes"] = int(
                    getattr(mem, "temp_size_in_bytes", -1))
            except Exception as exc:
                rec["memory_analysis_error"] = type(exc).__name__
            tiles[tag] = rec
        except Exception as exc:
            # RESOURCE_EXHAUSTED here IS the VMEM-fit evidence.
            tiles[tag] = {"compiled": False,
                          "error": f"{type(exc).__name__}: "
                                   f"{str(exc)[:300]}"}
    entry["flash_tile_pick"] = flash_pick(tiles)


def _child_main(argv: list[str]) -> int:
    """Runs inside the subprocess: probe ONE topology candidate, print
    ONE JSON line. Never raises — every failure is a recorded negative,
    which is the artifact's whole point."""
    if "--sleep" in argv:  # test hook: a hang, without a TPU
        time.sleep(float(argv[argv.index("--sleep") + 1]))
        return 0
    name = argv[argv.index("--topology") + 1]
    train_points = []
    if "--train-step" in argv:
        train_points = [s for s in
                        argv[argv.index("--train-step") + 1].split(",") if s]
    entry: dict = {"topology": name, "ok": False}
    try:
        import jax
        from jax.experimental import topologies

        entry["jax_version"] = jax.__version__
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=name)
        devices = list(topo.devices)
        entry["devices"] = len(devices)
        entry["device_kind"] = getattr(devices[0], "device_kind",
                                       "unknown") if devices else None
    except Exception as exc:
        entry["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        print(json.dumps(entry))
        return 0

    if "--pipeline-drill" in argv:
        # Pipeline-overlap drill (ISSUE 12): compile the double-buffered
        # toy pipeline against the topology with the latency-hiding
        # scheduler pinned and measure whether the stage→stage
        # ppermutes actually hide under stage compute. Value parity
        # between the schedules is CPU-testable and asserted in
        # tests/test_perf_audit.py; THIS measures the TPU schedule.
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh

        from polyaxon_tpu.parallel import overlap
        from polyaxon_tpu.parallel.pipeline import pipeline_forward
        from polyaxon_tpu.perf import hlo as hlo_mod

        options = overlap.latency_hiding_options(
            serialize="--serialize" in argv)
        n = len(devices)
        mesh = Mesh(np.array(devices).reshape(n), ("pp",))
        d = 1024  # permute payload [mb, d]; hideable fraction ∝ d
        stacked = jax.ShapeDtypeStruct((n, 1, d, d), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((4 * n, d), jnp.bfloat16)

        def stage_fn(local, h):
            out, _ = jax.lax.scan(
                lambda h, w: (jnp.tanh(h @ w), None), h, local["w"])
            return out

        entry["pipeline_drill"] = drill = {}
        for tag, db in (("double", True), ("single", False)):
            try:
                compiled = jax.jit(
                    lambda p, t, db=db: pipeline_forward(
                        mesh, stage_fn, {"w": p}, t,
                        n_microbatches=4, double_buffer=db)
                ).lower(stacked, x).compile(compiler_options=dict(options))
                ops = hlo_mod.parse_collectives(
                    compiled.as_text(), n_devices=n)
                perm = [o for o in ops if o.kind == "collective-permute"]
                drill[tag] = {
                    "overlap": hlo_mod.summarize_overlap(ops),
                    "n_permutes": len(perm),
                    "permute_max_overlap": max(
                        (o.overlap_ratio for o in perm), default=0.0),
                }
                entry["ok"] = True
            except Exception as exc:
                drill[tag] = {"error": f"{type(exc).__name__}: "
                                       f"{str(exc)[:300]}"}
        print(json.dumps(entry))
        return 0

    if "--overlap-audit" in argv:
        # Overlap-audit mode (ISSUE 12): compile the listed schedule
        # points with the latency-hiding scheduler pinned (or forcibly
        # serialized — the gate's deopt) and report their measured
        # overlap. Skips the matmul/flash stages: one subprocess, one
        # topology, all points, so the CI stage pays libtpu init once.
        from polyaxon_tpu.parallel import overlap
        from polyaxon_tpu.perf import audit

        serialize = "--serialize" in argv
        options = overlap.latency_hiding_options(serialize=serialize)
        points = [s for s in
                  argv[argv.index("--overlap-audit") + 1].split(",") if s]
        reports: dict = {}
        entry["overlap_audit"] = reports
        entry["serialized"] = serialize
        for point_name in points:
            try:
                reports[point_name] = audit.audit_point_aot(
                    audit.point_by_name(point_name), topology_name=name,
                    compiler_options=options)
                entry["ok"] = True
            except Exception as exc:
                reports[point_name] = {
                    "error": f"{type(exc).__name__}: {str(exc)[:300]}"}
        print(json.dumps(entry))
        return 0

    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices).reshape(len(devices)), ("dp",))
        x = jax.ShapeDtypeStruct((8 * len(devices), 512), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("dp")))
        w = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P()))
        compiled = jax.jit(lambda a, b: a @ b).lower(x, w).compile()
        entry["matmul"] = {"compiled": True,
                           "hlo_chars": len(compiled.as_text())}
        try:
            cost = compiled.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            entry["matmul"]["cost_flops"] = float(cost.get("flops", -1.0))
        except Exception as exc:
            entry["matmul"]["cost_analysis_error"] = type(exc).__name__
        entry["ok"] = True
    except Exception as exc:
        entry["matmul"] = {"compiled": False,
                           "error": f"{type(exc).__name__}: "
                                    f"{str(exc)[:300]}"}

    try:
        _flash_vmem_stage(topo, entry)
    except Exception as exc:
        entry["flash_tiles_error"] = f"{type(exc).__name__}: {str(exc)[:300]}"

    if train_points:
        from polyaxon_tpu.perf import audit

        reports = {}
        entry["train_step"] = reports
        for point_name in train_points:
            try:
                reports[point_name] = audit.audit_point_aot(
                    audit.point_by_name(point_name), topology_name=name)
            except Exception as exc:
                reports[point_name] = {
                    "error": f"{type(exc).__name__}: {str(exc)[:300]}"}
    print(json.dumps(entry))
    return 0


def _run_child(child_args: list[str], timeout_s: float) -> dict:
    cmd = [sys.executable, "-m", "polyaxon_tpu.perf.aot", _CHILD_FLAG]
    cmd += child_args
    env = {**os.environ}
    # The whole finding: topology-only compile works iff libtpu skips
    # the GCP metadata server (30x ~8s retries on non-GCP hosts).
    env["TPU_SKIP_MDS_QUERY"] = "1"
    # The probe targets topology compilation, not an attached device:
    # the child's own backend stays the CPU.
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    with subprocess.Popen(cmd, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True,
                          env=env) as popen:
        try:
            stdout, stderr = popen.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            popen.terminate()
            try:
                popen.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                popen.kill()
                popen.communicate()
            return {"ok": False, "timed_out": True,
                    "error": f"probe timeout>{timeout_s:.0f}s",
                    "wall_s": round(time.time() - t0, 1)}
    for line in reversed(stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            parsed["wall_s"] = round(time.time() - t0, 1)
            return parsed
    tail = " | ".join(stderr.strip().splitlines()[-3:])[-300:]
    return {"ok": False, "error": f"probe rc={popen.returncode}: {tail}",
            "wall_s": round(time.time() - t0, 1)}


def run_probe(timeout_s: float = PROBE_TIMEOUT_S,
              extra_child_args: Optional[list[str]] = None,
              train_step_points: Optional[str] = None) -> dict:
    """Probe each topology candidate in its own timeouted subprocess.

    Returns ``{"ok": <any candidate compiled>, "topologies": {...}}``;
    guaranteed to return in ~``timeout_s`` + 60s grace per candidate.
    ``extra_child_args`` replaces the candidate loop with one raw child
    invocation (the tests' ``--sleep`` hang hook).
    """
    if extra_child_args is not None:
        return _run_child(list(extra_child_args), timeout_s)
    out: dict = {"ok": False, "topologies": {}}
    for name in TOPOLOGY_CANDIDATES:
        args = ["--topology", name]
        if train_step_points:
            args += ["--train-step", train_step_points]
        entry = _run_child(args, timeout_s)
        out["topologies"][name] = entry
        out["ok"] = out["ok"] or bool(entry.get("ok"))
        if entry.get("ok") and train_step_points:
            # One topology with full evidence is the artifact's job;
            # don't spend another compile window on the control.
            break
    return out


def run_overlap_audit(points: Optional[list[str]] = None,
                      serialize: bool = False,
                      timeout_s: float = PROBE_TIMEOUT_S) -> dict:
    """Compile the standard schedule points against the first workable
    TPU topology with the overlap scheduler pinned (``serialize=True``
    = the forced-sync deopt) and return their overlap-annotated audit
    reports. Same containment contract as :func:`run_probe`: each
    candidate runs in its own strictly-timeouted subprocess, so a
    wedged libtpu init costs a timeout entry, never a hung CI stage."""
    from polyaxon_tpu.perf import audit

    names = ",".join(points if points
                     else [p.name for p in audit.STANDARD_POINTS])
    out: dict = {"ok": False, "serialized": serialize, "topologies": {}}
    for name in TOPOLOGY_CANDIDATES:
        args = ["--topology", name, "--overlap-audit", names]
        if serialize:
            args.append("--serialize")
        entry = _run_child(args, timeout_s)
        out["topologies"][name] = entry
        if entry.get("ok"):
            out["ok"] = True
            out["topology"] = name
            audit_map = entry.get("overlap_audit", {})
            out["reports"] = [r for r in audit_map.values()
                              if "error" not in r]
            errors = {k: r["error"] for k, r in audit_map.items()
                      if "error" in r}
            if errors:
                out["point_errors"] = errors
            break
    return out


def run_pipeline_drill(serialize: bool = False,
                       timeout_s: float = PROBE_TIMEOUT_S) -> dict:
    """Compile the double-buffered (and single-buffered control) toy
    pipeline against the first workable TPU topology and report the
    measured collective-permute overlap (same containment contract as
    :func:`run_probe`)."""
    out: dict = {"ok": False, "topologies": {}}
    for name in TOPOLOGY_CANDIDATES:
        args = ["--topology", name, "--pipeline-drill"]
        if serialize:
            args.append("--serialize")
        entry = _run_child(args, timeout_s)
        out["topologies"][name] = entry
        if entry.get("ok"):
            out["ok"] = True
            out["topology"] = name
            out["pipeline_drill"] = entry.get("pipeline_drill", {})
            break
    return out


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        sys.exit(_child_main(sys.argv))
    print(json.dumps(run_probe(), indent=2))
