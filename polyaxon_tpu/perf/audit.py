"""Per-(model, mesh, schedule) collective audit of the REAL train step.

Each point builds the same ``build_train_step`` program the runtime
loop executes (same rule tables, same optimizer, same donation), lowers
and compiles it against an N-device mesh, and censuses the collectives
in the compiled HLO (``perf/hlo.py``). Because the program is the real
one, a sharding-rule regression anywhere — model annotations, rule
tables, a manual schedule's specs — lands in these counts.

``inject_reshard=True`` deliberately re-constrains the batch to
replicated inside the step (the canonical "accidental reshard": one
stray ``with_sharding_constraint`` or a rule-table typo), which is how
tests and docs demonstrate the budget gate actually fails.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

from polyaxon_tpu.perf import hlo as hlo_lib


@dataclasses.dataclass(frozen=True)
class AuditPoint:
    """One (model, mesh, schedule) audit coordinate."""

    name: str
    axes: dict[str, int]
    model: str = "llama_tiny"
    attention: Optional[str] = None  # None = the model's default (xla)
    seq_len: int = 256
    global_batch: int = 8

    def describe(self) -> dict:
        return {
            "name": self.name,
            "model": self.model,
            "axes": dict(self.axes),
            "attention": self.attention or "xla",
            "seq_len": self.seq_len,
            "global_batch": self.global_batch,
        }


# The standing schedule census on the 8-device virtual mesh: one point
# per parallelism family whose collectives CI keeps budgeted. Meshes
# mirror the MULTICHIP dryrun; ring and ulysses share dp2xcp4 so their
# reports diff directly (the r5 4.7x-gap attribution mesh).
STANDARD_POINTS: tuple[AuditPoint, ...] = (
    AuditPoint("dp", {"dp": 8}),
    AuditPoint("fsdp", {"dp": 2, "fsdp": 4}),
    AuditPoint("tp", {"dp": 2, "tp": 4}),
    AuditPoint("ring-cp", {"dp": 2, "cp": 4}, attention="ring"),
    AuditPoint("ulysses-cp", {"dp": 2, "cp": 4}, attention="ulysses"),
)


def point_by_name(name: str) -> AuditPoint:
    for p in STANDARD_POINTS:
        if p.name == name:
            return p
    raise KeyError(
        f"unknown schedule {name!r}; standard points: "
        f"{[p.name for p in STANDARD_POINTS]}")


def audit_point(
    point: AuditPoint,
    *,
    inject_reshard: bool = False,
    devices: Optional[list] = None,
    keep_ops: bool = False,
) -> dict[str, Any]:
    """Compile the point's train step and census its collectives.

    Pure analysis: nothing is executed on the devices — ``lower()`` +
    ``compile()`` only — so a point is safe to run under CI timeouts.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyaxon_tpu.models import get_model
    from polyaxon_tpu.parallel.mesh import build_mesh
    from polyaxon_tpu.parallel.sharding import batch_spec, rules_for_mesh
    from polyaxon_tpu.runtime.config import RuntimeConfig
    from polyaxon_tpu.runtime.optim import build_optimizer
    from polyaxon_tpu.runtime.step import build_init, build_train_step

    t0 = time.perf_counter()
    n_needed = 1
    for s in point.axes.values():
        n_needed *= s
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n_needed:
        raise ValueError(
            f"point {point.name!r} needs {n_needed} devices, have "
            f"{len(devices)} (CI runs on the 8-device virtual CPU mesh)")
    hlo_lib.require_modelled_device(devices[0])
    mesh = build_mesh(axes=dict(point.axes), devices=devices[:n_needed])
    rules = rules_for_mesh(mesh)

    overrides: dict[str, Any] = {"max_seq_len": point.seq_len}
    if point.attention:
        overrides["attention_impl"] = point.attention
    model_def = get_model(point.model, **overrides)
    if inject_reshard:
        base_apply = model_def.apply
        replicated = NamedSharding(mesh, P())

        def bad_apply(variables, batch, train, rng):
            batch = dict(batch)
            batch["tokens"] = jax.lax.with_sharding_constraint(
                batch["tokens"], replicated)
            return base_apply(variables, batch, train, rng)

        model_def = dataclasses.replace(model_def, apply=bad_apply)

    cfg = RuntimeConfig(model=point.model, seq_len=point.seq_len)
    optimizer = build_optimizer(cfg)

    with mesh:
        init_fn = build_init(model_def, optimizer, mesh, rules)
        state = init_fn(jax.random.key(0))
        train_step = build_train_step(model_def, optimizer, mesh, rules)
        tokens = jnp.zeros((point.global_batch, point.seq_len), jnp.int32)
        sharding = NamedSharding(mesh, batch_spec(mesh, rules, ndim=2))
        batch = {"tokens": jax.device_put(tokens, sharding)}
        compiled = train_step.lower(state, batch, jax.random.key(1)).compile()
    hlo_text = compiled.as_text()

    ops = hlo_lib.parse_collectives(hlo_text, n_devices=mesh.devices.size)
    report = point.describe()
    report.update(hlo_lib.summarize_collectives(ops))
    overlap = hlo_lib.summarize_overlap(ops)
    report.update({
        # XLA:CPU emits only sync collectives (no async encoding on
        # that backend), so these rows carry overlap_ratio 0 on the CI
        # mesh; the overlap *budget* is enforced on the AOT TPU
        # topology path only (perf --audit --check).
        "overlap": overlap,
        "overlap_ratio": overlap["overlap_ratio"],
        "n_devices": int(mesh.devices.size),
        "backend": devices[0].platform,
        "compile_s": round(time.perf_counter() - t0, 1),
        "injected_reshard": bool(inject_reshard),
    })
    if keep_ops:
        report["ops"] = [dataclasses.asdict(o) for o in ops]
    return report


def audit_point_aot(point: AuditPoint, topology_name: str = "v5e:2x4",
                    keep_hlo: bool = False,
                    compiler_options: Optional[dict] = None) -> dict[str, Any]:
    """The audit against a TPU *topology description* — no live device.

    Nothing can execute, so the train state is fully abstract:
    ``eval_shape`` over the real ``build_init`` gives the avals, params
    carry their rule-table shardings, and the optimizer state's input
    shardings are left to GSPMD propagation (the one divergence from
    the runtime loop, where opt state is committed like params —
    collective counts here are TPU-backend evidence, not budget
    ground truth, which stays the CPU-mesh concrete path).

    Call this only inside the strictly-timeouted probe subprocess
    (``perf/aot.py``): creating the topology initializes libtpu.
    """
    import os

    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyaxon_tpu.models import get_model
    from polyaxon_tpu.parallel.mesh import build_mesh
    from polyaxon_tpu.parallel.sharding import batch_spec, rules_for_mesh
    from polyaxon_tpu.runtime.config import RuntimeConfig
    from polyaxon_tpu.runtime.optim import build_optimizer
    from polyaxon_tpu.runtime.step import (
        build_init,
        build_train_step,
        state_shardings,
    )

    t0 = time.perf_counter()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology_name)
    devices = list(topo.devices)
    hlo_lib.require_modelled_device(devices[0])
    mesh = build_mesh(axes=dict(point.axes), devices=devices)
    rules = rules_for_mesh(mesh)
    overrides: dict[str, Any] = {"max_seq_len": point.seq_len}
    if point.attention:
        overrides["attention_impl"] = point.attention
    model_def = get_model(point.model, **overrides)
    cfg = RuntimeConfig(model=point.model, seq_len=point.seq_len)
    optimizer = build_optimizer(cfg)

    with mesh:
        init_fn = build_init(model_def, optimizer, mesh, rules)
        rng_aval = jax.eval_shape(lambda: jax.random.key(0))
        avals = jax.eval_shape(init_fn, rng_aval)
        shardings = state_shardings(model_def, mesh, rules)
        abstract = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        state = {
            "params": jax.tree.map(
                lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sh),
                avals["params"], shardings["params"]),
            "state": jax.tree.map(abstract, avals["state"]),
            "opt_state": jax.tree.map(abstract, avals["opt_state"]),
            "step": jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(mesh, P())),
        }
        train_step = build_train_step(model_def, optimizer, mesh, rules)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (point.global_batch, point.seq_len), jnp.int32,
            sharding=NamedSharding(mesh, batch_spec(mesh, rules, ndim=2)))}
        lowered = train_step.lower(state, batch, rng_aval)
        if compiler_options:
            compiled = lowered.compile(compiler_options=dict(compiler_options))
        else:
            compiled = lowered.compile()
    hlo_text = compiled.as_text()

    ops = hlo_lib.parse_collectives(hlo_text, n_devices=mesh.devices.size)
    report = point.describe()
    report.update(hlo_lib.summarize_collectives(ops))
    overlap = hlo_lib.summarize_overlap(ops)
    report.update({
        "overlap": overlap,
        "overlap_ratio": overlap["overlap_ratio"],
        "n_devices": int(mesh.devices.size),
        "backend": "tpu-topology",
        "topology": topology_name,
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "hlo_chars": len(hlo_text),
        "compile_s": round(time.perf_counter() - t0, 1),
        "compiler_options": dict(compiler_options or {}),
    })
    try:
        mem = compiled.memory_analysis()
        report["memory_analysis"] = {
            "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
            "argument_size_bytes": int(
                getattr(mem, "argument_size_in_bytes", -1)),
            "output_size_bytes": int(
                getattr(mem, "output_size_in_bytes", -1)),
        }
    except Exception as exc:  # cost/memory APIs vary per jaxlib
        report["memory_analysis_error"] = type(exc).__name__
    if keep_hlo:
        report["hlo"] = hlo_text
    return report


def diff_reports(a: dict, b: dict) -> dict:
    """Collective-count/byte delta between two point reports (the
    ring-vs-ulysses attribution shape: same mesh, different schedule)."""
    kinds = sorted(set(a.get("counts", {})) | set(b.get("counts", {})))
    return {
        "a": a.get("name"),
        "b": b.get("name"),
        "count_delta": {
            k: b.get("counts", {}).get(k, 0) - a.get("counts", {}).get(k, 0)
            for k in kinds},
        "wire_bytes_delta": (b.get("est_wire_bytes_per_step", 0)
                             - a.get("est_wire_bytes_per_step", 0)),
    }
