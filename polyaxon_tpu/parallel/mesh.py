"""Device-mesh construction from JAXJob topology + mesh specs.

This is where the reference's replica-count orchestration (SURVEY.md §2b
[K]: Polyaxon only wires replica specs and rendezvous env; all real
parallelism is delegated) becomes an owned, first-class layer: a
``V1MeshSpec`` resolves against the slice topology into a
``jax.sharding.Mesh`` whose ICI-heavy axes (fsdp/tp/sp/cp/ep) sit on
intra-slice device dimensions and whose DCN axes (usually dp) span
slices — the hierarchy `jax.experimental.mesh_utils` encodes.

Axis convention (outermost → innermost):
    dp    data parallel (pure replication of params; gradients psum)
    pp    pipeline stages (DCN-friendly cuts)
    fsdp  fully-sharded data parallel (params/opt-state sharded; the
          [B] target config for Llama-3-8B over ICI)
    cp    context parallel (ring attention over sequence blocks)
    sp    sequence parallel (activation sharding fused with tp)
    ep    expert parallel (MoE dispatch axis)
    tp    tensor parallel (innermost — highest-bandwidth ICI)
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

from polyaxon_tpu.polyflow.environment import V1TpuTopology
from polyaxon_tpu.polyflow.runs import V1MeshSpec

# Canonical axis order: ICI-bandwidth-hungry axes innermost.
AXIS_ORDER: tuple[str, ...] = ("dp", "pp", "fsdp", "cp", "sp", "ep", "tp")

# Aliases accepted in specs (upstream-ish vocabulary → canonical).
AXIS_ALIASES = {"data": "dp", "model": "tp", "expert": "ep", "seq": "sp"}


def canonical_axes(axes: dict[str, int]) -> dict[str, int]:
    out: dict[str, int] = {}
    for name, size in axes.items():
        canon = AXIS_ALIASES.get(name, name)
        if canon in out:
            raise ValueError(f"Duplicate mesh axis `{name}` (alias of `{canon}`)")
        out[canon] = size
    return out


def order_axes(axes: dict[str, int]) -> dict[str, int]:
    """Order axes canonically; unknown axes keep their given order, last."""
    known = {k: axes[k] for k in AXIS_ORDER if k in axes}
    unknown = {k: v for k, v in axes.items() if k not in AXIS_ORDER}
    return {**known, **unknown}


def parse_mesh_axes(spec: str) -> dict[str, int]:
    """Parse a CLI-style mesh string — ``"tp=4,dp=2"`` / ``"fsdp=-1"``
    (-1 = absorb remaining devices) — into an axes dict. Raises
    ``ValueError`` with an actionable message; entry points convert it
    to their own usage-error style."""
    axes: dict[str, int] = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name = name.strip()
        if name in axes:
            raise ValueError(f"mesh axis {name!r} given twice")
        try:
            axes[name] = int(size)
        except ValueError:
            raise ValueError(
                f"mesh axes expect name=size pairs "
                f"(e.g. 'tp=4,dp=2'), got {part.strip()!r}") from None
        if not name:
            raise ValueError(f"mesh axis in {part.strip()!r} has no name")
    return axes


def build_mesh(
    mesh_spec: Optional[V1MeshSpec] = None,
    topology: Optional[V1TpuTopology] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axes: Optional[dict[str, int]] = None,
) -> Mesh:
    """Build a ``Mesh`` from a spec (or raw ``axes``) over ``devices``.

    Single-slice: ``mesh_utils.create_device_mesh`` maps the logical mesh
    onto the ICI torus. Multi-slice (``topology.slices > 1`` and
    ``dcn_axes``): ``create_hybrid_device_mesh`` places the DCN axes
    across slice granules so only those axes pay DCN latency
    (SURVEY.md §2c).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)

    if axes is None:
        if mesh_spec is None:
            axes = {"dp": n}
        else:
            axes = mesh_spec.resolved_axes(n)
    axes = order_axes(canonical_axes(axes))

    sizes = [s for s in axes.values()]
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"Mesh axes {axes} need {total} devices, have {n}")

    dcn_axes = set()
    if mesh_spec is not None and mesh_spec.dcn_axes:
        dcn_axes = {AXIS_ALIASES.get(a, a) for a in mesh_spec.dcn_axes}
    slices = topology.slices if topology is not None else 1

    names = tuple(axes.keys())
    if slices > 1 and dcn_axes:
        ici_shape = [1 if name in dcn_axes else size for name, size in axes.items()]
        dcn_shape = [size if name in dcn_axes else 1 for name, size in axes.items()]
        try:
            device_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape,
                dcn_shape,
                devices=devices,
                allow_split_physical_axes=bool(mesh_spec and mesh_spec.allow_split_physical_axes),
            )
            logger.info("hybrid mesh: dcn_axes=%s over %d hardware slices",
                        sorted(dcn_axes), slices)
        except ValueError:
            # Devices without slice_index (the CPU mesh): imitate the
            # slice granularity by putting DCN axes slowest-varying so each
            # contiguous device block is one "slice".
            perm = sorted(range(len(names)), key=lambda i: names[i] not in dcn_axes)
            permuted_sizes = [sizes[i] for i in perm]
            arr = np.asarray(devices).reshape(permuted_sizes)
            inverse = np.argsort(perm)
            device_array = arr.transpose(tuple(inverse))
            logger.info(
                "hybrid mesh: dcn_axes=%s over %d emulated slices "
                "(devices lack slice_index; DCN axes placed slowest-varying)",
                sorted(dcn_axes), slices)
    else:
        try:
            device_array = mesh_utils.create_device_mesh(
                sizes,
                devices=devices,
                allow_split_physical_axes=bool(mesh_spec and mesh_spec.allow_split_physical_axes),
            )
        except Exception:
            # CPU meshes / odd virtual topologies: fall back to a plain
            # row-major reshape (no ICI assignment to optimize anyway).
            device_array = np.asarray(devices).reshape(sizes)
    return Mesh(device_array, names)


def single_device_mesh(axis: str = "dp") -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,)), (axis,))


def mesh_summary(mesh: Mesh) -> dict:
    return {
        "axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": int(mesh.devices.size),
        "platform": mesh.devices.flat[0].platform,
        "device_kind": getattr(mesh.devices.flat[0], "device_kind", "unknown"),
    }
