"""Mesh-context helpers for the manual (``jax.shard_map``) regions.

The manual schedules (ring/ulysses attention, SPMD pipeline, ragged MoE
dispatch) and the Pallas kernels all need the same three facts about
where they are being traced: which mesh is ambient, which of its axes
an enclosing ``shard_map`` has already made manual, and which axes
carry the batch. They live here so the schedules and the kernels agree.

Two conventions callers must honor:

- An axis left out of a spec is *replicated* into a manual body (the
  boundary all-gathers over it). Schedules that take batch-sharded
  activations therefore name the batch axes in their specs — see
  ``batch_axes_in``. The communication audit (``polyaxon_tpu/perf``)
  counts exactly the collectives this produces.
- GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot
  be automatically partitioned"), so under a multi-device mesh every
  Pallas kernel call goes through ``shard_kernel``.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

__all__ = ["ambient_mesh", "batch_axes_in", "kernel_axes", "shard_kernel",
           "unsharded"]

# Mesh axes that carry the batch dimension of activations (the rule
# tables map logical "batch" onto these — parallel/sharding.py; "ep"
# joins them under EP_RULES only).
_BATCH_AXES = ("dp", "fsdp")
_KERNEL_BATCH_AXES = _BATCH_AXES + ("ep",)
_HEAD_AXIS = "tp"


def ambient_mesh():
    """The mesh entered via ``with mesh:`` (as the runtime loop does),
    else the abstract mesh of an enclosing manual region, else None.

    Reads the resource env through ``jax._src.mesh``: jax 0.9 has no
    public accessor for the ``with mesh:`` context, and
    ``get_abstract_mesh()`` is only populated by ``jax.set_mesh`` and
    inside ``shard_map`` bodies.
    """
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.thread_resources.env.physical_mesh
    if not mesh.empty:
        return mesh
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def batch_axes_in(mesh: Mesh):
    """The nontrivial batch-carrying mesh axes, as a PartitionSpec entry
    (None / a name / a tuple of names). Manual schedules put this on the
    batch dim of their specs so the manual region keeps the batch
    sharded instead of gathering it — the audit showed the replicated
    spelling costs 4 extra all-gathers + dp-redundant attention compute
    per step on a dp2xcp4 mesh (docs/performance.md)."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return _spec_entry([a for a in _BATCH_AXES if shape.get(a, 1) > 1])


def _spec_entry(axes: list):
    if not axes:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def _unbound_axes():
    """``(mesh argument for shard_map, {axis: size})`` over the ambient
    mesh axes that no enclosing ``shard_map`` has made manual yet.
    Inside a manual region the mesh argument is None: a nested
    ``shard_map`` must take the context's abstract mesh, and refuses
    the concrete one."""
    context = jax.sharding.get_abstract_mesh()
    if not context.empty:
        manual = set(context.manual_axes)
        return None, {a: s for a, s in context.shape.items()
                      if a not in manual}
    mesh = ambient_mesh()
    if mesh is None:
        return None, {}
    return mesh, dict(mesh.shape)


def kernel_axes(batch: int, heads: int):
    """PartitionSpec entries ``(batch_entry, heads_entry)`` for a kernel
    whose operands carry a batch dim of size ``batch`` and a (kv-)head
    dim of size ``heads``: the unbound batch axes and the ``tp`` axis,
    each kept only while it divides its dim (an axis that does not is
    left out, i.e. the kernel runs replicated over it)."""
    _, free = _unbound_axes()
    batch_axes, shards = [], 1
    for axis in _KERNEL_BATCH_AXES:
        size = free.get(axis, 1)
        if size > 1 and batch % (shards * size) == 0:
            batch_axes.append(axis)
            shards *= size
    tp = free.get(_HEAD_AXIS, 1)
    head_entry = _HEAD_AXIS if tp > 1 and heads % tp == 0 else None
    return _spec_entry(batch_axes), head_entry


def unsharded() -> bool:
    """Whether a kernel traced here is handed whole arrays: no mesh,
    one device, or an enclosing region that already bound every axis."""
    _, free = _unbound_axes()
    return all(size == 1 for size in free.values())


def shard_kernel(fn, in_specs, out_specs):
    """``fn`` run per shard over every ambient mesh axis not yet manual
    (``fn`` itself when there is nothing to bind: `unsharded`)."""
    mesh, free = _unbound_axes()
    if all(size == 1 for size in free.values()):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(free),
                         check_vma=False)
