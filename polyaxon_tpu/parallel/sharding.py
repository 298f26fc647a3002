"""Logical-axis sharding rules: how params/activations map onto the mesh.

Models annotate every parameter with *logical* axis names (``("embed",
"mlp")`` …); a rule table maps logical names to mesh axes per parallelism
strategy. This is the flax/t5x "logical axis rules" idiom — the
TPU-native answer to the reference's delegated DP/FSDP/TP (SURVEY.md
§2b): instead of wiring torch DDP env vars, the framework owns the
placement of every tensor.

``-`` in a rule means "explicitly replicated"; an axis with no rule is
replicated too. A rule may map one logical axis to a tuple of mesh axes
(e.g. batch → ("dp", "fsdp") so FSDP shards the batch with dp).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Sequence[tuple[str, Union[None, str, tuple[str, ...]]]]

# Rule presets per strategy. Logical vocabulary used by models/:
#   batch, seq, embed, vocab, heads, kv_heads, head_dim, mlp, layers,
#   conv_in, conv_out, classes, expert
FSDP_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("embed", "fsdp"),
    ("vocab", None),
    ("mlp", None),
    ("heads", None),
    ("kv_heads", None),
    ("seq", None),
)
DP_RULES: Rules = (("batch", ("dp", "fsdp")),)
TP_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("embed", "fsdp"),
    ("vocab", "tp"),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
)
# TP with sequence parallelism: activations shard seq on tp outside
# attention/mlp blocks; param rules are the same as TP.
TP_SP_RULES: Rules = TP_RULES + (("seq", "sp"),)
# Context parallel (ring attention): sequence blocks over cp.
CP_RULES: Rules = (
    ("batch", ("dp", "fsdp")),
    ("embed", "fsdp"),
    ("seq", "cp"),
    ("heads", None),
)
# Expert parallel: experts over ep; the batch shards over ep TOO — ep
# devices act as extra data parallelism outside the MoE block (the
# standard GShard/Mixtral layout: without this, attention and every
# dense matmul would be computed ep-fold redundantly). Inside the
# block, tokens reshard token→expert: GSPMD inserts the all-to-alls
# for the dense one-hot dispatch; dispatch="ragged" does it explicitly
# with per-expert counts (models/moe.py _moe_ragged).
EP_RULES: Rules = (
    ("batch", ("dp", "fsdp", "ep")),
    ("embed", "fsdp"),
    ("expert", "ep"),
    ("mlp", None),
)

STRATEGY_RULES: dict[str, Rules] = {
    "dp": DP_RULES,
    "fsdp": FSDP_RULES,
    "tp": TP_RULES,
    "tp_sp": TP_SP_RULES,
    "cp": CP_RULES,
    "ep": EP_RULES,
}


def merge_rules(*rule_sets: Rules) -> Rules:
    """Later rule sets win per logical-axis name."""
    table: dict[str, Union[None, str, tuple[str, ...]]] = {}
    for rules in rule_sets:
        for name, target in rules:
            table[name] = target
    return tuple(table.items())


def rules_for_mesh(mesh: Mesh, base: Optional[Rules] = None) -> Rules:
    """Compose strategy rule-sets for every nontrivial axis in the mesh.

    A mesh with {dp, fsdp, tp} > 1 gets DP+FSDP+TP rules merged in that
    order; callers can override with ``base``.
    """
    sets: list[Rules] = [DP_RULES]
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if shape.get("fsdp", 1) > 1:
        sets.append(FSDP_RULES)
    if shape.get("tp", 1) > 1:
        sets.append(TP_RULES)
    if shape.get("sp", 1) > 1:
        sets.append(TP_SP_RULES)
    if shape.get("cp", 1) > 1:
        sets.append(CP_RULES)
    if shape.get("ep", 1) > 1:
        sets.append(EP_RULES)
    if base is not None:
        sets.append(base)
    return merge_rules(*sets)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Rules,
    *,
    mesh: Optional[Mesh] = None,
) -> P:
    """Map a tuple of logical axis names to a ``PartitionSpec``.

    Mesh axes already consumed by an earlier dimension are skipped
    (a mesh axis may shard at most one tensor dimension), and axes not
    present in the mesh (or of size 1) resolve to replication.
    """
    table = dict(rules)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else None
    used: set[str] = set()
    parts: list[Union[None, str, tuple[str, ...]]] = []
    for logical in logical_axes:
        target = table.get(logical) if logical is not None else None
        if target is None:
            parts.append(None)
            continue
        names = (target,) if isinstance(target, str) else tuple(target)
        kept = []
        for name in names:
            if name in used:
                continue
            if mesh_shape is not None and mesh_shape.get(name, 1) <= 1:
                continue
            kept.append(name)
            used.add(name)
        if not kept:
            parts.append(None)
        elif len(kept) == 1:
            parts.append(kept[0])
        else:
            parts.append(tuple(kept))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(
    logical_tree: Any,
    mesh: Mesh,
    rules: Rules,
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedSharding."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh=mesh)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def batch_spec(mesh: Mesh, rules: Rules, ndim: int = 2) -> P:
    """PartitionSpec for a [batch, ...] array (batch sharded, rest replicated)."""
    return logical_to_spec(("batch",) + (None,) * (ndim - 1), rules, mesh=mesh)


def param_bytes(params: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def bytes_per_device(tree: Any) -> dict[int, int]:
    """Bytes of ``tree`` resident on each addressable device, by device
    id. Says at a glance whether a tree is sharded (each device holds a
    fraction), replicated (each holds all of it), or sitting whole on
    the first device — which code that has only seen one chip can do
    without anyone noticing."""
    held: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.size * leaf.dtype.itemsize)
    return dict(sorted(held.items()))

