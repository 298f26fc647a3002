"""Int8 weight-only quantization for the serving path.

Decode is HBM-bandwidth-bound: every generated token re-reads the whole
weight tree, so halving the bytes at rest (bf16 -> int8 + per-channel
f32 scales) is a direct throughput lever on TPU (SURVEY.md §6 HBM
roofline; the reference serves full-precision only — net-new surface,
held to this repo's own bar per VERDICT r2 item 10).

Scheme: symmetric per-channel quantization over the contraction axis.
JAX weights are laid out ``[..., in, out]`` (activations contract the
second-to-last axis), so the scale reduces over ``axis=-2`` only —
stacked-layer weights ``[L, in, out]`` keep per-layer per-out-channel
scales, and the dequant ``q * scale`` broadcast is always elementwise-
valid whatever the rank.

Integration contract: engines pass quantized trees through WHOLE; each
model unwraps every weight at its consumption site (``models/llama.py
_w`` / ``_embed_rows``, shared by moe/t5), duck-typed on
``.dequantize``. The placement matters: inside a ``lax.scan`` decode
loop a tree-level dequant is loop-invariant, so XLA hoists it and
materializes a bf16 copy that every step re-reads — int8 then saves
nothing. Per-consumption unwrapping keeps the convert+multiply fused
into each matmul's operand read, so int8 stays the HBM-resident format
and bf16 weights exist only in VMEM tiles (embedding rows are gathered
int8-first, never the whole table). 1-D leaves (norm gains, biases)
stay full precision — they are a rounding error of the footprint and
the quality-sensitive part.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from polyaxon_tpu.models.common import HELD_TRANSPOSED_SUFFIX

_QMAX = 127.0


class QuantizedTensor:
    """An int8 weight + broadcastable scale, registered as a pytree so
    quantized trees flow through jit/device_put/tree_map unchanged."""

    __slots__ = ("q", "scale", "dtype")

    def __init__(self, q, scale, dtype):
        self.q = q
        self.scale = scale
        self.dtype = np.dtype(dtype)

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes(self) -> int:
        return int(self.q.size * self.q.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)

    def dequantize(self) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(self.dtype)

    def tree_flatten(self):
        return (self.q, self.scale), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        return cls(children[0], children[1], dtype)

    def __repr__(self):
        return f"QuantizedTensor(shape={tuple(self.q.shape)}, dtype={self.dtype})"


jax.tree_util.register_pytree_node(
    QuantizedTensor,
    lambda t: t.tree_flatten(),
    QuantizedTensor.tree_unflatten,
)


# Leaf-name fragments that mark NON-matmul per-layer vectors (norm
# gains/biases, layer-norm scale/bias pairs, additive biases). These
# are excluded BY NAME, not just rank: stacked per-layer vectors are
# 2-D ([L, D] — a rank rule can't tell them from embed/lm_head), they
# are the quality-sensitive part, and their reduced scale ([1, D],
# leading axis 1) cannot ride a lax.scan over the layer stack the way
# real stacked weights' [L, 1, out] scales can.
_SKIP_FRAGMENTS = ("norm", "bias", "scale", "ln1", "ln2", "router", "pos")
# "router": MoE router weights are a rounding error of the footprint
# ([L, D, E]) but feed an argmax/top-k — a discrete, discontinuous
# choice where quantization noise flips expert assignment outright
# rather than nudging logits. Standard practice keeps routers in full
# precision; the bytes saved would be unmeasurable.
# "pos": additive positional tables (t5 enc_pos) are 2-D but not
# matmul weights — their dequant noise adds straight into every
# activation, and they are footprint-negligible like the norms.


def _eligible(path, leaf: Any) -> bool:
    segments = [str(getattr(k, "key", k)).lower() for k in path]
    if any(frag in seg for seg in segments for frag in _SKIP_FRAGMENTS):
        return False
    if segments and segments[-1].startswith("b_"):
        return False
    return (hasattr(leaf, "ndim") and leaf.ndim >= 2
            and jnp.issubdtype(leaf.dtype, jnp.floating))


def quantize_leaf(w: jax.Array) -> QuantizedTensor:
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / _QMAX  # all-zero channels stay finite
    q = jnp.clip(jnp.round(w32 / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return QuantizedTensor(q, scale, w.dtype)


_jit_quantize_leaf = jax.jit(quantize_leaf)  # one compile per distinct shape


def quantize_tree(params: Any, *, mode: str = "int8") -> Any:
    """Quantize every matmul-shaped leaf (ndim >= 2, floating) of a
    params tree to int8 + per-channel scales. Runs jitted so sharded
    inputs produce sharded quantized weights (GSPMD propagates the
    input sharding through the elementwise quant ops)."""
    if mode != "int8":
        raise ValueError(f"unknown quantization mode {mode!r} "
                         "(supported: 'int8')")
    return jax.tree_util.tree_map_with_path(
        lambda p, w: _jit_quantize_leaf(w) if _eligible(p, w) else w,
        params)


def dequantize_tree(params: Any) -> Any:
    """Identity on plain trees; materializes bf16/f32 views of quantized
    leaves. NOT used on the serving hot path anymore (models unwrap at
    consumption — see module docstring); kept for tests and interop
    (e.g. exporting a quantized checkpoint back to full precision)."""
    return jax.tree.map(
        lambda leaf: leaf.dequantize() if isinstance(leaf, QuantizedTensor)
        else leaf,
        params, is_leaf=lambda leaf: isinstance(leaf, QuantizedTensor))


def tree_bytes(params: Any) -> int:
    """Device bytes of a (possibly quantized) params tree — the number
    the int8 path exists to halve."""
    return sum(
        leaf.nbytes for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if hasattr(leaf, "nbytes"))


def weight_bytes(params: Any) -> dict[str, int]:
    """``tree_bytes`` split by the dtype the bytes are held in (an int8
    leaf counts its ``q`` under int8 and its scales under float32):
    `/v1/stats` ``weight_bytes``. A server holds its matmul weights in
    the compute dtype (``server.py load_params``), so float32 here is
    the norm gains and what else a family reads at float32."""
    held: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        if hasattr(leaf, "nbytes"):
            name = str(leaf.dtype)
            held[name] = held.get(name, 0) + int(leaf.nbytes)
    return dict(sorted(held.items()))


def held_transposed_bytes(params: Any) -> int:
    """Bytes of the projections a tree holds ``[N, D]`` (``models/
    common.py served_params``; 0 on a plain tree): `/v1/stats`
    ``weights_held_transposed_bytes``, which tree a run measured."""
    return sum(
        int(leaf.nbytes)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if str(getattr(path[-1], "key", "")).endswith(HELD_TRANSPOSED_SUFFIX))
