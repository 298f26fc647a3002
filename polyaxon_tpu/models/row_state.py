"""What the families with a per-row state share of the engine's paged
surface (``models/nemotron_h.py``: Mamba-2's state; ``models/
qwen3_next.py``: the gated delta rule's).

Such a family's cache has K and V pages for its attention layers, as
llama's, and beside them leaves *per row* under ``cache["rows"]``
(``[L, rows, ...]``, given by the family's ``paged_init_rows``): what a
sequence carries whatever its length. The engine tells every prefill
program its row (``serving/batching.py``); a radix match has no state to
resume from, so the pool matches nothing for such a cache
(``serving/paged.py``).

A family brings its ``_sequence_pass(cfg, params, tokens, k_prefix,
v_prefix, carried, m, real_len) -> (hidden, k, v, carried)`` (one causal
pass behind an optional prefix) and binds the two prefill functions
below to it; the K/V side of every function is llama's as it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama


def put_layer(stack: jax.Array, new: jax.Array, i: int) -> jax.Array:
    """``new`` [B, ...] over the first B rows of layer ``i`` of
    ``stack`` [L, rows ≥ B, ...], as an update of that slice in place
    (an ``.at[i, :B].set`` is a scatter, which the chip's compiler
    turns into a pass over the whole stack: 1.25 GB a Mamba-2 layer a
    step at 64 rows)."""
    return jax.lax.dynamic_update_slice(
        stack, new[None].astype(stack.dtype), (i,) + (0,) * new.ndim)


def row_of(rows: dict, row) -> dict:
    """Row ``row`` (traced) of every per-row leaf, as a batch of one:
    [L, 1, ...]."""
    return {name: jax.lax.dynamic_slice_in_dim(leaf, row, 1, axis=1)
            for name, leaf in rows.items()}


def set_row(rows: dict, carried: dict, row) -> dict:
    return {name: jax.lax.dynamic_update_slice_in_dim(
        leaf, carried[name].astype(leaf.dtype), row, axis=1)
        for name, leaf in rows.items()}


def paged_prefill_kv(sequence_pass, cfg, params: dict, prompt: jax.Array):
    """The whole prompt [1, P] as a suffix behind nothing: (k, v
    [L_attn, P, KV, Hd], what the row carries after it) for
    `paged_insert_prefill`."""
    _, k, v, carried = sequence_pass(cfg, params, prompt)
    return k[:, 0], v[:, 0], carried


def paged_insert_prefill(cache: dict, k_all: jax.Array, v_all: jax.Array,
                         carried: dict, page_ids: jax.Array,
                         page_size: int, row) -> dict:
    """K and V into the row's pages as llama does, the carried leaves
    into row ``row``."""
    kv = llama.paged_insert_prefill(
        {"k": cache["k"], "v": cache["v"]}, k_all, v_all, page_ids,
        page_size)
    return {**cache, **kv, "rows": set_row(cache["rows"], carried, row)}


def paged_gather_prefix(cache: dict, page_ids: jax.Array, row) -> tuple:
    """What a suffix prefill reads of the row's earlier chunks: K and V
    of the pages ``page_ids`` token-major [L_attn, n·page, KV, Hd], and
    what row ``row`` carries (true where the prefix is this row's own
    work, which is the prefill lane's case: a radix match has no state,
    so for this cache the pool gives none)."""
    return (llama.paged_gather(cache["k"], page_ids),
            llama.paged_gather(cache["v"], page_ids),
            row_of(cache["rows"], row))


def paged_prefill_suffix_kv(sequence_pass, cfg, params: dict,
                            suffix: jax.Array, k_prefix: jax.Array,
                            v_prefix: jax.Array, carried: dict, m,
                            real_len):
    """The tail ``suffix`` [1, S] (``real_len`` of it real, the rest
    padding) of a prompt whose first ``m`` tokens exist
    (`paged_gather_prefix`'s three): (k, v [L_attn, S, KV, Hd], what the
    row carries after the last real position) for
    `paged_insert_suffix`. At ``m`` = 0 the row starts from zeros,
    whatever it held."""
    carried = jax.tree.map(lambda leaf: jnp.where(m > 0, leaf, 0), carried)
    _, k, v, carried = sequence_pass(
        cfg, params, suffix, k_prefix[:, None], v_prefix[:, None], carried,
        m, real_len)
    return k[:, 0], v[:, 0], carried


def paged_insert_suffix(cache: dict, k_suf: jax.Array, v_suf: jax.Array,
                        carried: dict, page_ids: jax.Array, start,
                        page_size: int, real_len, row) -> dict:
    kv = llama.paged_insert_suffix(
        {"k": cache["k"], "v": cache["v"]}, k_suf, v_suf, page_ids, start,
        page_size, real_len)
    return {**cache, **kv, "rows": set_row(cache["rows"], carried, row)}
