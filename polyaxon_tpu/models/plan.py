"""A decoder whose layers follow a static plan, stated once: what
``models/lfm2.py``, ``nemotron_h.py``, ``qwen3_next.py``,
``smallthinker.py``, ``kimi_k2.py`` and ``exaone_moe.py`` share.

Such a family stacks its parameters by kind and walks its layers in
published order, in Python, while a program is traced: nothing of a
walk runs per token. A family module keeps what is its own (config,
draw, mixers, expert block, whatever only it has of the paged surface)
and describes itself to the walks below in one `Family` table; the
engine's names on the module are bindings of this file's functions to
that table (``forward = functools.partial(plan.forward, FAMILY)``).

**The walks.** `sequence_pass` takes a sequence behind an optional
prefix and carried state (``forward``, every prefill, the loss);
`decode` takes one position a row. In each layer a *mixer*
(attention, or a layer with a state a sequence carries) and then an
*FFN* (``dense``: llama's ``_mlp``; ``moe``: the family's expert block;
or none), either of which a layer may lack. Attention over a sequence
is the table's callable (`prefix_attention`: llama's
``suffix_attn_step``); over one position it is the ``attend(kind, i,
layer, x)`` closure of the cache in use, which the surface that owns
the cache passes in. A state kept a row, a page or not at all is the
mixer's own read and write of the ``state`` its surface hands the walk.

**The caches.** The dense slot cache holds K and V ``[L_attn, B, C, KV,
Hd]`` and beside them what `Family.init_rows` names, every leaf's axis 1
the slot. The paged one holds K and V pages as llama's and, for a
family whose rows carry a state, leaves *per row* under
``cache["rows"]`` (``[L, rows, ...]``, the family's
``paged_init_rows``): the engine tells every prefill program its row
(``serving/batching.py``), and a radix match has no state to resume
from, so the pool matches nothing for such a cache
(``serving/paged.py``). ``moe_expert_tokens`` / ``moe_pairs_elsewhere``
count, on the device, the decode steps' routed (row, choice) pairs.

**A latent a token** (``models/kimi_k2.py``). A family whose attention
caches one vector a token for all heads writes the cache surfaces
itself and takes the walks from here. Its mixer has no ``step`` (its
decode goes through ``attend``) and keeps ``{"latent": [B, S, W]}`` of a
sequence, which `sequence_layers` hands back by that name; a prefix
comes to it as ``carried["latent"]`` [L, B, Mpad, W], empty
(`Family.init_rows`) behind nothing. The paged cache is one leaf
``latent`` [L, P, 1, page, W]: pages of a single "KV head", so that
llama's page-wise writes (``paged_write_step``, ``paged_write_span``),
``paged_gather`` and ``serving/paged.py page_bytes`` take it as they
take K or V, and with no leaf a page or a row the radix tree matches
for it as for llama. The engine's names on such a module:
``paged_init_cache`` (the leaf and the counters), ``decode_step_paged``,
``paged_prefill_kv`` and ``paged_insert_prefill`` (one leaf where llama
has two), ``paged_gather_prefix``, ``paged_prefill_suffix_kv`` and
``paged_insert_suffix`` (the matched pages' latents, the tail behind
them), and the slot cache's ``init_cache`` / ``prefill`` /
``decode_step_ragged`` over ``latent`` [L, B, C, W].

**Window layers beside full ones** (``models/smallthinker.py``,
``models/exaone_moe.py``). A family some of whose attention layers see
the last ``sliding_window`` positions only declares ``paged_window`` and
is given two page spaces (``serving/paged.py WindowedPagePool``): ``k``
/ ``v`` [L_full, P, KV, page, Hd] and ``window`` {``k``, ``v``}
[L_window, P_w, KV, page, Hd], a row's two block-table rows ``[2,
maxp]`` wherever llama's surface takes one. Its two mixers take the
walks from here; the paged surface is written once, in
``models/smallthinker.py``, over a `Family` table it is handed:
``paged_init_cache(cfg, n_pages, page_size, window_pages)``,
``decode_step_paged`` (``tables`` a pair), ``paged_prefill_kv`` /
``paged_insert_prefill`` (by kind; the window layers' the pages the row
holds), and behind a shared prefix ``paged_gather_prefix`` (the full
layers' matched pages: the radix tree shares that space alone),
``paged_prefill_suffix_kv(cfg, params, suffix, k_prefix, v_prefix,
start)`` and ``paged_insert_suffix(cache, *kv, page_ids, start, m,
real_len)``: the run starts at ``start``, below the match of ``m``
tokens, so that the window layers, which start empty there, come out
exact by ``m``; the full layers read the cached pages below ``m``
(``carried["near"]`` hands a full layer those of the stretch the run
computes again) and write from ``m`` on. ``start`` and ``m`` are plain
numbers: such a pool matches whole pages.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama
from polyaxon_tpu.models.common import (
    Batch,
    ModelDef,
    Variables,
    _embed_rows,
    chunked_lm_loss,
    lm_logits,
    put_layer,
    shift_right,
)


# --------------------------------------------------------------- the table
class Mixer(NamedTuple):
    """One kind of mixer. ``stack``: the key of ``params`` that holds
    its layers' weights. ``sequence(cfg, layer, x [B, S, D], i, behind)
    -> (x, kept)``: the layer over a sequence behind `Behind`; ``kept``
    is ``{"k", "v"}`` of an attention layer over per-head K and V, by
    any other name what else a layer leaves behind: the leaves the
    sequence carries on, or what an attention without per-head K and V
    caches a token (a latent; such a layer reads its prefix from
    ``behind.carried``). ``step(cfg, layer, x [B, 1, D], i, state,
    started) -> (x, state)``: one position a row over the cache's
    ``state`` (rows not ``started`` begin from zeros), or None for
    attention, which goes through the cache's ``attend``. ``scope``:
    the `jax.named_scope` of its calls, or None."""
    stack: str
    sequence: Callable
    step: Optional[Callable]
    scope: Optional[str]


class Ffn(NamedTuple):
    """One kind of FFN. ``block(cfg, params, i, x, pre) -> (x, the
    routed choices' one-hot [T, K, E] or None)``: the residual block of
    layer ``i`` of that kind's stack. ``before(cfg, layer, x) -> pre``:
    what the block reads of the layer's input and of its mixer's weights
    before the mixer runs (a router there), or None."""
    before: Optional[Callable]
    block: Callable


class Family(NamedTuple):
    """What a family with a layer plan is to this file. ``name``: its
    module's ``__name__``. ``layers(cfg)``: per layer (mixer kind or
    None, its index in that kind's stack, FFN kind or None, its index
    in that kind's stack); the expert block's FFN kind is named ``moe``. ``init_rows(cfg, rows)``: what ``rows``
    sequences carry through the mixers, zeroed (``{}`` for none), leaves
    ``[L, rows, ...]`` under names no two mixers share."""
    name: str
    configs: dict
    init: Callable
    logical_axes: Callable
    layers: Callable
    mixers: dict
    ffns: dict
    init_rows: Callable


class Behind(NamedTuple):
    """What a sequence ``[B, S]`` at positions m..m+S−1 stands behind:
    the prefix's K and V ``[L_attn, B, Mpad, KV, Hd]`` (columns at or
    past ``m`` masked by ``valid``), what the mixers carry after
    position m−1, and where the padding starts (``real_len``, None:
    nowhere)."""
    k: jax.Array
    v: jax.Array
    positions: jax.Array
    valid: jax.Array
    carried: dict
    real_len: Optional[jax.Array]


@functools.lru_cache(maxsize=None)
def indexed(kinds: tuple) -> tuple:
    """Per layer, in order: (its kind, its index among that kind's
    layers, which is its place in that kind's stack)."""
    seen: dict = {}
    out = []
    for kind in kinds:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return tuple(out)


def kind_counts(kinds: tuple, names: tuple) -> dict:
    return {name: kinds.count(name) for name in names}


def _at(stack: dict, i: int) -> dict:
    """Layer `i` of one kind's stacked parameters."""
    return {name: leaf[i] for name, leaf in stack.items()}


def prefix_attention(cfg, layer: dict, x: jax.Array, i: int, behind: Behind):
    x, k, v = llama.suffix_attn_step(cfg, layer, x, behind.k[i], behind.v[i],
                                     behind.positions, behind.valid)
    return x, {"k": k, "v": v}


# Attention as llama has it (the QK-norm, the output gate and the
# partial rotary embedding where the layer or the config carries them),
# weights under ``params["attn"]``; the dense FFN under
# ``params["dense"]``.
ATTENTION = Mixer("attn", prefix_attention, None, None)
DENSE = Ffn(None, lambda cfg, params, i, x, _: (
    llama._mlp(cfg, x, _at(params["dense"], i)), None))


def _scope(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _attention_layers(family: Family, cfg) -> int:
    return sum(kind is not None and family.mixers[kind].step is None
               for kind, _, _, _ in family.layers(cfg))


def _before(family: Family, cfg, ffn, layer: dict, x: jax.Array):
    if ffn is None or family.ffns[ffn].before is None:
        return None
    return family.ffns[ffn].before(cfg, layer, x)


# --------------------------------------------------------------- the walks
def sequence_pass(family: Family, cfg, params: dict, tokens: jax.Array,
                  *prefix):
    """`sequence_layers` with what it returns stacked: (hidden before
    the final norm [B, S, D], the attention layers' k [L_attn, B, S,
    KV, Hd] in plan order, their v, the mixers' ``kept`` leaves [L,
    B, ...] each)."""
    x, ks, vs, kept = sequence_layers(family, cfg, params, tokens, *prefix)
    return x, _stacked(ks), _stacked(vs), {
        name: jnp.stack(leaves) for name, leaves in kept.items()}


def _stacked(leaves: list):
    """None for a family no layer of which keeps per-head K and V."""
    return jnp.stack(leaves) if leaves else None


def sequence_layers(family: Family, cfg, params: dict, tokens: jax.Array,
                    k_prefix: Optional[jax.Array] = None,
                    v_prefix: Optional[jax.Array] = None,
                    carried: Optional[dict] = None, m=0, real_len=None):
    """One causal pass over ``tokens`` [B, S] behind a prefix that
    already exists (`Behind`). Without one (all None, m = 0) it is the
    whole-sequence forward. Returns (hidden before the final norm, every
    attention layer's k [B, S, KV, Hd] in plan order, their v, the
    mixers' ``kept`` leaves by name), the last three as lists."""
    dt = cfg.dtype
    B, S = tokens.shape
    if k_prefix is None:
        # Heads of per-head K and V; a family that keeps none (a latent
        # a token, `Mixer`) has neither number.
        shape = (_attention_layers(family, cfg), B, 0,
                 getattr(cfg, "n_kv_heads", 1), getattr(cfg, "head_dim", 1))
        k_prefix = v_prefix = jnp.zeros(shape, dt)
    if carried is None:
        carried = family.init_rows(cfg, B)
    positions = jnp.broadcast_to(
        m + jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    valid = llama._suffix_mask(S, k_prefix.shape[2], m)
    behind = Behind(k_prefix, v_prefix, positions, valid, carried, real_len)
    x = _embed_rows(params["embed"], tokens, dt)
    ks, vs, kept = [], [], {}
    for kind, i, ffn, fi in family.layers(cfg):
        pre = None
        if kind is not None:
            mixer = family.mixers[kind]
            layer = _at(params[mixer.stack], i)
            pre = _before(family, cfg, ffn, layer, x)
            with _scope(mixer.scope):
                x, out = mixer.sequence(cfg, layer, x, i, behind)
            for name, leaf in out.items():
                (ks if name == "k" else vs if name == "v"
                 else kept.setdefault(name, [])).append(leaf)
        if ffn is not None:
            x, _ = family.ffns[ffn].block(cfg, params, fi, x, pre)
    return x, ks, vs, kept


COUNTERS = ("moe_expert_tokens", "moe_pairs_elsewhere")


def _count(cfg, counters: dict, i: int, onehot: jax.Array, live: jax.Array):
    """Live rows' routed pairs of expert layer ``i`` added by expert
    and, where this chip holds a share of the experts, those routed
    elsewhere."""
    held = jnp.einsum("tke,t->e", onehot.astype(jnp.int32), live)
    out = {"moe_expert_tokens": counters["moe_expert_tokens"].at[i].add(held)}
    if "moe_pairs_elsewhere" in counters:
        out["moe_pairs_elsewhere"] = counters["moe_pairs_elsewhere"].at[i].add(
            cfg.experts_per_token * jnp.sum(live) - jnp.sum(held))
    return out


def _head(cfg, params: dict, x: jax.Array) -> jax.Array:
    """Final norm and the head, hidden [..., D] → fp32 logits: the
    tree's ``lm_head``, or the embedding table transposed (tied) where
    it has none."""
    x = llama._norm(cfg, x, params["final_norm"])
    if "lm_head" in params:
        return lm_logits(x, params["lm_head"], cfg.dtype,
                         chunk=cfg.lm_logits_chunk)
    return lm_logits(x, params["embed"], cfg.dtype, transpose=True,
                     chunk=cfg.lm_logits_chunk)


def decode(family: Family, cfg, params: dict, tokens: jax.Array,
           pos: jax.Array, attend, state: dict, counters: dict):
    """One position a row (``tokens`` [B]; ``pos`` [B], −1 = idle)
    through every layer. ``attend(kind, i, layer, x)`` is the attention
    layer over the cache in use; ``state`` holds the leaves the other
    mixers read and write where they lie (a row at position 0 starts
    from zeros, an idle row's is garbage the next admission's prefill
    replaces). Live rows' routed pairs are added to ``counters``
    (`COUNTERS`, those the cache has). Returns (logits [B, V] fp32,
    state, counters)."""
    x = _embed_rows(params["embed"], tokens, cfg.dtype)[:, None, :]
    started = pos > 0 if state else None
    live = (pos >= 0).astype(jnp.int32)
    for kind, i, ffn, fi in family.layers(cfg):
        pre = None
        if kind is not None:
            mixer = family.mixers[kind]
            layer = _at(params[mixer.stack], i)
            pre = _before(family, cfg, ffn, layer, x)
            if mixer.step is None:
                with _scope(mixer.scope):
                    x = attend(kind, i, layer, x)
            else:
                x, state = mixer.step(cfg, layer, x, i, state, started)
        if ffn is not None:
            x, onehot = family.ffns[ffn].block(cfg, params, fi, x, pre)
            if counters and onehot is not None:
                counters = _count(cfg, counters, fi, onehot, live)
    return _head(cfg, params, x[:, 0]), state, counters


def forward(family: Family, cfg, params: dict,
            tokens: jax.Array) -> jax.Array:
    """Token ids [B, S] → logits [B, S, vocab] fp32."""
    x, _, _, _ = sequence_pass(family, cfg, params, tokens)
    return _head(cfg, params, x)


# ------------------------------------------------------- dense slot cache
def init_cache(family: Family, cfg, batch: int, max_len: int) -> dict:
    """The slot cache: K/V [L_attn, B, C, KV, Hd] and what each slot
    carries through the other mixers (`Family.init_rows`)."""
    kv = (_attention_layers(family, cfg), batch, max_len, cfg.n_kv_heads,
          cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **family.init_rows(cfg, batch)}


def prefill(family: Family, cfg, params: dict, prompt: jax.Array,
            max_len: int):
    """One pass over the prompt [B, P]: (last-position logits [B, V]
    fp32, the slot cache holding it)."""
    P = prompt.shape[1]
    if P > max_len:
        raise ValueError(f"prompt length {P} exceeds cache length {max_len}")
    x, k, v, carried = sequence_pass(family, cfg, params, prompt)
    pad = ((0, 0), (0, 0), (0, max_len - P), (0, 0), (0, 0))
    cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad), **carried}
    return _head(cfg, params, x[:, -1]), cache


def decode_step_ragged(family: Family, cfg, params: dict, cache: dict,
                       tokens: jax.Array, pos: jax.Array):
    """One step with per-row positions ([B], −1 = idle) over the slot
    cache: llama's ``cached_attn_step`` in the attention layers, the
    slot's own carried state in the others."""
    positions, slot, valid = llama.ragged_cache_coords(pos,
                                                       cache["k"].shape[2])
    kv = {"k": cache["k"], "v": cache["v"]}

    def attend(_, i, layer, x):
        x, k, v = llama.cached_attn_step(cfg, layer, x, kv["k"][i],
                                         kv["v"][i], positions, slot, valid)
        kv["k"], kv["v"] = put_layer(kv["k"], k, i), put_layer(kv["v"], v, i)
        return x

    state = {name: leaf for name, leaf in cache.items() if name not in kv}
    logits, state, _ = decode(family, cfg, params, tokens, pos, attend,
                              state, {})
    return logits, {**kv, **state}


def decode_step(ragged, cfg, params: dict, cache: dict, tokens: jax.Array,
                pos: jax.Array):
    """Scalar-position decode over the family's ``decode_step_ragged``:
    every row at the same position."""
    return ragged(cfg, params, cache, tokens, jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32), tokens.shape[:1]))


def cb_prefill(prefill_fn, cfg, params: dict, prompt: jax.Array,
               max_len: int) -> dict:
    return prefill_fn(cfg, params, prompt, max_len)[1]


def insert_cache_row(cache: dict, row: dict, b) -> dict:
    """A prefilled row into slot `b`: every leaf's axis 1 is the slot."""
    return {name: jax.lax.dynamic_update_slice(
        leaf, row[name], (0, b) + (0,) * (leaf.ndim - 2))
        for name, leaf in cache.items()}


# ------------------------------------------------------------ paged cache
def paged_init_cache(family: Family, cfg, n_pages: int,
                     page_size: int) -> dict:
    """The paged part of the cache: K/V pages of the attention layers
    and the decode steps' routed pairs, by expert held here and, where
    the config names a share of the experts (``held``), by layer those
    routed elsewhere. The engine adds ``paged_init_rows`` under
    ``rows``."""
    kv = (_attention_layers(family, cfg), n_pages, cfg.n_kv_heads, page_size,
          cfg.head_dim)
    n_moe = sum(ffn == "moe" for _, _, ffn, _ in family.layers(cfg))
    held = getattr(cfg, "held", None)
    cache = {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
             "moe_expert_tokens": jnp.zeros(
                 (n_moe, held[1] if held else cfg.n_experts), jnp.int32)}
    if held:
        cache["moe_pairs_elsewhere"] = jnp.zeros((n_moe,), jnp.int32)
    return cache


def paged_attend(cfg, cache: dict, tables: jax.Array, coords: tuple):
    """(the K and V pools, which it updates; ``attend`` over them):
    llama's ``paged_attn_step`` at ``coords`` (``llama.paged_coords``)
    through the block tables."""
    positions, write_page, write_off, valid = coords
    kv = {"k": cache["k"], "v": cache["v"]}

    def attend(_, i, layer, x):
        x, kv["k"], kv["v"] = llama.paged_attn_step(
            cfg, layer, x, kv["k"], kv["v"], i, positions, write_page,
            write_off, tables, valid)
        return x

    return kv, attend


def counters_of(cache: dict) -> dict:
    return {name: cache[name] for name in COUNTERS if name in cache}


def decode_step_paged(family: Family, cfg, params: dict, cache: dict,
                      tokens: jax.Array, pos: jax.Array, tables: jax.Array):
    """`decode_step_ragged` over the paged pool: row b's K and V in its
    pages, its state in row b of ``cache["rows"]``, read and written in
    place."""
    coords = llama.paged_coords(pos, tables, cache["k"].shape[-2])
    kv, attend = paged_attend(cfg, cache, tables, coords)
    logits, rows, counters = decode(family, cfg, params, tokens, pos, attend,
                                    cache["rows"], counters_of(cache))
    return logits, {**kv, **counters, "rows": rows}


def row_of(rows: dict, row) -> dict:
    """Row ``row`` (traced) of every per-row leaf, as a batch of one:
    [L, 1, ...]."""
    return {name: jax.lax.dynamic_slice_in_dim(leaf, row, 1, axis=1)
            for name, leaf in rows.items()}


def set_row(rows: dict, carried: dict, row) -> dict:
    return {name: jax.lax.dynamic_update_slice_in_dim(
        leaf, carried[name].astype(leaf.dtype), row, axis=1)
        for name, leaf in rows.items()}


def paged_prefill_kv(family: Family, cfg, params: dict, prompt: jax.Array):
    """The whole prompt [1, P] as a suffix behind nothing: (k, v
    [L_attn, P, KV, Hd], what the row carries after it) for
    `paged_insert_prefill`."""
    _, k, v, carried = sequence_pass(family, cfg, params, prompt)
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


def paged_prefill_suffix_kv(family: Family, cfg, params: dict,
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
        family, cfg, params, suffix, k_prefix[:, None], v_prefix[:, None],
        carried, m, real_len)
    return k[:, 0], v[:, 0], carried


def paged_insert_suffix(cache: dict, k_suf: jax.Array, v_suf: jax.Array,
                        carried: dict, page_ids: jax.Array, start,
                        page_size: int, real_len, row) -> dict:
    kv = llama.paged_insert_suffix(
        {"k": cache["k"], "v": cache["v"]}, k_suf, v_suf, page_ids, start,
        page_size, real_len)
    return {**cache, **kv, "rows": set_row(cache["rows"], carried, row)}


# --------------------------------------------------------------- training
def apply(family: Family, cfg, variables: Variables, batch: Batch,
          train: bool = True, rng: Optional[jax.Array] = None):
    """Next-token loss (chunked head). No auxiliary loss: where a
    published model balances its experts it does so through a selection
    bias, which this objective leaves alone."""
    tokens = batch["tokens"]
    if batch.get("segments") is not None:
        raise ValueError(f"{family.name.rpartition('.')[2]} models do not "
                         "support packed sequences (segments)")
    params = variables["params"]
    x, _, _, _ = sequence_pass(family, cfg, params, shift_right(tokens))
    x = llama._norm(cfg, x, params["final_norm"])
    head = (params["lm_head"] if "lm_head" in params
            else params["embed"].T)
    loss, acc = chunked_lm_loss(x, head.astype(cfg.dtype), tokens,
                                batch.get("mask"), chunk=cfg.loss_chunk)
    return loss, {"loss": loss, "accuracy": acc}, variables["state"]


def model_def(family: Family, name: str, **overrides) -> ModelDef:
    cfg = dataclasses.replace(family.configs[name], **overrides)
    return ModelDef(
        name=name,
        init=functools.partial(family.init, cfg),
        apply=functools.partial(apply, family, cfg),
        logical_axes=functools.partial(family.logical_axes, cfg),
        unit="tokens",
    )
