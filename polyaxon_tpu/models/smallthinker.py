"""SmallThinker-style decoder: full attention without positions and
rotary sliding-window attention in one period, every layer followed by
a block of softmax-routed ReGLU experts whose router reads the layer's
input.

The published ``smallthinker`` architecture (PowerInfer/SmallThinker-
21BA3B-Instruct ``config.json``). With ``rms(x, g) = x / sqrt(mean(x²) +
eps) · g``, layer ``l`` is::

    h  = rms(x, attn_norm_l)
    r  = h · W_r                       # router logits, before attention
    q, k, v = h · W_q, h · W_k, h · W_v
    if rope_layout[l]:  q, k = rope(q), rope(k)      # the whole head
    a  = causal softmax(q·k / √Hd), keys within the last `sliding_window`
         positions if window_layout[l]; GQA
    x  = x + a · W_o
    g  = rms(x, moe_norm_l)
    x  = x + Σ_{e in top K of softmax(r), renormalised}
             p_e · W_down,e(relu(W_gate,e g) ⊙ W_up,e g)

and after the last layer ``rms(x, final_norm)`` and the untied head.
The published layouts give a period of four, ``G W W W``: G is full
causal attention with no position encoding, W rotary attention over the
last 4,096 positions.

- *Attention* is llama's walks (``_qkv``, ``_attn_out``,
  ``paged_attn_step``, ``cached_attn_step``), told two things a layer
  from the static plan (`layer_plan`): whether the rotary embedding
  turns q and k, and the window.
- *Experts* go through ``models/moe.py``: a decode step through the
  one-hot buffers at the no-drop capacity (``dense_dispatch``), a
  sequence through sorted pairs and grouped matmuls
  (``sorted_dispatch``), both with ``relu`` on the gate stack and the
  routing (`routing`) handed in, because its input is not the block's.
  Every expert is held here.

**The cache.** Two page spaces (``serving/paged.py WindowedPagePool``,
which the engine builds for a family with `paged_window`): ``k``/``v``
hold the full layers' pages ``[L_full, P, KV, page, Hd]``, every
position of a row; ``window`` holds the window layers' ``[L_window,
P_w, KV, page, Hd]``, the last ``sliding_window`` positions of a row
and nothing older. A decode step is handed both block tables; in a
window layer ``ops/paged_attention.py``'s kernel starts at the window's
first page (a call named ``window_decode``). A prefill runs flash
attention (``window=`` on the W layers) over the prompt padded to whole
tiles and writes its K and V by whole pages (``llama.paged_write_pages``),
the window layers' only the pages the row holds. ``moe_expert_tokens``
``[L, E]`` counts the decode steps' (row, choice) pairs by expert.

**A shared prefix.** The radix tree shares the full space's pages
between rows (``serving/paged.py WindowedPagePool``); the window space
is nobody's but the row's. A prompt that matches ``m`` tokens (whole
pages) takes the suffix surface below: its program starts at ``start
= m - sliding_window x (window layers)``, a page boundary at or below
what is exact (``serving/paged.py window_suffix_start``), and computes
``[start, P)``. A window layer attends within that run alone and so
starts empty at ``start``; a full layer attends the cached pages for
every key below ``m`` and its own from ``m`` on, and writes nothing
below ``m``. A window layer's output is exact wherever its input is
over the ``sliding_window - 1`` positions before it and at it, a full
layer's wherever its input is, so the i-th window layer's input is
exact from ``start + (sliding_window - 1) · i`` and every window
layer's K and V over ``[m - sliding_window, m)``, and everything from
``m`` on, is what a whole-prompt pass gives. Nothing is kept for it
anywhere. (At the published sizes of this family ``6 x 4,096`` lies
past the context: no match could skip a position, so the pool matches
nothing there, as it did before it shared. ``models/exaone_moe.py``,
window 128, is the family it pays for.)

The engine's chunked prefill and speculation refuse a
``sliding_window`` as they do llama's. The walks over the plan and the
surfaces no page space touches are ``models/plan.py``'s, bound below to
this family's table (`FAMILY`); the paged surface takes the table as its
first argument, and ``models/exaone_moe.py`` binds it to its own.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama, moe, plan
from polyaxon_tpu.models.common import (
    Variables,
    put_layer,
    scaled_init,
    truncated_normal_init,
)
from polyaxon_tpu.ops.attention import (dot_product_attention,
                                        xla_attention_with_lse)

SEQ2SEQ = False
# A prefill's sequence is padded to a multiple of this: the flash
# kernel tiles a sequence into blocks of at least 128 and gives way to
# the einsum reference (a [S, S] score matrix a head) where it cannot.
PREFILL_TILE = 256
# The least block the flash kernel tiles a sequence into.
FLASH_TILE = 128


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151_936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1_500_000.0
    # Per layer, 1 where the rotary embedding turns q and k / where the
    # keys are those of the last `sliding_window` positions. None: the
    # published period of four, 0 1 1 1, for both.
    rope_layout: Optional[tuple] = None
    window_layout: Optional[tuple] = None
    sliding_window: int = 4096
    n_experts: int = 64
    experts_per_token: int = 6
    moe_ffn_dim: int = 768
    norm_eps: float = 1e-6
    max_seq_len: int = 16_384
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"  # the sequence passes': as LlamaConfig's
    paged_attention_impl: str = "auto"  # as LlamaConfig's
    loss_chunk: int = 256
    lm_logits_chunk: int = 4096

    def __post_init__(self):
        for name in ("rope_layout", "window_layout"):
            layout = getattr(self, name)
            if layout is None:
                layout = tuple(int(i % 4 != 0) for i in range(self.n_layers))
            if len(layout) != self.n_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.n_layers} layers")
            object.__setattr__(self, name, tuple(int(v) for v in layout))
        if len(set(self.window_layout)) != 2:
            raise ValueError(
                "a smallthinker model has window and full layers side by "
                "side; a window in every layer or in none is llama's")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")


CONFIGS: dict[str, SmallThinkerConfig] = {
    "smallthinker_21b_a3b": SmallThinkerConfig(),
    "smallthinker_tiny": SmallThinkerConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, sliding_window=32, n_experts=8, experts_per_token=2,
        moe_ffn_dim=32, max_seq_len=128),
}


def _kinds(cfg) -> tuple:
    return tuple("window" if windowed else "full"
                 for windowed in cfg.window_layout)


def layer_plan(cfg) -> tuple:
    """Per layer, in published order: (its attention's kind, ``full`` or
    ``window``; its index among that kind's layers, which is its layer
    of that kind's page pool; whether the rotary embedding turns its q
    and k). The parameters are stacked over every layer. Read off
    ``cfg.window_layout`` and ``cfg.rope_layout``, whichever family's
    config carries them."""
    return tuple((kind, i, bool(rotary)) for (kind, i), rotary
                 in zip(plan.indexed(_kinds(cfg)), cfg.rope_layout))


def kind_counts(cfg) -> dict:
    return plan.kind_counts(_kinds(cfg), ("full", "window"))


def _layer_window(cfg, kind: str) -> Optional[int]:
    return cfg.sliding_window if kind == "window" else None


def init(cfg: SmallThinkerConfig, rng: jax.Array) -> Variables:
    """Seeded float32 weights as the zoo draws them (truncated normal,
    1/sqrt(fan_in); the tables std 0.02), norm gains at ones."""
    keys = jax.random.split(rng, 10)
    L, D, H, KV, Hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    E, F = cfg.n_experts, cfg.moe_ffn_dim
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "attn": {
            "attn_norm": jnp.ones((L, D)),
            "router": scaled_init(keys[1], (L, D, E), fan_in=D),
            "wq": scaled_init(keys[2], (L, D, H * Hd), fan_in=D),
            "wk": scaled_init(keys[3], (L, D, KV * Hd), fan_in=D),
            "wv": scaled_init(keys[4], (L, D, KV * Hd), fan_in=D),
            "wo": scaled_init(keys[5], (L, H * Hd, D), fan_in=H * Hd),
        },
        "moe": {
            "moe_norm": jnp.ones((L, D)),
            "w_gate": scaled_init(keys[6], (L, E, D, F), fan_in=D),
            "w_up": scaled_init(keys[7], (L, E, D, F), fan_in=D),
            "w_down": scaled_init(keys[8], (L, E, F, D), fan_in=F),
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": truncated_normal_init(keys[9], (D, cfg.vocab_size)),
    }
    return {"params": params, "state": {}}


def logical_axes(cfg: SmallThinkerConfig) -> Variables:
    del cfg
    return {
        "params": {
            "embed": ("vocab", "embed"),
            "attn": {
                "attn_norm": ("layers", "embed"),
                "router": ("layers", "embed", None),
                "wq": ("layers", "embed", "heads"),
                "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"),
                "wo": ("layers", "heads", "embed"),
            },
            "moe": {
                "moe_norm": ("layers", "embed"),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
            },
            "final_norm": ("embed",),
            "lm_head": ("embed", "vocab"),
        },
        "state": {},
    }


# Leaves read at float32: the norm gains, and the router (its scores
# decide a top-k, so that matmul is float32 at full precision, as the
# other routed families'). The rest are read at ``cfg.dtype`` and a
# server holds them so (``common.served_params``).
READ_AT_FLOAT32 = frozenset({"attn_norm", "moe_norm", "final_norm", "router"})

# Leaves a server holds ``[.., N, D]``: the three projections of every
# layer, read by llama's `_qkv` (its table says why).
HELD_TRANSPOSED = llama.HELD_TRANSPOSED


# ------------------------------------------------------------ the layers
def routing(cfg: SmallThinkerConfig, layer: dict, h: jax.Array) -> tuple:
    """(chosen experts [T, K], their weights [T, K]) for the layer's
    normed input ``h`` [..., D], T its positions: the softmax over every
    expert's logit, the K largest, renormalised (``moe.route``). The
    scores decide a top-k, where a rounding flips an expert: the
    router's own matmul runs in float32 at full precision."""
    logits = jnp.dot(h.reshape(-1, h.shape[-1]).astype(jnp.float32),
                     layer["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_idx, top_w, _ = moe.route(cfg, logits)
    return top_idx, top_w


def expert_block(cfg: SmallThinkerConfig, stack: dict, i: int, x: jax.Array,
                 routed: tuple):
    """Layer ``i``'s expert residual over ``x`` [B, S, D] under the
    routing the layer's input gave (`routing`), its B·S tokens one
    dispatch group; nothing is dropped. A single position a row (a
    decode step) goes through the one-hot buffers, a sequence through
    sorted pairs. Returns (x after the residual, the choices' one-hot
    [B·S, K, E] or None)."""
    dt = cfg.dtype
    B, S, D = x.shape
    tokens = llama._norm(cfg, x, stack["moe_norm"][i]).reshape(B * S, D)
    top_idx, top_w = routed
    if S > 1:
        out, onehot = moe.sorted_dispatch(
            tokens, top_idx, top_w, stack["w_gate"], stack["w_up"],
            stack["w_down"], 0, dt, layer=i, gate_act=jax.nn.relu), None
    else:
        out, onehot = moe.dense_dispatch(
            tokens, top_idx, top_w, stack["w_gate"][i], stack["w_up"][i],
            stack["w_down"][i], B * S, dt, experts=moe.reglu_expert_ffn)
    return x + out.reshape(B, S, D), onehot


def _with_lse(cfg, q: jax.Array, k: jax.Array, v: jax.Array, causal: bool):
    """(attention [B, S, H, Hd], its rows' logsumexp [B, H, S]): flash
    attention where `dot_product_attention` would run it."""
    impl = cfg.attention_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "flash":
        from polyaxon_tpu.ops.flash import flash_attention_with_lse

        return flash_attention_with_lse(q, k, v, causal=causal)
    return xla_attention_with_lse(q, k, v, causal=causal)


def _attend_behind(cfg, q: jax.Array, k: jax.Array, v: jax.Array,
                   prefix: tuple, near: tuple) -> jax.Array:
    """A full layer's attention of a run at positions start..start+S−1
    behind a match of ``m`` tokens: ``prefix`` is the cached K and V
    [B, start, KV, Hd] of the positions below the run, ``near`` those
    [B, m − start, KV, Hd] of the positions the run computes again,
    which stand where the run's own K and V would: keys below ``m`` are
    the cache's, from ``m`` on the run's. Two attentions merged by their
    logsumexp, both of shapes the flash kernel takes: the run over
    itself under the causal mask, and over the prefix under none (every
    key there lies below every query). The prefix is cut at a multiple
    of `FLASH_TILE`; what is left of it leads the run's keys, behind as
    many query rows of zeros, which are cut off again."""
    S, R = q.shape[1], near[0].shape[1]
    lead = prefix[0].shape[1] % FLASH_TILE
    cut = prefix[0].shape[1] - lead
    pad = -(lead + S) % PREFILL_TILE

    def whole(ahead, own):  # [lead of the prefix; near; the run's own]
        return jnp.pad(jnp.concatenate([ahead[:, cut:], own], axis=1),
                       ((0, 0), (0, pad), (0, 0), (0, 0)))

    run, lse = _with_lse(
        cfg, jnp.pad(q, ((0, 0), (lead, pad), (0, 0), (0, 0))),
        whole(prefix[0], jnp.concatenate([near[0], k[:, R:]], axis=1)),
        whole(prefix[1], jnp.concatenate([near[1], v[:, R:]], axis=1)), True)
    run, lse = run[:, lead:lead + S], lse[:, :, lead:lead + S]
    if cut == 0:
        return run
    far, lse_far = _with_lse(cfg, q, prefix[0][:, :cut], prefix[1][:, :cut],
                             False)
    total = jnp.logaddexp(lse, lse_far)

    def share(part, part_lse):  # [B, S, H, Hd] by [B, H, S]
        weight = jnp.exp(part_lse - total).swapaxes(1, 2)[..., None]
        return part.astype(jnp.float32) * weight

    return (share(run, lse) + share(far, lse_far)).astype(q.dtype)


def _attention(kind: str, cfg, layer: dict, x: jax.Array, l: int,
               behind: plan.Behind):
    """Layer ``l``'s attention over a whole sequence at
    ``behind.positions``: flash attention, under the window in a window
    layer, which attends within the sequence alone. A full layer behind
    a match (``behind.carried["near"]``, `paged_prefill_suffix_kv`)
    attends the cached pages too (`_attend_behind`)."""
    h = llama._norm(cfg, x, layer["attn_norm"])
    q, k, v, gate = llama._qkv(cfg, layer, h, behind.positions,
                               bool(cfg.rope_layout[l]))
    near = behind.carried.get("near")
    if kind == "window" or near is None:
        attn = dot_product_attention(
            q, k, v, causal=True, impl=cfg.attention_impl,
            window=_layer_window(cfg, kind))
    else:
        i = layer_plan(cfg)[l][1]
        attn = _attend_behind(cfg, q, k, v, (behind.k[i], behind.v[i]),
                              (near["k"][i], near["v"][i]))
    return llama._attn_out(cfg, layer, x, attn, gate), {"k": k, "v": v}


def attention_mixers() -> dict:
    """The two kinds of attention layer as a `plan.Family`'s mixers."""
    return {kind: plan.Mixer("attn", functools.partial(_attention, kind),
                             None, kind + "_attention")
            for kind in ("full", "window")}


def _layers(cfg: SmallThinkerConfig) -> tuple:
    """Attention and an expert block in every layer, both stacked over
    every layer: a layer's index in either stack is its number."""
    return tuple((kind, l, "moe", l)
                 for l, (kind, _, _) in enumerate(layer_plan(cfg)))


FAMILY = plan.Family(
    name=__name__, configs=CONFIGS, init=init,
    logical_axes=logical_axes, layers=_layers,
    mixers=attention_mixers(),
    # The router reads the layer's normed input, before attention.
    ffns={"moe": plan.Ffn(
        lambda cfg, layer, x: routing(
            cfg, layer, llama._norm(cfg, x, layer["attn_norm"])),
        lambda cfg, params, l, x, routed: expert_block(
            cfg, params["moe"], l, x, routed))},
    init_rows=lambda cfg, rows: {})

# The engine's names (``serving/batching.py`` finds a surface by
# ``hasattr``), those no page space touches: `plan`'s functions over
# this family's table. The slot cache holds K/V [L, B, C, KV, Hd], every
# layer at the full length (slot == position; a window layer masks what
# lies behind its window and keeps it).
forward = functools.partial(plan.forward, FAMILY)
init_cache = cb_init_cache = functools.partial(plan.init_cache, FAMILY)
prefill = functools.partial(plan.prefill, FAMILY)
cb_prefill = functools.partial(plan.cb_prefill, prefill)
insert_cache_row = plan.insert_cache_row
cb_admission, cb_validate = llama.cb_admission, llama.cb_validate
apply = functools.partial(plan.apply, FAMILY)
model_def = functools.partial(plan.model_def, FAMILY)


def window_decode_step_ragged(family: plan.Family, cfg, params: dict,
                              cache: dict, tokens: jax.Array, pos: jax.Array):
    """One step with per-row positions ([B], −1 = idle) over the slot
    cache: llama's ``cached_attn_step``, a window layer's mask cut to
    its window."""
    C = cache["k"].shape[2]
    positions, slot, valid = llama.ragged_cache_coords(pos, C)
    behind = jnp.arange(C)[None, :] <= (positions - cfg.sliding_window)
    valid_window = valid & ~behind[:, None, None, :]
    kv = {"k": cache["k"], "v": cache["v"]}

    def attend(kind, l, layer, x):
        x, k, v = llama.cached_attn_step(
            cfg, layer, x, kv["k"][l], kv["v"][l], positions, slot,
            valid_window if kind == "window" else valid,
            bool(cfg.rope_layout[l]))
        kv["k"], kv["v"] = put_layer(kv["k"], k, l), put_layer(kv["v"], v, l)
        return x

    logits, _, _ = plan.decode(family, cfg, params, tokens, pos, attend,
                               {}, {})
    return logits, kv


decode_step_ragged = functools.partial(window_decode_step_ragged, FAMILY)
decode_step = functools.partial(plan.decode_step, decode_step_ragged)
generate = functools.partial(llama.generate_loop, prefill, decode_step)


# ------------------------------------------------------------ paged cache
def paged_window(cfg) -> int:
    """The window of this family's window layers: what tells the engine
    to build the pool with a window space (``serving/paged.py
    WindowedPagePool``) and to hand `paged_init_cache` its size and
    `decode_step_paged` both block tables."""
    return cfg.sliding_window


def window_page_spaces(cfg, n_pages: int, page_size: int,
                       window_pages: int) -> dict:
    """The two page spaces (module docstring), to which a family adds
    its counters."""
    n = kind_counts(cfg)

    def pool(layers, pages):
        return jnp.zeros((layers, pages, cfg.n_kv_heads, page_size,
                          cfg.head_dim), cfg.dtype)

    return {"k": pool(n["full"], n_pages), "v": pool(n["full"], n_pages),
            "window": {"k": pool(n["window"], window_pages),
                       "v": pool(n["window"], window_pages)}}


def paged_init_cache(cfg: SmallThinkerConfig, n_pages: int, page_size: int,
                     window_pages: int) -> dict:
    """The two page spaces and the decode steps' routed pairs by
    expert."""
    return {**window_page_spaces(cfg, n_pages, page_size, window_pages),
            "moe_expert_tokens": jnp.zeros((cfg.n_layers, cfg.n_experts),
                                           jnp.int32)}


def window_decode_step_paged(family: plan.Family, cfg, params: dict,
                             cache: dict, tokens: jax.Array, pos: jax.Array,
                             tables: tuple):
    """`window_decode_step_ragged` over the two page spaces: ``tables``
    is (the full space's block tables, the window space's), [B, maxp]
    each and indexed by the same logical page; a window layer writes and
    reads through the second, from its window's first page on."""
    page = cache["k"].shape[-2]
    window = cfg.sliding_window
    coords = {
        "full": (tables[0], *llama.paged_coords(pos, tables[0], page)),
        "window": (tables[1],
                   *llama.paged_coords(pos, tables[1], page, window))}
    pools = {"full": [cache["k"], cache["v"]],
             "window": [cache["window"]["k"], cache["window"]["v"]]}
    entries = layer_plan(cfg)

    def attend(kind, l, layer, x):
        table, positions, write_page, write_off, valid = coords[kind]
        _, i, rotary = entries[l]
        x, *pools[kind] = llama.paged_attn_step(
            cfg, layer, x, *pools[kind], i, positions, write_page,
            write_off, table, valid, window=_layer_window(cfg, kind),
            rotary=rotary)
        return x

    logits, _, counters = plan.decode(family, cfg, params, tokens, pos,
                                      attend, {}, plan.counters_of(cache))
    return logits, {
        "k": pools["full"][0], "v": pools["full"][1],
        "window": {"k": pools["window"][0], "v": pools["window"][1]},
        **counters}


decode_step_paged = functools.partial(window_decode_step_paged, FAMILY)


def _by_kind(cfg, ks: list, vs: list, n: Optional[int] = None) -> tuple:
    """A sequence pass's K and V of one row, every layer's [1, S, KV,
    Hd] in plan order, as the two spaces take them: (the full layers' k
    [L_full, n, KV, Hd], their v, the window layers' k, their v), the
    first ``n`` positions of each (None: all S)."""
    def of(kind, leaves):
        return jnp.stack([leaf[0, :n] for leaf, (k, _, _)
                          in zip(leaves, layer_plan(cfg)) if k == kind])

    return (of("full", ks), of("full", vs), of("window", ks),
            of("window", vs))


def window_paged_prefill_kv(family: plan.Family, cfg, params: dict,
                            prompt: jax.Array):
    """The prompt pass for one row [1, P], padded to whole flash tiles
    (causal: what lies behind the prompt changes nothing in it): (the
    full layers' k [L_full, P, KV, Hd], their v, the window layers' k
    [L_window, P, KV, Hd], their v, the window), the last a plain
    number for `paged_insert_prefill`, which is handed no config."""
    P = prompt.shape[1]
    padded = jnp.pad(prompt, ((0, 0), (0, -P % PREFILL_TILE)))
    _, ks, vs, _ = plan.sequence_layers(family, cfg, params, padded)
    return (*_by_kind(cfg, ks, vs, P), cfg.sliding_window)


paged_prefill_kv = functools.partial(window_paged_prefill_kv, FAMILY)


def paged_insert_prefill(cache: dict, k_full: jax.Array, v_full: jax.Array,
                         k_window: jax.Array, v_window: jax.Array,
                         window: int, page_ids: jax.Array,
                         page_size: int) -> dict:
    """A prefilled row's K and V into its pages, by whole pages.
    ``page_ids`` [2, maxp] are the row's two block-table rows
    (``WindowedPagePool.padded_row``). The full layers take every page
    of the prompt; the window layers the pages the row was admitted
    with, the last ``window // page_size + 1`` of the prompt and its
    first decode position (``WindowedPagePool._window_span``), and
    nothing of what lies before them."""
    P = k_full.shape[1]
    n = -(-P // page_size)                      # pages the prompt reaches
    held = -(-(P + 1) // page_size)             # with the first decode position
    first = max(0, held - (window // page_size + 1))
    tail = ((0, 0), (0, n * page_size - P), (0, 0), (0, 0))

    def put(pool, kv, ids, lo):
        kv = jnp.pad(kv[:, lo * page_size:], tail)
        return llama.paged_write_pages(pool, kv, jnp.maximum(ids[lo:n], 0))

    return {**cache,
            "k": put(cache["k"], k_full, page_ids[0], 0),
            "v": put(cache["v"], v_full, page_ids[0], 0),
            "window": {
                "k": put(cache["window"]["k"], k_window, page_ids[1], first),
                "v": put(cache["window"]["v"], v_window, page_ids[1], first)}}


# ------------------------------------------------- behind a shared prefix
# The matched pages' K and V, the full layers': the window space holds
# nothing of a prefix.
paged_gather_prefix = llama.paged_gather_prefix


def window_paged_prefill_suffix_kv(family: plan.Family, cfg, params: dict,
                                   suffix: jax.Array, k_prefix: jax.Array,
                                   v_prefix: jax.Array, start: int):
    """The run ``suffix`` [1, S] at positions start..start+S−1 of a
    prompt whose first ``m`` tokens are matched, ``m`` the length of
    ``k_prefix`` / ``v_prefix`` [L_full, m, KV, Hd] (whole pages:
    `paged_gather_prefix`), ``start`` a plain number (the pool's
    ``suffix_start``): K and V as `_by_kind` gives them, [.., S, KV,
    Hd], for `paged_insert_suffix`. What lies past the real tokens is
    padding, behind every real position."""
    def behind(kv, lo, hi):
        return kv[:, None, lo:hi]

    m = k_prefix.shape[1]
    near = {"k": behind(k_prefix, start, m), "v": behind(v_prefix, start, m)}
    _, ks, vs, _ = plan.sequence_layers(
        family, cfg, params, suffix, behind(k_prefix, 0, start),
        behind(v_prefix, 0, start), {"near": near}, start)
    return _by_kind(cfg, ks, vs)


paged_prefill_suffix_kv = functools.partial(window_paged_prefill_suffix_kv,
                                            FAMILY)


def paged_insert_suffix(cache: dict, k_full: jax.Array, v_full: jax.Array,
                        k_window: jax.Array, v_window: jax.Array,
                        page_ids: jax.Array, start: int, m: int,
                        real_len) -> dict:
    """A suffix run's K and V (`paged_prefill_suffix_kv`) into the row's
    pages ``page_ids`` [2, maxp], ``real_len`` of it real: the full
    layers' from position ``m`` on (below it the pages are shared, and
    what the run computed there again is dropped), the window layers'
    into whatever window pages the row holds (the others name the
    scratch page)."""
    def put(pool, kv, ids, first):
        return llama.paged_write_span(pool, kv, ids, start, real_len, first)

    return {**cache,
            "k": put(cache["k"], k_full, page_ids[0], m),
            "v": put(cache["v"], v_full, page_ids[0], m),
            "window": {
                "k": put(cache["window"]["k"], k_window, page_ids[1], None),
                "v": put(cache["window"]["v"], v_window, page_ids[1], None)}}
