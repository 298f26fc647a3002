"""LFM2-style hybrid decoder: gated short convolutions beside GQA
attention, dense and sigmoid-routed expert feed-forwards in one stack.

The published ``lfm2_moe`` architecture (LiquidAI/LFM2-8B-A1B
``config.json``). With ``rms(x, g) = x / sqrt(mean(x²) + eps) · g``,
layer ``l`` is ``h = x + Op_l(rms(x, operator_norm_l))``, ``y = h +
FFN_l(rms(h, ffn_norm_l))``:

- ``Op_l`` is attention where ``layer_types[l] == "full_attention"``
  (q/k/v without bias, RMSNorm over each head's dims on q and on k
  before rotate-half RoPE, causal GQA softmax), else the short
  convolution: ``[B, C, X] = split3(u · W_in)``, ``z = B ⊙ X``, ``c_t =
  Σ_j w[:, j] ⊙ z_{t-(K-1)+j}`` (depthwise, causal, kernel
  ``conv_kernel``, zeros before the sequence, no bias), ``out = (C ⊙ c)
  · W_out``. What one sequence carries between tokens is the last
  ``conv_kernel − 1`` vectors ``z`` a convolution layer.
- ``FFN_l`` is a dense SwiGLU for ``l < n_dense_layers``, else the
  expert block: ``s = sigmoid(h · W_r)``; the experts are chosen by
  ``top_k(s + expert_bias)`` but weighted by ``s`` alone (``models/
  moe.py route``); no capacity, no token dropped.
- The head is the embedding table transposed (tied).

**Layers of different kinds.** Parameters are stacked by kind
(``attn``, ``conv``, ``dense``, ``moe``: each a dict of ``[L_kind,
...]`` leaves) and a static Python plan (`layer_plan`) walks the layers
in published order. The attention bodies are llama's
(``cached_attn_step``, ``paged_attn_step``, ``suffix_attn_step``: they
apply the QK-norm when a layer carries ``q_norm``/``k_norm``), the
dense FFN is llama's ``_mlp``, routing and dispatch are moe's.

**The hybrid cache.** ``k``/``v`` hold the attention layers' pages
``[L_attn, P, KV, page, Hd]``; ``conv`` holds ``[L_conv, P, K−1, D]``:
a page's entry is the convolution state after the last position
written in that page, so hand-off, eviction and radix sharing stay
block-table bookkeeping. Decode at position ``t`` reads the state from
the page of ``t − 1`` and writes it to the page of ``t``; a prefill
writes one snapshot a page it touches. A radix match that ends inside
a page has no true state: ``serving/paged.py`` rounds matches down to
whole pages for a cache with such a leaf. ``moe_expert_tokens``
``[L_moe, E]`` counts, on the device, the (row, choice) pairs the
decode steps routed to each expert.

The walks over the plan and the surfaces that do not touch the state's
pages are ``models/plan.py``'s, bound below to this family's table
(`FAMILY`). Speculation and chunked dense prefill need
``decode_chunk``, which this family does not have (the state has no
rollback), and the engine refuses them by that.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama, moe, plan
from polyaxon_tpu.models.common import (
    Variables,
    _w,
    rms_norm,
    scaled_init,
    truncated_normal_init,
)

SEQ2SEQ = False

_PERIOD = ("full_attention", "conv", "conv", "conv")
_PUBLISHED_LAYER_TYPES = (
    ("conv", "conv") + _PERIOD * 4
    + ("full_attention", "conv", "conv", "full_attention", "conv", "conv"))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65_536
    dim: int = 2048
    n_layers: int = 24
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 7168  # the leading dense layers' SwiGLU
    moe_ffn_dim: int = 1792  # per expert
    n_experts: int = 32
    experts_per_token: int = 4
    n_dense_layers: int = 2
    # "conv" | "full_attention" per layer, in published order.
    layer_types: tuple = _PUBLISHED_LAYER_TYPES
    conv_kernel: int = 3  # the published `conv_L_cache`
    # The router (models/moe.py `route`): sigmoid scores, selection by
    # score + expert_bias, weights from the score alone.
    router_score: str = "sigmoid"
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 128_000
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    paged_attention_impl: str = "auto"  # as LlamaConfig's
    loss_chunk: int = 256
    lm_logits_chunk: int = 4096

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers lies outside the stack")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


CONFIGS: dict[str, Lfm2Config] = {
    "lfm2_8b_a1b": Lfm2Config(),
    "lfm2_tiny": Lfm2Config(
        vocab_size=256, dim=64, n_layers=5, n_heads=4, n_kv_heads=2,
        ffn_dim=128, moe_ffn_dim=32, n_experts=8, experts_per_token=2,
        n_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "conv",
                     "full_attention"),
        max_seq_len=128, rope_theta=10_000.0),
}


def _kinds(cfg: Lfm2Config) -> tuple:
    """(every layer's operator kind, every layer's FFN kind)."""
    return (tuple("attn" if kind == "full_attention" else "conv"
                  for kind in cfg.layer_types),
            tuple("dense" if l < cfg.n_dense_layers else "moe"
                  for l in range(cfg.n_layers)))


def layer_plan(cfg: Lfm2Config) -> tuple:
    """Per layer, in published order: (operator kind, its index in that
    kind's stack, FFN kind, its index in that kind's stack)."""
    ops, ffns = _kinds(cfg)
    return tuple(op + ffn for op, ffn
                 in zip(plan.indexed(ops), plan.indexed(ffns)))


def kind_counts(cfg: Lfm2Config) -> dict:
    ops, ffns = _kinds(cfg)
    return {**plan.kind_counts(ops, ("attn", "conv")),
            **plan.kind_counts(ffns, ("dense", "moe"))}


def init(cfg: Lfm2Config, rng: jax.Array) -> Variables:
    """Seeded float32 weights, stacked by kind. The published
    ``expert_bias`` is a learned buffer; here it is drawn non-zero
    (truncated normal, std 0.02) so that selection and weighting can be
    told apart."""
    keys = jax.random.split(rng, 16)
    n = kind_counts(cfg)
    D, F, Fm, E = cfg.dim, cfg.ffn_dim, cfg.moe_ffn_dim, cfg.n_experts
    H, KV, Hd, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.conv_kernel
    La, Lc, Ld, Lm = n["attn"], n["conv"], n["dense"], n["moe"]
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "attn": {
            "attn_norm": jnp.ones((La, D)),
            "wq": scaled_init(keys[1], (La, D, H * Hd), fan_in=D),
            "wk": scaled_init(keys[2], (La, D, KV * Hd), fan_in=D),
            "wv": scaled_init(keys[3], (La, D, KV * Hd), fan_in=D),
            "wo": scaled_init(keys[4], (La, H * Hd, D), fan_in=H * Hd),
            "q_norm": jnp.ones((La, Hd)),
            "k_norm": jnp.ones((La, Hd)),
        },
        "conv": {
            "conv_norm": jnp.ones((Lc, D)),
            "w_in": scaled_init(keys[5], (Lc, D, 3 * D), fan_in=D),
            "w_conv": scaled_init(keys[6], (Lc, D, K), fan_in=K),
            "w_out": scaled_init(keys[7], (Lc, D, D), fan_in=D),
        },
        "dense": {
            "mlp_norm": jnp.ones((Ld, D)),
            "w_gate": scaled_init(keys[8], (Ld, D, F), fan_in=D),
            "w_up": scaled_init(keys[9], (Ld, D, F), fan_in=D),
            "w_down": scaled_init(keys[10], (Ld, F, D), fan_in=F),
        },
        "moe": {
            "moe_norm": jnp.ones((Lm, D)),
            "router": scaled_init(keys[11], (Lm, D, E), fan_in=D),
            "expert_bias": truncated_normal_init(keys[12], (Lm, E)),
            "w_gate": scaled_init(keys[13], (Lm, E, D, Fm), fan_in=D),
            "w_up": scaled_init(keys[14], (Lm, E, D, Fm), fan_in=D),
            "w_down": scaled_init(keys[15], (Lm, E, Fm, D), fan_in=Fm),
        },
        "final_norm": jnp.ones((D,)),
    }
    return {"params": params, "state": {}}


def logical_axes(cfg: Lfm2Config) -> Variables:
    del cfg
    return {
        "params": {
            "embed": ("vocab", "embed"),
            "attn": {
                "attn_norm": ("layers", "embed"),
                "wq": ("layers", "embed", "heads"),
                "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"),
                "wo": ("layers", "heads", "embed"),
                "q_norm": ("layers", None),
                "k_norm": ("layers", None),
            },
            "conv": {
                "conv_norm": ("layers", "embed"),
                "w_in": ("layers", "embed", "mlp"),
                "w_conv": ("layers", "embed", None),
                "w_out": ("layers", "mlp", "embed"),
            },
            "dense": {
                "mlp_norm": ("layers", "embed"),
                "w_gate": ("layers", "embed", "mlp"),
                "w_up": ("layers", "embed", "mlp"),
                "w_down": ("layers", "mlp", "embed"),
            },
            "moe": {
                "moe_norm": ("layers", "embed"),
                "router": ("layers", "embed", "expert"),
                "expert_bias": ("layers", "expert"),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
            },
            "final_norm": ("embed",),
        },
        "state": {},
    }


# Leaves read at float32: every norm gain, the convolution's taps
# (``conv_op``), and the router with its bias (``expert_ffn``: the
# scores decide a top-k, so its matmul is float32 at full precision,
# unlike ``models/moe.py``'s, which reads its router through ``_w``).
# The rest are read at ``cfg.dtype`` and a server holds them so
# (``common.served_params``).
READ_AT_FLOAT32 = frozenset(
    {"attn_norm", "q_norm", "k_norm", "conv_norm", "mlp_norm", "moe_norm",
     "final_norm", "w_conv", "router", "expert_bias"})

# Leaves a server holds ``[.., N, D]``: the attention layers' three
# projections, read by llama's `_qkv` (its table says why).
HELD_TRANSPOSED = llama.HELD_TRANSPOSED


# ------------------------------------------------------------ the layers
def conv_op(cfg: Lfm2Config, layer: dict, x: jax.Array, state: jax.Array):
    """The gated short convolution over ``x`` [B, S, D] behind the
    carried ``state`` [B, K−1, D] (the ``z`` of the K−1 positions before
    the first of ``x``; zeros at the start of a sequence). Returns (x
    after the residual, ``z`` with the state in front [B, K−1+S, D]:
    entry ``K−1+t`` is position t's). A decode step is S = 1."""
    dt = cfg.dtype
    S, K = x.shape[1], cfg.conv_kernel
    h = rms_norm(x, layer["conv_norm"], cfg.norm_eps)
    gate_b, gate_c, inner = jnp.split(h @ _w(layer["w_in"], dt), 3, axis=-1)
    z = jnp.concatenate([state.astype(dt), gate_b * inner], axis=1)
    w = layer["w_conv"].astype(jnp.float32)  # [D, K]
    # K shifted adds, summed in float32 (elementwise: nothing to save).
    conv = sum(w[:, j] * z[:, j:j + S].astype(jnp.float32) for j in range(K))
    return x + (gate_c * conv.astype(dt)) @ _w(layer["w_out"], dt), z


def expert_ffn(cfg: Lfm2Config, layer: dict, x: jax.Array):
    """The expert block's residual over ``x`` [B, S, D], its B·S tokens
    one dispatch group at the no-drop capacity (the published model has
    no capacity). Returns (x after the residual, the choices' one-hot
    [B·S, K, E])."""
    B, S, D = x.shape
    dt = cfg.dtype
    tokens = rms_norm(x, layer["moe_norm"], cfg.norm_eps).reshape(B * S, D)
    # The scores decide a top-k, where a rounding flips an expert: the
    # router's own matmul (D x E, nothing beside the experts') runs in
    # float32 at full precision.
    logits = jnp.dot(tokens.astype(jnp.float32),
                     layer["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_idx, top_w, _ = moe.route(
        cfg, logits, layer["expert_bias"] if cfg.use_expert_bias else None)
    out, onehot = moe.dense_dispatch(
        tokens, top_idx, top_w, layer["w_gate"], layer["w_up"],
        layer["w_down"], B * S, dt)
    return x + out.reshape(B, S, D), onehot


def init_rows(cfg: Lfm2Config, rows: int) -> dict:
    """What ``rows`` sequences carry through the convolution layers,
    zeroed: the ``z`` of the last K−1 positions."""
    return {"conv": jnp.zeros((kind_counts(cfg)["conv"], rows,
                               cfg.conv_kernel - 1, cfg.dim), cfg.dtype)}


def _conv_sequence(cfg: Lfm2Config, layer: dict, x: jax.Array, i: int,
                   behind: plan.Behind):
    """Keeps ``z`` whole, the carried state in front [B, K−1+S, D]: a
    prefill leaves a snapshot in every page it touches."""
    x, z = conv_op(cfg, layer, x, behind.carried["conv"][i])
    return x, {"conv": z}


def _conv_step(cfg: Lfm2Config, layer: dict, x: jax.Array, i: int,
               state: dict, started: jax.Array):
    """`conv_op` for one position a row over the cache's ``conv`` leaf:
    the slot cache's [L_conv, B, K−1, D], read and written by row, or
    the pool's [L_conv, P, K−1, D] at ``state["pages"]``, (the page of
    each row's last position, the page of this one)."""
    read, write = state.get("pages", (slice(None), slice(None)))
    carried = jnp.where(started[:, None, None], state["conv"][i, read], 0)
    x, z = conv_op(cfg, layer, x, carried)
    return x, {**state, "conv": state["conv"].at[i, write].set(z[:, 1:])}


FAMILY = plan.Family(
    name=__name__, configs=CONFIGS, init=init,
    logical_axes=logical_axes, layers=layer_plan,
    mixers={"attn": plan.ATTENTION,
            "conv": plan.Mixer("conv", _conv_sequence, _conv_step, None)},
    ffns={"dense": plan.DENSE,
          "moe": plan.Ffn(None, lambda cfg, params, i, x, _: expert_ffn(
              cfg, plan._at(params["moe"], i), x))},
    init_rows=init_rows)

# The engine's names (``serving/batching.py`` finds a surface by
# ``hasattr``), those the state's pages do not touch: `plan`'s
# functions over this family's table; admission and the K/V page gather
# are llama's as they are.
forward = functools.partial(plan.forward, FAMILY)
init_cache = cb_init_cache = functools.partial(plan.init_cache, FAMILY)
decode_step_ragged = functools.partial(plan.decode_step_ragged, FAMILY)
decode_step = functools.partial(plan.decode_step, decode_step_ragged)
insert_cache_row = plan.insert_cache_row
cb_admission, cb_validate = llama.cb_admission, llama.cb_validate
paged_gather = llama.paged_gather
apply = functools.partial(plan.apply, FAMILY)
model_def = functools.partial(plan.model_def, FAMILY)


def prefill(cfg: Lfm2Config, params: dict, prompt: jax.Array, max_len: int):
    """`plan.prefill`; of the pass's ``z`` a slot keeps the state after
    the prompt's last position."""
    logits, cache = plan.prefill(FAMILY, cfg, params, prompt, max_len)
    return logits, {**cache, "conv": cache["conv"][:, :, prompt.shape[1]:]}


cb_prefill = functools.partial(plan.cb_prefill, prefill)
generate = functools.partial(llama.generate_loop, prefill, decode_step)


# ------------------------------------------------------------ paged cache
def paged_init_cache(cfg: Lfm2Config, n_pages: int, page_size: int) -> dict:
    """The hybrid pool (module docstring): K/V pages of the attention
    layers, the decode steps' routed pairs by expert, and one
    convolution state a page a convolution layer."""
    return {**plan.paged_init_cache(FAMILY, cfg, n_pages, page_size),
            "conv": init_rows(cfg, n_pages)["conv"]}


def decode_step_paged(cfg: Lfm2Config, params: dict, cache: dict,
                      tokens: jax.Array, pos: jax.Array,
                      tables: jax.Array):
    """`decode_step_ragged` over the hybrid pool. A row at position t
    reads its convolution state from the page of t−1 (zeros at t = 0)
    and leaves the new one in the page of t; idle rows write the
    scratch page. Live rows' routed (row, choice) pairs are added to
    ``moe_expert_tokens``."""
    page = cache["k"].shape[-2]
    coords = llama.paged_coords(pos, tables, page)
    before = jnp.maximum(pos - 1, 0)
    read_page = jnp.maximum(
        tables[jnp.arange(tokens.shape[0]), before // page], 0)
    kv, attend = plan.paged_attend(cfg, cache, tables, coords)
    logits, state, counters = plan.decode(
        FAMILY, cfg, params, tokens, pos, attend,
        {"conv": cache["conv"], "pages": (read_page, coords[1])},
        plan.counters_of(cache))
    return logits, {**kv, "conv": state["conv"], **counters}


def paged_gather_prefix(cache: dict, page_ids: jax.Array) -> tuple:
    """What a suffix prefill reads of the matched pages ``page_ids``
    ([n], clamped, chain order): K and V token-major [L_attn, n·page,
    KV, Hd] and the pages' convolution states [L_conv, n, K−1, D]."""
    return (paged_gather(cache["k"], page_ids),
            paged_gather(cache["v"], page_ids),
            jnp.take(cache["conv"], page_ids, axis=1))


def paged_prefill_suffix_kv(cfg: Lfm2Config, params: dict,
                            suffix: jax.Array, k_prefix: jax.Array,
                            v_prefix: jax.Array, conv_pages: jax.Array, m):
    """The novel tail ``suffix`` [1, S] of a prompt whose first ``m``
    tokens exist (`paged_gather_prefix`'s three, of n pages ≥ m
    tokens): (k, v [L_attn, S, KV, Hd], z [L_conv, K−1+S, D]) for
    `paged_insert_suffix`. The carried state is the one the page of
    position m−1 holds: true where the prefix ends on a page's last
    written position, which whole-page matches and the prefill lane's
    own chunks both give."""
    page = k_prefix.shape[1] // max(conv_pages.shape[1], 1)
    if conv_pages.shape[1]:
        carried = {"conv": jnp.where(
            m > 0, conv_pages[:, jnp.maximum(m - 1, 0) // page], 0)[:, None]}
    else:
        carried = None
    _, k, v, kept = plan.sequence_pass(
        FAMILY, cfg, params, suffix, k_prefix[:, None], v_prefix[:, None],
        carried, m)
    return k[:, 0], v[:, 0], kept["conv"][:, 0]


def paged_insert_suffix(cache: dict, k_suf: jax.Array, v_suf: jax.Array,
                        z: jax.Array, page_ids: jax.Array, start,
                        page_size: int, real_len=None) -> dict:
    """Scatter a suffix's K/V as llama does, and leave in every page it
    touches the convolution state after the last real position written
    there (``z`` [L_conv, K−1+S, D], entry K−1+i position start+i's;
    positions at or past ``real_len`` are padding)."""
    S = k_suf.shape[1]
    keep = z.shape[1] - S  # K − 1
    kv = llama.paged_insert_suffix(
        {"k": cache["k"], "v": cache["v"]}, k_suf, v_suf, page_ids, start,
        page_size, real_len)
    last = start + (S if real_len is None else real_len) - 1
    first_page = start // page_size
    touched = first_page + jnp.arange(-(-S // page_size) + 1)
    at = jnp.minimum((touched + 1) * page_size - 1, last)  # snapshot positions
    slot = jnp.minimum(touched, page_ids.shape[0] - 1)
    pidx = jnp.where(touched * page_size <= last,
                     jnp.maximum(page_ids[slot], 0), 0)  # untouched → scratch
    # The state after position p: z's entries p−(K−2)..p, which sit at
    # p − start + 1 .. p − start + K − 1.
    take = (at - start + 1)[:, None] + jnp.arange(keep)[None, :]
    conv = cache["conv"].at[:, pidx].set(z[:, take])
    return {**cache, **kv, "conv": conv}


def paged_prefill_kv(cfg: Lfm2Config, params: dict, prompt: jax.Array):
    """The whole prompt [1, P] as a suffix behind nothing: (k, v, z) for
    `paged_insert_prefill`."""
    k, v, kept = plan.paged_prefill_kv(FAMILY, cfg, params, prompt)
    return k, v, kept["conv"][:, 0]


def paged_insert_prefill(cache: dict, k_all: jax.Array, v_all: jax.Array,
                         z: jax.Array, page_ids: jax.Array,
                         page_size: int) -> dict:
    return paged_insert_suffix(cache, k_all, v_all, z, page_ids,
                               jnp.int32(0), page_size)
