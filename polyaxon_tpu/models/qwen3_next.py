"""Qwen3-Next-style hybrid decoder: Gated DeltaNet layers beside gated
attention, every layer followed by a block of routed SwiGLU experts.

The published ``qwen3_next`` architecture (Qwen/Qwen3-Next-80B-A3B-
Instruct ``config.json``). With ``rms(x, g) = x / sqrt(mean(x²) + eps) ·
(1 + g)``, layer ``l`` is ``x ← x + Mixer_l(rms(x, norm_l))`` and then
``x ← x + Experts_l(rms(x, norm'_l))``: a mixer *and* an expert block in
every layer. After the last layer ``rms(x, norm_f)`` and the untied
head.

- The mixer is full attention where ``(l + 1) % full_attention_interval
  == 0`` and Gated DeltaNet elsewhere (a period of four is ``D D D A``).
- *Gated DeltaNet* (``ops/gated_delta.py``): what a sequence carries
  between tokens is a matrix state ``S`` [Hv, dk, dv] float32 a layer
  and the last K−1 inputs of its convolution.
- *Gated attention*: llama's attention walks, which read three things
  from this config and its layers: ``wq`` twice as wide (each head's
  query, then the gate of its output: ``W_o(attn ⊙ sigmoid(gate))``),
  ``partial_rotary_factor`` (the rotary embedding turns the first
  quarter of a head's 256 dimensions) and q/k norm gains that apply,
  like every norm here but the delta layer's output norm, as ``1 + w``
  (``norm_offset``).
- *Experts*: ``p = softmax(u · W_r)`` over every routed expert in
  float32; the K largest, renormalised (``models/moe.py route``); ``r =
  Σ_k w_k · W_down,e(silu(W_gate,e u) ⊙ W_up,e u)``; ``out = r +
  sigmoid(u · w_sg) · Shared(u)``, one shared SwiGLU expert behind a
  scalar gate a token.

**The chip's share of the experts.** ``held_experts = (first, count)``
names the routed experts whose weights this chip holds (``w_gate``/
``w_up``/``w_down`` are ``[L, count, ...]``). The layer routes over all
``n_experts`` with the published router; a (token, choice) pair whose
expert lies elsewhere adds nothing here, and the partial sum goes on to
the next layer. The shared expert is whole here. No code stands in for
the absent chips or their exchange. A decode step dispatches its rows
through the one-hot buffers at the no-drop capacity
(``moe.dense_dispatch``), a sequence through sorted pairs and grouped
matmuls (``moe.sorted_dispatch``: three a block over the stacks handed
whole, on a TPU ``ops/grouped_matmul.py``'s kernel, which reads each
expert that holds a pair once; elsewhere ``jax.lax.ragged_dot``).

**Layers of different kinds.** The mixers' parameters are stacked by
kind (``gdn``, ``attn``), the expert blocks' over every layer
(``moe``); a static plan (`layer_plan`) walks them.

**The cache.** ``k``/``v`` hold the attention layers' pages ``[L_attn,
P, KV, page, Hd]``; the delta layers' state is *per row* (``models/
row_state.py``): ``rows`` holds ``gdn`` ``[L_gdn, rows, Hv, dk, dv]``
float32 and ``conv`` ``[L_gdn, rows, K−1, conv_dim]``, indexed by the
engine's row. A decode step reads and writes each live row's state in
place, a layer at a time (``ops/gated_delta.py step_rows``: on a TPU
``ops/gdn_update.py``'s kernel over the leaf, one read and one write of
a row's state); a prefill writes its row's; the pool matches nothing
for such a cache. ``moe_expert_tokens`` ``[L, count]`` counts
the decode steps' (row, choice) pairs by held expert,
``moe_pairs_elsewhere`` ``[L]`` those routed to experts this chip does
not hold.

One sequence pass (`_sequence_pass`: a suffix behind an optional
prefix) serves ``forward``, the whole-prompt prefill and the suffix
prefill; speculation and chunked dense prefill need ``decode_chunk``,
which this family does not have (the state has no rollback), and the
engine refuses them by that. The published multi-token-prediction
module is no part of the next-token pass and is not here.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama, moe, row_state
from polyaxon_tpu.models.common import (
    Batch,
    ModelDef,
    Variables,
    _embed_rows,
    _w,
    chunked_lm_loss,
    lm_logits,
    scaled_init,
    shift_right,
    truncated_normal_init,
)
# A prefilled row goes into its slot as the other hybrid families' does
# (every leaf's axis 1 is the slot); decoder-only admission and the K/V
# page gather are llama's as they are; the per-row side of the paged
# surface is `row_state`'s.
from polyaxon_tpu.models.lfm2 import (  # noqa: F401  (re-exported hook)
    _at,
    insert_cache_row,
)
from polyaxon_tpu.models.llama import (  # noqa: F401  (re-exported hooks)
    cb_admission,
    cb_validate,
    paged_gather,
)
from polyaxon_tpu.models.row_state import (  # noqa: F401  (re-exported hooks)
    paged_gather_prefix,
    paged_insert_prefill,
    paged_insert_suffix,
    put_layer,
)
from polyaxon_tpu.ops import gated_delta

SEQ2SEQ = False
# The seeded draw of dt_bias (the delta rule's own initialisation,
# Mamba-2's): a time step log-uniform between the first two, held over
# the third.
TIME_STEP = (0.001, 0.1, 1e-4)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151_936
    dim: int = 2048
    n_layers: int = 48
    # Layer l is full attention where (l + 1) % this == 0.
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    n_experts: int = 512  # what the router scores
    experts_per_token: int = 10
    moe_ffn_dim: int = 512  # per routed expert
    shared_ffn_dim: int = 512  # the shared expert
    # (first, count) of the routed experts held here; None: all.
    held_experts: Optional[tuple] = None
    norm_offset: float = 1.0  # gains apply as (1 + w)
    norm_eps: float = 1e-6
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16
    paged_attention_impl: str = "auto"  # as LlamaConfig's
    loss_chunk: int = 256
    lm_logits_chunk: int = 4096

    def __post_init__(self):
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError("gdn_value_heads is not a multiple of "
                             "gdn_key_heads")
        if self.full_attention_interval < 1:
            raise ValueError("full_attention_interval must be at least 1")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} lie outside "
                             f"the {self.n_experts} routed experts")

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts held here."""
        return self.held_experts or (0, self.n_experts)


CONFIGS: dict[str, Qwen3NextConfig] = {
    "qwen3_next_80b_a3b": Qwen3NextConfig(),
    "qwen3_next_tiny": Qwen3NextConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8,
        gdn_value_dim=8, chunk_size=8, n_experts=16, experts_per_token=4,
        moe_ffn_dim=32, shared_ffn_dim=32, max_seq_len=128),
}


@functools.lru_cache(maxsize=None)
def _plan(n_layers: int, interval: int) -> tuple:
    seen = {"gdn": 0, "attn": 0}
    out = []
    for i in range(n_layers):
        kind = "attn" if (i + 1) % interval == 0 else "gdn"
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return tuple(out)


def layer_plan(cfg: Qwen3NextConfig) -> tuple:
    """Per layer, in published order: (its mixer's kind, its index in
    that kind's stack). Layer l's expert block is ``moe``'s l-th."""
    return _plan(cfg.n_layers, cfg.full_attention_interval)


def kind_counts(cfg: Qwen3NextConfig) -> dict:
    kinds = [kind for kind, _ in layer_plan(cfg)]
    return {"gdn": kinds.count("gdn"), "attn": kinds.count("attn")}


def init(cfg: Qwen3NextConfig, rng: jax.Array) -> Variables:
    """Seeded float32 weights, the mixers' stacked by kind. Projections
    as the zoo draws them (truncated normal, 1/sqrt(fan_in); the tables
    std 0.02); norm gains at the identity (zeros where they apply as
    ``1 + w``, ones for the delta layer's output norm). What the
    published model learns as small vectors is drawn so that each shows
    in the result: ``A_log = log(A)``, A uniform in [1, 16), and
    ``dt_bias`` the inverse softplus of a time step log-uniform in
    `TIME_STEP` (the gated delta rule's own initialisation, which is
    Mamba-2's)."""
    keys = jax.random.split(rng, 20)
    n = kind_counts(cfg)
    L, Lg, La = cfg.n_layers, n["gdn"], n["attn"]
    D, H, KV, Hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv, K = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.conv_kernel
    E, held = cfg.n_experts, cfg.held[1]
    F, Fs = cfg.moe_ffn_dim, cfg.shared_ffn_dim
    conv_dim = gated_delta.conv_dim(cfg)
    lo, hi, floor = TIME_STEP
    step = jnp.exp(jax.random.uniform(keys[5], (Lg, Hv))
                   * (math.log(hi) - math.log(lo)) + math.log(lo))
    step = jnp.maximum(step, floor)
    identity = 1.0 - cfg.norm_offset
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "gdn": {
            "gdn_norm": jnp.full((Lg, D), identity),
            "w_qkvz": scaled_init(
                keys[1], (Lg, D, 2 * Hk * dk + 2 * Hv * dv), fan_in=D),
            "w_ba": scaled_init(keys[2], (Lg, D, 2 * Hv), fan_in=D),
            "conv_w": scaled_init(keys[3], (Lg, conv_dim, K), fan_in=K),
            "A_log": jnp.log(jax.random.uniform(
                keys[4], (Lg, Hv), minval=1.0, maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "out_norm": jnp.ones((Lg, dv)),
            "w_out": scaled_init(keys[6], (Lg, Hv * dv, D), fan_in=Hv * dv),
        },
        "attn": {
            "attn_norm": jnp.full((La, D), identity),
            "wq": scaled_init(keys[7], (La, D, 2 * H * Hd), fan_in=D),
            "wk": scaled_init(keys[8], (La, D, KV * Hd), fan_in=D),
            "wv": scaled_init(keys[9], (La, D, KV * Hd), fan_in=D),
            "q_norm": jnp.full((La, Hd), identity),
            "k_norm": jnp.full((La, Hd), identity),
            "wo": scaled_init(keys[10], (La, H * Hd, D), fan_in=H * Hd),
        },
        "moe": {
            "moe_norm": jnp.full((L, D), identity),
            "router": scaled_init(keys[11], (L, D, E), fan_in=D),
            "w_gate": scaled_init(keys[12], (L, held, D, F), fan_in=D),
            "w_up": scaled_init(keys[13], (L, held, D, F), fan_in=D),
            "w_down": scaled_init(keys[14], (L, held, F, D), fan_in=F),
            "ws_gate": scaled_init(keys[15], (L, D, Fs), fan_in=D),
            "ws_up": scaled_init(keys[16], (L, D, Fs), fan_in=D),
            "ws_down": scaled_init(keys[17], (L, Fs, D), fan_in=Fs),
            "shared_gate": scaled_init(keys[18], (L, D), fan_in=D),
        },
        "final_norm": jnp.full((D,), identity),
        "lm_head": truncated_normal_init(keys[19], (D, cfg.vocab_size)),
    }
    return {"params": params, "state": {}}


def logical_axes(cfg: Qwen3NextConfig) -> Variables:
    del cfg
    return {
        "params": {
            "embed": ("vocab", "embed"),
            "gdn": {
                "gdn_norm": ("layers", "embed"),
                "w_qkvz": ("layers", "embed", "mlp"),
                "w_ba": ("layers", "embed", None),
                "conv_w": ("layers", "mlp", None),
                "A_log": ("layers", None),
                "dt_bias": ("layers", None),
                "out_norm": ("layers", None),
                "w_out": ("layers", "mlp", "embed"),
            },
            "attn": {
                "attn_norm": ("layers", "embed"),
                "wq": ("layers", "embed", "heads"),
                "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"),
                "q_norm": ("layers", None),
                "k_norm": ("layers", None),
                "wo": ("layers", "heads", "embed"),
            },
            "moe": {
                "moe_norm": ("layers", "embed"),
                "router": ("layers", "embed", None),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
                "ws_gate": ("layers", "embed", "mlp"),
                "ws_up": ("layers", "embed", "mlp"),
                "ws_down": ("layers", "mlp", "embed"),
                "shared_gate": ("layers", "embed"),
            },
            "final_norm": ("embed",),
            "lm_head": ("embed", "vocab"),
        },
        "state": {},
    }


# Leaves read at float32: every norm gain, the recurrence's own vectors
# and the convolution's taps (``ops/gated_delta.py`` reads them with
# ``.astype(float32)``: the taps are summed in float32), and the router
# (the scores decide a top-k, so that matmul is float32 at full
# precision, as the other routed families'). The rest are read at
# ``cfg.dtype`` and a server holds them so (``common.served_params``).
READ_AT_FLOAT32 = frozenset(
    {"gdn_norm", "attn_norm", "moe_norm", "final_norm", "q_norm", "k_norm",
     "out_norm", "A_log", "dt_bias", "conv_w", "router"})

# Leaves a server holds ``[.., N, D]``: the attention layers' three
# projections, read by llama's `_qkv` (its table says why), and the
# delta layers' ``w_qkvz``, whose product ``ops/gated_delta.py
# split_projections`` splits by key head (the decode program copied
# each of the six layers' 50 MB transposed every step). ``w_ba``,
# ``w_out`` and the experts' stream from their stacks as they are.
HELD_TRANSPOSED = llama.HELD_TRANSPOSED | {"w_qkvz"}


# ------------------------------------------------------------ the layers
def gdn_layer(cfg: Qwen3NextConfig, layer: dict, x: jax.Array,
              conv_tail: jax.Array, state: jax.Array, real_len=None):
    """The Gated DeltaNet mixer over ``x`` [B, S, D] behind what the
    sequence carries (``ops/gated_delta.py mixer``). Returns (x after
    the residual, new convolution tail, new state)."""
    u = llama._norm(cfg, x, layer["gdn_norm"])
    out, tail, state = gated_delta.mixer(cfg, layer, u, conv_tail, state,
                                         real_len)
    return x + out, tail, state


def gdn_decode_layer(cfg: Qwen3NextConfig, layer: dict, x: jax.Array,
                     conv_tail: jax.Array, gdn: jax.Array, i: int,
                     started: jax.Array):
    """`gdn_layer` for one position a row ([B, 1, D]) over the decode
    cache's leaf ``gdn`` [L_gdn, rows ≥ B, Hv, dk, dv], whose layer
    ``i`` is updated where it lies (``ops/gated_delta.py
    decode_mixer``). Returns (x after the residual, new convolution
    tail, the leaf)."""
    u = llama._norm(cfg, x, layer["gdn_norm"])
    out, tail, gdn = gated_delta.decode_mixer(cfg, layer, u, conv_tail, gdn,
                                              i, started)
    return x + out, tail, gdn


def routed_experts(cfg: Qwen3NextConfig, stack: dict, i: int,
                   tokens: jax.Array, sequence: bool):
    """The held experts' part of the routed sum in layer ``i`` of
    ``stack`` (``params["moe"]``) for ``tokens`` [T, D] (already
    normalised): (r [T, D], the held choices' one-hot [T, K, count] or
    None for a sequence)."""
    dt = cfg.dtype
    # The scores decide a top-k, where a rounding flips an expert: the
    # router's own matmul runs in float32 at full precision.
    logits = jnp.dot(tokens.astype(jnp.float32),
                     stack["router"][i].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_idx, top_w, _ = moe.route(cfg, logits)
    first = cfg.held[0]
    if sequence:
        return moe.sorted_dispatch(
            tokens, top_idx, top_w, stack["w_gate"], stack["w_up"],
            stack["w_down"], first, dt, layer=i), None
    return moe.dense_dispatch(
        tokens, top_idx, top_w, stack["w_gate"][i], stack["w_up"][i],
        stack["w_down"][i], tokens.shape[0], dt, first=first)


def shared_expert(cfg: Qwen3NextConfig, stack: dict, i: int,
                  tokens: jax.Array) -> jax.Array:
    """The shared SwiGLU expert of layer ``i`` behind its gate, a scalar
    a token: ``sigmoid(u · w_sg) · W_down(silu(W_gate u) ⊙ W_up u)``."""
    dt = cfg.dtype
    hidden = (jax.nn.silu(tokens @ _w(stack["ws_gate"][i], dt))
              * (tokens @ _w(stack["ws_up"][i], dt)))
    out = hidden @ _w(stack["ws_down"][i], dt)
    gate = jax.nn.sigmoid(
        (tokens @ _w(stack["shared_gate"][i], dt)).astype(jnp.float32))
    return (out * gate[:, None]).astype(dt)


def expert_block(cfg: Qwen3NextConfig, stack: dict, i: int, x: jax.Array):
    """Layer ``i``'s expert residual over ``x`` [B, S, D], its B·S
    tokens one dispatch group; nothing is dropped. A single position a
    row (a decode step) goes through the one-hot buffers, a sequence
    through sorted pairs. Returns (x after the residual, the held
    choices' one-hot [B·S, K, count] or None)."""
    B, S, D = x.shape
    tokens = llama._norm(cfg, x, stack["moe_norm"][i]).reshape(B * S, D)
    routed, onehot = routed_experts(cfg, stack, i, tokens, sequence=S > 1)
    out = routed + shared_expert(cfg, stack, i, tokens)
    return x + out.reshape(B, S, D), onehot


def _head(cfg: Qwen3NextConfig, params: dict, x: jax.Array) -> jax.Array:
    """Final norm and the untied head: hidden [..., D] → fp32 logits."""
    x = llama._norm(cfg, x, params["final_norm"])
    return lm_logits(x, params["lm_head"], cfg.dtype,
                     chunk=cfg.lm_logits_chunk)


def init_rows(cfg: Qwen3NextConfig, rows: int) -> dict:
    """What ``rows`` sequences carry through the delta layers, zeroed:
    the matrix state, float32, and the convolution's last K−1 inputs."""
    n = kind_counts(cfg)["gdn"]
    return {"gdn": jnp.zeros((n, rows, cfg.gdn_value_heads, cfg.gdn_key_dim,
                              cfg.gdn_value_dim), jnp.float32),
            "conv": jnp.zeros((n, rows, cfg.conv_kernel - 1,
                               gated_delta.conv_dim(cfg)), cfg.dtype)}


def _sequence_pass(cfg: Qwen3NextConfig, params: dict, tokens: jax.Array,
                   k_prefix: Optional[jax.Array] = None,
                   v_prefix: Optional[jax.Array] = None,
                   carried: Optional[dict] = None, m=0, real_len=None):
    """One causal pass over ``tokens`` [B, S] at absolute positions
    m..m+S−1, behind a prefix that already exists: its K/V
    ``k_prefix``/``v_prefix`` [L_attn, B, Mpad, KV, Hd] (columns at or
    past ``m`` masked) and what the delta layers carry after position
    m−1, ``carried`` (`init_rows`' two leaves for B rows). Without a
    prefix (all None, m = 0) it is the whole-sequence forward.
    Positions at or past ``real_len`` are padding
    (``gated_delta.mixer``). Returns (hidden before the final norm [B,
    S, D], k [L_attn, B, S, KV, Hd], v, what the layers carry after the
    last real position)."""
    dt = cfg.dtype
    B, S = tokens.shape
    if k_prefix is None:
        shape = (kind_counts(cfg)["attn"], B, 0, cfg.n_kv_heads,
                 cfg.head_dim)
        k_prefix = v_prefix = jnp.zeros(shape, dt)
    if carried is None:
        carried = init_rows(cfg, B)
    positions = jnp.broadcast_to(
        m + jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    valid = llama._suffix_mask(S, k_prefix.shape[2], m)
    x = _embed_rows(params["embed"], tokens, dt)
    ks, vs, tails, states = [], [], [], []
    for layer, (kind, i) in enumerate(layer_plan(cfg)):
        if kind == "attn":
            with jax.named_scope("gated_attention"):
                x, k, v = llama.suffix_attn_step(
                    cfg, _at(params["attn"], i), x, k_prefix[i],
                    v_prefix[i], positions, valid)
            ks.append(k)
            vs.append(v)
        else:
            x, tail, state = gdn_layer(
                cfg, _at(params["gdn"], i), x, carried["conv"][i],
                carried["gdn"][i], real_len)
            tails.append(tail)
            states.append(state)
        x, _ = expert_block(cfg, params["moe"], layer, x)
    return x, jnp.stack(ks), jnp.stack(vs), {
        "gdn": jnp.stack(states), "conv": jnp.stack(tails)}


def forward(cfg: Qwen3NextConfig, params: dict,
            tokens: jax.Array) -> jax.Array:
    """Token ids [B, S] → logits [B, S, vocab] fp32."""
    x, _, _, _ = _sequence_pass(cfg, params, tokens)
    return _head(cfg, params, x)


# ------------------------------------------------------- dense slot cache
def init_cache(cfg: Qwen3NextConfig, batch: int, max_len: int) -> dict:
    """The slot cache: K/V [L_attn, B, C, KV, Hd] and what each slot
    carries through the delta layers (`init_rows`)."""
    kv = (kind_counts(cfg)["attn"], batch, max_len, cfg.n_kv_heads,
          cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **init_rows(cfg, batch)}


def prefill(cfg: Qwen3NextConfig, params: dict, prompt: jax.Array,
            max_len: int):
    """One pass over the prompt [B, P]: (last-position logits [B, V]
    fp32, the slot cache holding it)."""
    P = prompt.shape[1]
    if P > max_len:
        raise ValueError(f"prompt length {P} exceeds cache length {max_len}")
    x, k, v, carried = _sequence_pass(cfg, params, prompt)
    pad = ((0, 0), (0, 0), (0, max_len - P), (0, 0), (0, 0))
    cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad), **carried}
    return _head(cfg, params, x[:, -1]), cache


def _decode_layers(cfg: Qwen3NextConfig, params: dict, x: jax.Array,
                   pos: jax.Array, attend, gdn: jax.Array, conv: jax.Array,
                   counters: Optional[dict] = None):
    """One position a row through every layer. ``attend(i, layer, x)``
    is the attention layer over the cache in use; ``gdn``/``conv`` are
    the rows' carried leaves ([L_gdn, rows ≥ B, ...]; a row at position
    0 starts from zeros, an idle row's is garbage the next admission's
    prefill replaces), updated in place a layer at a time. Live rows'
    routed pairs are added to ``counters`` where given."""
    B = x.shape[0]
    started = pos > 0
    live = (pos >= 0).astype(jnp.int32)
    for layer, (kind, i) in enumerate(layer_plan(cfg)):
        if kind == "attn":
            with jax.named_scope("gated_attention"):
                x = attend(i, _at(params["attn"], i), x)
        else:
            tail = jnp.where(started[:, None, None], conv[i, :B], 0)
            x, tail, gdn = gdn_decode_layer(cfg, _at(params["gdn"], i), x,
                                            tail, gdn, i, started)
            conv = put_layer(conv, tail, i)
        x, onehot = expert_block(cfg, params["moe"], layer, x)
        if counters is not None:
            held = jnp.einsum("tke,t->e", onehot.astype(jnp.int32), live)
            counters = {
                "moe_expert_tokens":
                    counters["moe_expert_tokens"].at[layer].add(held),
                "moe_pairs_elsewhere":
                    counters["moe_pairs_elsewhere"].at[layer].add(
                        cfg.experts_per_token * jnp.sum(live)
                        - jnp.sum(held))}
    return x, gdn, conv, counters


def decode_step_ragged(cfg: Qwen3NextConfig, params: dict, cache: dict,
                       tokens: jax.Array, pos: jax.Array):
    """One step with per-row positions ([B], −1 = idle) over the slot
    cache: llama's ``cached_attn_step`` in the attention layers, the
    row's own carried state in the delta layers."""
    positions, slot, valid = llama.ragged_cache_coords(pos,
                                                       cache["k"].shape[2])
    kv = {"k": cache["k"], "v": cache["v"]}

    def attend(i, layer, x):
        x, k, v = llama.cached_attn_step(cfg, layer, x, kv["k"][i],
                                         kv["v"][i], positions, slot, valid)
        kv["k"], kv["v"] = put_layer(kv["k"], k, i), put_layer(kv["v"], v, i)
        return x

    x = _embed_rows(params["embed"], tokens, cfg.dtype)[:, None, :]
    x, gdn, conv, _ = _decode_layers(cfg, params, x, pos, attend,
                                     cache["gdn"], cache["conv"])
    return _head(cfg, params, x[:, 0]), {**kv, "gdn": gdn, "conv": conv}


def decode_step(cfg: Qwen3NextConfig, params: dict, cache: dict,
                tokens: jax.Array, pos: jax.Array):
    """Scalar-position decode: every row at the same position."""
    return decode_step_ragged(
        cfg, params, cache, tokens,
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tokens.shape[:1]))


def generate(cfg: Qwen3NextConfig, params: dict, prompt: jax.Array,
             **sampling):
    """Greedy or sampled continuation [B, max_new]: llama's
    ``generate_loop`` over this family's prefill and decode step."""
    return llama.generate_loop(prefill, decode_step, cfg, params, prompt,
                               **sampling)


def cb_init_cache(cfg: Qwen3NextConfig, slots: int, max_len: int) -> dict:
    return init_cache(cfg, slots, max_len)


def cb_prefill(cfg: Qwen3NextConfig, params: dict, prompt: jax.Array,
               max_len: int) -> dict:
    return prefill(cfg, params, prompt, max_len)[1]


# ------------------------------------------------------------ paged cache
def paged_init_cache(cfg: Qwen3NextConfig, n_pages: int,
                     page_size: int) -> dict:
    """The paged part of the cache (module docstring): K/V pages of the
    attention layers and the decode steps' routed pairs. The engine
    adds `paged_init_rows` under ``rows``."""
    kv = (kind_counts(cfg)["attn"], n_pages, cfg.n_kv_heads, page_size,
          cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "moe_expert_tokens": jnp.zeros((cfg.n_layers, cfg.held[1]),
                                           jnp.int32),
            "moe_pairs_elsewhere": jnp.zeros((cfg.n_layers,), jnp.int32)}


# What each of the engine's rows carries beside its pages: the engine
# keeps it under ``cache["rows"]``, leaves ``[L, rows, ...]``.
paged_init_rows = init_rows


def decode_step_paged(cfg: Qwen3NextConfig, params: dict, cache: dict,
                      tokens: jax.Array, pos: jax.Array,
                      tables: jax.Array):
    """`decode_step_ragged` over the paged pool: row b's K and V in its
    pages, its delta state in row b of ``cache["rows"]``, read and
    written in place."""
    page = cache["k"].shape[-2]
    positions, write_page, write_off, valid = llama.paged_coords(
        pos, tables, page)
    kv = {"k": cache["k"], "v": cache["v"]}

    def attend(i, layer, x):
        x, kv["k"], kv["v"] = llama.paged_attn_step(
            cfg, layer, x, kv["k"], kv["v"], i, positions, write_page,
            write_off, tables, valid)
        return x

    x = _embed_rows(params["embed"], tokens, cfg.dtype)[:, None, :]
    x, gdn, conv, counters = _decode_layers(
        cfg, params, x, pos, attend, cache["rows"]["gdn"],
        cache["rows"]["conv"],
        counters={name: cache[name] for name in (
            "moe_expert_tokens", "moe_pairs_elsewhere")})
    return _head(cfg, params, x[:, 0]), {
        **kv, **counters, "rows": {"gdn": gdn, "conv": conv}}


def paged_prefill_kv(cfg: Qwen3NextConfig, params: dict, prompt: jax.Array):
    return row_state.paged_prefill_kv(_sequence_pass, cfg, params, prompt)


def paged_prefill_suffix_kv(cfg: Qwen3NextConfig, params: dict, *suffix):
    return row_state.paged_prefill_suffix_kv(_sequence_pass, cfg, params,
                                             *suffix)


# --------------------------------------------------------------- training
def apply(cfg: Qwen3NextConfig, variables: Variables, batch: Batch,
          train: bool = True, rng: Optional[jax.Array] = None):
    """Next-token loss (chunked head), no auxiliary loss."""
    tokens = batch["tokens"]
    if batch.get("segments") is not None:
        raise ValueError("qwen3_next models do not support packed sequences "
                         "(segments): the recurrent state would cross them")
    params = variables["params"]
    x, _, _, _ = _sequence_pass(cfg, params, shift_right(tokens))
    x = llama._norm(cfg, x, params["final_norm"])
    loss, acc = chunked_lm_loss(x, params["lm_head"].astype(cfg.dtype),
                                tokens, batch.get("mask"),
                                chunk=cfg.loss_chunk)
    return loss, {"loss": loss, "accuracy": acc}, variables["state"]


def model_def(name: str, **overrides) -> ModelDef:
    cfg = dataclasses.replace(CONFIGS[name], **overrides)
    return ModelDef(
        name=name,
        init=functools.partial(init, cfg),
        apply=functools.partial(apply, cfg),
        logical_axes=functools.partial(logical_axes, cfg),
        unit="tokens",
    )
