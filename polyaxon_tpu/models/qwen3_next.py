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
plan.py``): ``rows`` holds ``gdn`` ``[L_gdn, rows, Hv, dk, dv]``
float32 and ``conv`` ``[L_gdn, rows, K−1, conv_dim]``, indexed by the
engine's row. A decode step reads and writes each live row's state in
place, a layer at a time (``ops/gated_delta.py step_rows``: on a TPU
``ops/gdn_update.py``'s kernel over the leaf, one read and one write of
a row's state); a prefill writes its row's; the pool matches nothing
for such a cache. ``moe_expert_tokens`` ``[L, count]`` counts
the decode steps' (row, choice) pairs by held expert,
``moe_pairs_elsewhere`` ``[L]`` those routed to experts this chip does
not hold.

The walks over the plan and the engine's surfaces are ``models/
plan.py``'s, bound below to this family's table (`FAMILY`). Speculation
and chunked dense prefill need ``decode_chunk``, which this family does
not have (the state has no rollback), and the engine refuses them by
that. The published multi-token-prediction module is no part of the
next-token pass and is not here.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama, moe, plan
from polyaxon_tpu.models.common import (
    Variables,
    _w,
    put_layer,
    scaled_init,
    truncated_normal_init,
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


def _kinds(cfg: Qwen3NextConfig) -> tuple:
    return tuple("attn" if (l + 1) % cfg.full_attention_interval == 0
                 else "gdn" for l in range(cfg.n_layers))


def layer_plan(cfg: Qwen3NextConfig) -> tuple:
    """Per layer, in published order: (its mixer's kind, its index in
    that kind's stack). Layer l's expert block is ``moe``'s l-th."""
    return plan.indexed(_kinds(cfg))


def kind_counts(cfg: Qwen3NextConfig) -> dict:
    return plan.kind_counts(_kinds(cfg), ("gdn", "attn"))


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


def init_rows(cfg: Qwen3NextConfig, rows: int) -> dict:
    """What ``rows`` sequences carry through the delta layers, zeroed:
    the matrix state, float32, and the convolution's last K−1 inputs."""
    n = kind_counts(cfg)["gdn"]
    return {"gdn": jnp.zeros((n, rows, cfg.gdn_value_heads, cfg.gdn_key_dim,
                              cfg.gdn_value_dim), jnp.float32),
            "conv": jnp.zeros((n, rows, cfg.conv_kernel - 1,
                               gated_delta.conv_dim(cfg)), cfg.dtype)}


def _gdn_sequence(cfg: Qwen3NextConfig, layer: dict, x: jax.Array, i: int,
                  behind: plan.Behind):
    x, tail, state = gdn_layer(cfg, layer, x, behind.carried["conv"][i],
                               behind.carried["gdn"][i], behind.real_len)
    return x, {"gdn": state, "conv": tail}


def _gdn_step(cfg: Qwen3NextConfig, layer: dict, x: jax.Array, i: int,
              rows: dict, started: jax.Array):
    """`gdn_decode_layer` over the rows' leaves ([L_gdn, rows ≥ B,
    ...]), layer ``i`` of each updated in place."""
    B = x.shape[0]
    tail = jnp.where(started[:, None, None], rows["conv"][i, :B], 0)
    x, tail, gdn = gdn_decode_layer(cfg, layer, x, tail, rows["gdn"], i,
                                    started)
    return x, {"gdn": gdn, "conv": put_layer(rows["conv"], tail, i)}


def _layers(cfg: Qwen3NextConfig) -> tuple:
    """A mixer and an expert block in every layer."""
    return tuple((kind, i, "moe", l)
                 for l, (kind, i) in enumerate(layer_plan(cfg)))


FAMILY = plan.Family(
    name=__name__, configs=CONFIGS, init=init,
    logical_axes=logical_axes, layers=_layers,
    mixers={"attn": plan.ATTENTION._replace(scope="gated_attention"),
            "gdn": plan.Mixer("gdn", _gdn_sequence, _gdn_step, None)},
    ffns={"moe": plan.Ffn(None, lambda cfg, params, i, x, _: expert_block(
        cfg, params["moe"], i, x))},
    init_rows=init_rows)

# The engine's names (``serving/batching.py`` finds a surface by
# ``hasattr``): `plan`'s functions over this family's table; admission
# and the K/V page gather are llama's as they are. What each of the
# engine's rows carries beside its pages is kept under
# ``cache["rows"]``, leaves ``[L, rows, ...]`` (`paged_init_rows`).
forward = functools.partial(plan.forward, FAMILY)
init_cache = cb_init_cache = functools.partial(plan.init_cache, FAMILY)
prefill = functools.partial(plan.prefill, FAMILY)
cb_prefill = functools.partial(plan.cb_prefill, prefill)
decode_step_ragged = functools.partial(plan.decode_step_ragged, FAMILY)
decode_step = functools.partial(plan.decode_step, decode_step_ragged)
generate = functools.partial(llama.generate_loop, prefill, decode_step)
insert_cache_row = plan.insert_cache_row
cb_admission, cb_validate = llama.cb_admission, llama.cb_validate
paged_init_cache = functools.partial(plan.paged_init_cache, FAMILY)
paged_init_rows = init_rows
decode_step_paged = functools.partial(plan.decode_step_paged, FAMILY)
paged_gather = llama.paged_gather
paged_gather_prefix = plan.paged_gather_prefix
paged_prefill_kv = functools.partial(plan.paged_prefill_kv, FAMILY)
paged_prefill_suffix_kv = functools.partial(plan.paged_prefill_suffix_kv,
                                            FAMILY)
paged_insert_prefill = plan.paged_insert_prefill
paged_insert_suffix = plan.paged_insert_suffix
apply = functools.partial(plan.apply, FAMILY)
model_def = functools.partial(plan.model_def, FAMILY)
