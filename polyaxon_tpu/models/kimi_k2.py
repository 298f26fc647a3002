"""Kimi-K2-style decoder (the DeepSeek-V3 block): multi-head latent
attention (MLA) under a YaRN rotary rule in every layer, a dense SwiGLU
MLP in the leading layers and behind them sigmoid-routed SwiGLU experts
beside one shared expert.

The published ``kimi_k2`` architecture (moonshotai/Kimi-K2.6
``config.json``). With ``rms(x, g) = x / sqrt(mean(x²) + eps) · g``,
layer ``l`` is::

    h    = rms(x, attn_norm_l)
    c_q  = rms(h · W_qa, q_norm)                          # q_lora_rank
    [q_nope (dn) ‖ q_pe (dr)] a head = c_q · W_qb         # H heads
    [c_kv (R) ‖ k_pe (dr)] = h · W_kva;  c_kv = rms(c_kv, kv_norm)
    q_pe, k_pe = rope(q_pe), rope(k_pe)                   # k_pe one for all heads
    k_nope a head = c_kv · W_UK,h;   v a head = c_kv · W_UV,h
    a    = causal softmax((q_nope·k_nope + q_pe·k_pe) · s) · v     # float32
    x    = x + concat(a) · W_o
    g    = rms(x, ffn_norm_l)
    x    = x + W_down(silu(W_gate g) ⊙ W_up g)            # l < first_dense
    x    = x + Σ_{e in top K of sigmoid(g · W_r) + bias} w_e · E_e(g)
             + E_shared(g)                                # the others

``w`` are the chosen experts' scores over their sum, times
``routed_scaling_factor`` (``models/moe.py route``); ``s`` is
``(dn + dr)^-0.5`` times the YaRN rule's factor
(``models/common.py yarn_softmax_scale``). ``W_UK`` and ``W_UV`` are the
two halves of the published ``kv_b_proj`` a head, drawn and held as two
leaves ``[H, R, dn]`` and ``[H, R, dv]``: the split a loader makes once.

**What is cached a token a layer** is ``c_kv`` after its norm and
``k_pe`` after its rotation and nothing else: one *latent* of ``R + dr``
values (576 published), padded with zeros to whole lane tiles
(`latent_pad`, 640) because the chip's page copy wants the pool's last
dimension so (``ops/mla_decode.py``). No per-head K or V exists in a
decode or a suffix program.

- *Decode* is the absorbed form: ``q_lat = q_nope · W_UK^T`` (R a
  head), ``score = (q_lat·c_kv + q_pe·k_pe) · s``, ``o = (Σ p c_kv) ·
  W_UV``: the same mathematics, one read of the latent for keys and
  values of all heads (``ops/mla_decode.py``, a call named
  ``mla_decode``; off the chip the same sums over gathered pages).
- *A whole prompt* is the up-projected form through flash attention,
  the query/key head (dn + dr = 192) and the value head (dv = 128) both
  padded with zeros to 256: the kernel takes one head size of 64 or a
  multiple of 128 for q, k and v alike. The zeros add nothing to a
  score and the value's are cut off again; the cost is 1.6x the
  matmul work of the attention itself, on the path that builds a
  document's pages once.
- *A suffix behind cached pages* (a radix match, the prefill lane's
  later chunks) is the absorbed form again, the prefix's latent and the
  suffix's own as one run of keys taken in blocks of `KEY_BLOCK` under an
  online softmax: no ``[S, prefix]`` score tensor, and the prefix is
  never up-projected.

**The cache.** Dense: ``latent`` [L, B, C, W]. Paged: ``latent``
[L, P, 1, page, W], a page pool of one "KV head" that
``serving/paged.py page_bytes`` counts as llama's K and V; a latent page
carries no state, so the radix tree matches for this family as it does
for llama, copy-on-write forks included. Beside it the decode steps'
routed pairs (``moe_expert_tokens``, ``moe_pairs_elsewhere``) and
``mla_decode_positions``: the live positions the decode steps' attention
read, summed over rows and steps, as (multiples of 2³⁰, remainder).

**The chip's share of the experts.** ``held_experts = (first, count)``
as ``models/qwen3_next.py``: the router scores every expert, the chip
computes those it holds and the shared expert, and leaves the rest out.

The walks over the plan are ``models/plan.py``'s, bound below to this
family's table (`FAMILY`); the cache surfaces are this file's.
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
    _w,
    project,
    put_layer,
    rms_norm,
    rope,
    scaled_init,
    truncated_normal_init,
    yarn_softmax_scale,
)
from polyaxon_tpu.ops.attention import xla_attention
from polyaxon_tpu.ops.mla_decode import (mla_decode_attention,
                                         mla_decode_reference)
from polyaxon_tpu.ops.paged_attention import LANES, NEG_INF

SEQ2SEQ = False
# A whole prompt's sequence is padded to a multiple of this (flash
# attention tiles a sequence into blocks of at least 128).
PREFILL_TILE = 256
# Keys a turn of the suffix prefill's online softmax.
KEY_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163_840
    dim: int = 7168
    n_layers: int = 61
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50_000.0
    # The YaRN rule (``models/common.py yarn_frequencies``).
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    ffn_dim: int = 18_432  # the leading dense layers' MLP
    first_dense: int = 1  # how many leading layers are dense
    n_experts: int = 384  # what the router scores
    experts_per_token: int = 8
    moe_ffn_dim: int = 2048  # a routed expert, and the shared one
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    router_score: str = "sigmoid"
    # (first, count) of the routed experts held here; None: all.
    held_experts: Optional[tuple] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"  # the whole-prompt pass: as LlamaConfig's
    paged_attention_impl: str = "auto"  # as LlamaConfig's
    loss_chunk: int = 256
    lm_logits_chunk: int = 4096

    def __post_init__(self):
        if self.router_score != "sigmoid":
            raise ValueError("a kimi_k2 router scores by sigmoid")
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError("first_dense lies outside the layers")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} lie outside "
                             f"the {self.n_experts} routed experts")

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts held here."""
        return self.held_experts or (0, self.n_experts)

    @property
    def rope_scaling(self) -> dict:
        return {"type": "yarn", "factor": self.rope_factor,
                "original_max_position_embeddings": self.rope_original_max,
                "beta_fast": self.rope_beta_fast,
                "beta_slow": self.rope_beta_slow,
                "mscale": self.rope_mscale,
                "mscale_all_dim": self.rope_mscale_all_dim}

    @property
    def softmax_scale(self) -> float:
        return yarn_softmax_scale(
            self.qk_nope_head_dim + self.qk_rope_head_dim, self.rope_scaling)

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer: c_kv and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_pad(self) -> int:
        """`latent_width` padded to whole lane tiles: a page's last
        dimension."""
        return -(-self.latent_width // LANES) * LANES


CONFIGS: dict[str, KimiK2Config] = {
    "kimi_k2_6": KimiK2Config(),
    "kimi_k2_tiny": KimiK2Config(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_factor=4.0, rope_original_max=32, ffn_dim=96,
        first_dense=1, n_experts=16, experts_per_token=4, moe_ffn_dim=32,
        max_seq_len=128),
}


def _layers(cfg: KimiK2Config) -> tuple:
    """MLA in every layer; the leading ``first_dense`` layers' FFN is
    dense, the others' the expert block."""
    return tuple(
        ("mla", l, "dense", l) if l < cfg.first_dense
        else ("mla", l, "moe", l - cfg.first_dense)
        for l in range(cfg.n_layers))


def init(cfg: KimiK2Config, rng: jax.Array) -> Variables:
    """Seeded float32 weights as the zoo draws them (truncated normal,
    1/sqrt(fan_in); the tables std 0.02), norm gains at ones; the
    selection bias, a learned buffer in the published model, is drawn
    around zero (std 0.02) so that it shows in the choice."""
    keys = jax.random.split(rng, 19)
    L, D, H = cfg.n_layers, cfg.dim, cfg.n_heads
    Ld, Lm = cfg.first_dense, cfg.n_layers - cfg.first_dense
    Rq, R = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    E, held = cfg.n_experts, cfg.held[1]
    F, Fm = cfg.ffn_dim, cfg.moe_ffn_dim
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "attn": {
            "attn_norm": jnp.ones((L, D)),
            "wq_a": scaled_init(keys[1], (L, D, Rq), fan_in=D),
            "q_norm": jnp.ones((L, Rq)),
            "wq_b": scaled_init(keys[2], (L, Rq, H * (dn + dr)), fan_in=Rq),
            "wkv_a": scaled_init(keys[3], (L, D, R + dr), fan_in=D),
            "kv_norm": jnp.ones((L, R)),
            "w_uk": scaled_init(keys[4], (L, H, R, dn), fan_in=R),
            "w_uv": scaled_init(keys[5], (L, H, R, dv), fan_in=R),
            "wo": scaled_init(keys[6], (L, H * dv, D), fan_in=H * dv),
        },
        "dense": {
            "mlp_norm": jnp.ones((Ld, D)),
            "w_gate": scaled_init(keys[7], (Ld, D, F), fan_in=D),
            "w_up": scaled_init(keys[8], (Ld, D, F), fan_in=D),
            "w_down": scaled_init(keys[9], (Ld, F, D), fan_in=F),
        },
        "moe": {
            "moe_norm": jnp.ones((Lm, D)),
            "router": scaled_init(keys[10], (Lm, D, E), fan_in=D),
            "expert_bias": truncated_normal_init(keys[11], (Lm, E)),
            "w_gate": scaled_init(keys[12], (Lm, held, D, Fm), fan_in=D),
            "w_up": scaled_init(keys[13], (Lm, held, D, Fm), fan_in=D),
            "w_down": scaled_init(keys[14], (Lm, held, Fm, D), fan_in=Fm),
            "ws_gate": scaled_init(keys[15], (Lm, D, Fm), fan_in=D),
            "ws_up": scaled_init(keys[16], (Lm, D, Fm), fan_in=D),
            "ws_down": scaled_init(keys[17], (Lm, Fm, D), fan_in=Fm),
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": truncated_normal_init(keys[18], (D, cfg.vocab_size)),
    }
    return {"params": params, "state": {}}


def logical_axes(cfg: KimiK2Config) -> Variables:
    del cfg
    return {
        "params": {
            "embed": ("vocab", "embed"),
            "attn": {
                "attn_norm": ("layers", "embed"),
                "wq_a": ("layers", "embed", None),
                "q_norm": ("layers", None),
                "wq_b": ("layers", None, "heads"),
                "wkv_a": ("layers", "embed", None),
                "kv_norm": ("layers", None),
                "w_uk": ("layers", "heads", None, None),
                "w_uv": ("layers", "heads", None, None),
                "wo": ("layers", "heads", "embed"),
            },
            "dense": {
                "mlp_norm": ("layers", "embed"),
                "w_gate": ("layers", "embed", "mlp"),
                "w_up": ("layers", "embed", "mlp"),
                "w_down": ("layers", "mlp", "embed"),
            },
            "moe": {
                "moe_norm": ("layers", "embed"),
                "router": ("layers", "embed", None),
                "expert_bias": ("layers", None),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
                "ws_gate": ("layers", "embed", "mlp"),
                "ws_up": ("layers", "embed", "mlp"),
                "ws_down": ("layers", "mlp", "embed"),
            },
            "final_norm": ("embed",),
            "lm_head": ("embed", "vocab"),
        },
        "state": {},
    }


# Leaves read at float32: the norm gains, and the router with its bias
# (the scores decide a top-k, so that matmul is float32 at full
# precision, as the other routed families'). The rest are read at
# ``cfg.dtype`` and a server holds them so (``common.served_params``).
READ_AT_FLOAT32 = frozenset(
    {"attn_norm", "q_norm", "kv_norm", "mlp_norm", "moe_norm", "final_norm",
     "router", "expert_bias"})

# Held ``[.., N, D]`` by a server: the one projection whose product is
# split into heads (``common.project``; llama's table says why).
HELD_TRANSPOSED = frozenset({"wq_b"})


# ------------------------------------------------------------ the layers
def _queries(cfg: KimiK2Config, layer: dict, h: jax.Array,
             positions: jax.Array):
    """(q_nope [B, S, H, dn], q_pe [B, S, H, dr] after its rotation)."""
    dt = cfg.dtype
    B, S, _ = h.shape
    dn = cfg.qk_nope_head_dim
    c_q = rms_norm(h @ _w(layer["wq_a"], dt), layer["q_norm"], cfg.norm_eps)
    q = project(layer, "wq_b", c_q, dt).reshape(B, S, cfg.n_heads, -1)
    return q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta,
                             cfg.rope_scaling)


def _latent(cfg: KimiK2Config, layer: dict, h: jax.Array,
            positions: jax.Array) -> jax.Array:
    """What a layer caches of ``h`` [B, S, D]: ``c_kv`` after its norm,
    the shared rotary key after its rotation, zeros up to `latent_pad`:
    [B, S, W]."""
    R = cfg.kv_lora_rank
    kv = h @ _w(layer["wkv_a"], cfg.dtype)
    c_kv = rms_norm(kv[..., :R], layer["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, R:], positions, cfg.rope_theta,
                cfg.rope_scaling)[..., 0, :]
    pad = jnp.zeros((*kv.shape[:-1], cfg.latent_pad - cfg.latent_width),
                    kv.dtype)
    return jnp.concatenate([c_kv, k_pe, pad], axis=-1)


def _absorbed_queries(cfg: KimiK2Config, layer: dict, q_nope: jax.Array,
                      q_pe: jax.Array) -> jax.Array:
    """Each head's query as wide as the latent, [..., H, W]: ``q_nope ·
    W_UK^T`` over c_kv's columns, ``q_pe`` over the rotary key's, zeros
    over the padding."""
    q_lat = jnp.einsum("...hn,hcn->...hc", q_nope,
                       _w(layer["w_uk"], cfg.dtype))
    pad = jnp.zeros((*q_pe.shape[:-1], cfg.latent_pad - cfg.latent_width),
                    q_pe.dtype)
    return jnp.concatenate([q_lat, q_pe, pad], axis=-1)


def _out(cfg: KimiK2Config, layer: dict, x: jax.Array,
         o_lat: jax.Array) -> jax.Array:
    """The residual behind absorbed attention: ``o_lat`` [B, S, H, R],
    the probability-weighted c_kv a head, through ``W_UV`` and ``W_o``."""
    dt = cfg.dtype
    o = jnp.einsum("bshc,hcn->bshn", o_lat, _w(layer["w_uv"], dt))
    return x + o.reshape(*o.shape[:2], -1) @ _w(layer["wo"], dt)


def _attend_whole(cfg: KimiK2Config, layer: dict, x: jax.Array,
                  q_nope: jax.Array, q_pe: jax.Array,
                  latent: jax.Array) -> jax.Array:
    """Causal attention of a sequence over itself in the up-projected
    form: every head's keys and values made from the latent, flash
    attention on a TPU where the sequence tiles (module docstring: both
    head sizes padded to one), the einsum reference elsewhere."""
    dt = cfg.dtype
    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    B, S, H, _ = q_nope.shape
    c_kv = latent[..., :R]
    k_nope = jnp.einsum("bsc,hcn->bshn", c_kv, _w(layer["w_uk"], dt))
    v = jnp.einsum("bsc,hcn->bshn", c_kv, _w(layer["w_uv"], dt))
    k_pe = jnp.broadcast_to(latent[:, :, None, R:R + dr], (B, S, H, dr))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    impl = cfg.attention_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "flash":
        from polyaxon_tpu.ops.flash import flash_attention

        wide = -(-max(q.shape[-1], v.shape[-1]) // LANES) * LANES

        def widen(t):
            return jnp.pad(t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),))

        attn = flash_attention(
            widen(q), widen(k), widen(v), causal=True,
            softmax_scale=cfg.softmax_scale)[..., :v.shape[-1]]
    else:
        attn = xla_attention(q, k, v, causal=True,
                             softmax_scale=cfg.softmax_scale)
    return x + attn.reshape(B, S, -1) @ _w(layer["wo"], dt)


def _attend_behind(cfg: KimiK2Config, layer: dict, x: jax.Array,
                   q_nope: jax.Array, q_pe: jax.Array, latent: jax.Array,
                   prefix: jax.Array, m) -> jax.Array:
    """Attention of a suffix [B, S] at positions m..m+S−1 over a cached
    prefix ``prefix`` [B, Mpad, W] (columns at or past ``m`` padding)
    and itself, in the absorbed form: one run of keys [prefix; suffix],
    `KEY_BLOCK` a turn under an online softmax in float32."""
    R = cfg.kv_lora_rank
    B, S, H, _ = q_nope.shape
    Mpad = prefix.shape[1]
    q = _absorbed_queries(cfg, layer, q_nope, q_pe)  # [B, S, H, W]
    keys = jnp.concatenate([prefix.astype(latent.dtype), latent], axis=1)
    T = keys.shape[1]
    block = min(KEY_BLOCK, T)
    n = -(-T // block)
    keys = jnp.pad(keys, ((0, 0), (0, n * block - T), (0, 0)))
    col = jnp.arange(n * block)
    # A prefix column j is position j, real below m; a suffix column is
    # position m + (j − Mpad); what pads the last block is nobody's.
    key_pos = jnp.where(col < Mpad, col, m + col - Mpad)
    key_ok = jnp.where(col < Mpad, col < m, col < T)
    q_pos = m + jnp.arange(S)
    scale = cfg.softmax_scale

    def turn(carry, inputs):
        acc, top, total = carry
        c, pos, ok = inputs  # [B, block, W], [block], [block]
        mask = (ok[None, :] & (pos[None, :] <= q_pos[:, None]))[None, None]
        s = jnp.einsum("bshw,btw->bhst", q, c).astype(jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        new_top = jnp.maximum(top, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - new_top[..., None]), 0.0)
        alpha = jnp.exp(top - new_top)
        total = total * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhst,btc->bhsc", p.astype(c.dtype), c[..., :R])
        acc = acc * alpha[..., None] + pv.astype(jnp.float32)
        return (acc, new_top, total), None

    init = (jnp.zeros((B, H, S, R), jnp.float32),
            jnp.full((B, H, S), NEG_INF, jnp.float32),
            jnp.zeros((B, H, S), jnp.float32))
    (acc, _, total), _ = jax.lax.scan(
        turn, init,
        (keys.reshape(B, n, block, -1).swapaxes(0, 1),
         key_pos.reshape(n, block), key_ok.reshape(n, block)))
    o_lat = (acc / total[..., None]).astype(cfg.dtype).swapaxes(1, 2)
    return _out(cfg, layer, x, o_lat)


def _mla_sequence(cfg: KimiK2Config, layer: dict, x: jax.Array, i: int,
                  behind: plan.Behind):
    """Layer ``i``'s attention over a sequence behind what
    ``behind.carried["latent"]`` [L, B, Mpad, W] holds of its prefix
    (Mpad 0: behind nothing). Keeps the sequence's own latent."""
    h = llama._norm(cfg, x, layer["attn_norm"])
    q_nope, q_pe = _queries(cfg, layer, h, behind.positions)
    latent = _latent(cfg, layer, h, behind.positions)
    prefix = behind.carried["latent"]
    if prefix.shape[2] == 0:
        x = _attend_whole(cfg, layer, x, q_nope, q_pe, latent)
    else:
        x = _attend_behind(cfg, layer, x, q_nope, q_pe, latent, prefix[i],
                           behind.positions[0, 0])
    return x, {"latent": latent}


def _mla_step(cfg: KimiK2Config, layer: dict, x: jax.Array,
              positions: jax.Array, put, read):
    """One position a row ([B, 1, D]) in the absorbed form:
    ``put(latent [B, W])`` writes the step's latent into the cache,
    ``read(q [B, H, W]) -> [B, H, R]`` is the attention over it."""
    h = llama._norm(cfg, x, layer["attn_norm"])
    q_nope, q_pe = _queries(cfg, layer, h, positions)
    put(_latent(cfg, layer, h, positions)[:, 0])
    q = _absorbed_queries(cfg, layer, q_nope, q_pe)[:, 0]
    return _out(cfg, layer, x, read(q).astype(cfg.dtype)[:, None])


# The expert block is ``models/moe.py``'s, shared with
# ``models/exaone_moe.py``: sigmoid scores and a selection bias over
# every expert, the held ones' part of the routed sum, one shared
# expert.
routed_experts = moe.deepseek_routed_experts
expert_block = moe.deepseek_expert_block


def init_rows(cfg: KimiK2Config, rows: int) -> dict:
    """What ``rows`` sequences stand behind when they stand behind
    nothing: an empty prefix of latents."""
    return {"latent": jnp.zeros((cfg.n_layers, rows, 0, cfg.latent_pad),
                                cfg.dtype)}


FAMILY = plan.Family(
    name=__name__, configs=CONFIGS, init=init,
    logical_axes=logical_axes, layers=_layers,
    mixers={"mla": plan.Mixer("attn", _mla_sequence, None, "mla")},
    ffns={"dense": plan.DENSE,
          "moe": plan.Ffn(None, lambda cfg, params, i, x, _: expert_block(
              cfg, params["moe"], i, x))},
    init_rows=init_rows)

forward = functools.partial(plan.forward, FAMILY)
cb_admission, cb_validate = llama.cb_admission, llama.cb_validate
insert_cache_row = plan.insert_cache_row
apply = functools.partial(plan.apply, FAMILY)
model_def = functools.partial(plan.model_def, FAMILY)


def _stacked_latent(kept: dict) -> jax.Array:
    return jnp.stack(kept["latent"])  # [L, B, S, W]


# ------------------------------------------------------- dense slot cache
def init_cache(cfg: KimiK2Config, batch: int, max_len: int) -> dict:
    """The slot cache: every layer's latent [L, B, C, W], slot ==
    position."""
    return {"latent": jnp.zeros((cfg.n_layers, batch, max_len,
                                 cfg.latent_pad), cfg.dtype)}


cb_init_cache = init_cache


def prefill(cfg: KimiK2Config, params: dict, prompt: jax.Array,
            max_len: int):
    """One pass over the prompt [B, P]: (last-position logits [B, V]
    fp32, the slot cache holding it)."""
    P = prompt.shape[1]
    if P > max_len:
        raise ValueError(f"prompt length {P} exceeds cache length {max_len}")
    x, _, _, kept = plan.sequence_layers(FAMILY, cfg, params, prompt)
    latent = jnp.pad(_stacked_latent(kept),
                     ((0, 0), (0, 0), (0, max_len - P), (0, 0)))
    return plan._head(cfg, params, x[:, -1]), {"latent": latent}


cb_prefill = functools.partial(plan.cb_prefill, prefill)


def decode_step_ragged(cfg: KimiK2Config, params: dict, cache: dict,
                       tokens: jax.Array, pos: jax.Array):
    """One step with per-row positions ([B], −1 = idle) over the slot
    cache, absorbed: the scores and the value read over the row's own
    latents [C, W]."""
    C = cache["latent"].shape[2]
    R = cfg.kv_lora_rank
    positions, slot, valid = llama.ragged_cache_coords(pos, C)
    rows = jnp.arange(tokens.shape[0])
    held = {"latent": cache["latent"]}

    def attend(_, i, layer, x):
        def put(latent):
            held["latent"] = put_layer(
                held["latent"],
                held["latent"][i].at[rows, slot].set(latent), i)

        def read(q):
            c = held["latent"][i]  # [B, C, W]
            s = jnp.einsum("bhw,btw->bht", q, c).astype(jnp.float32)
            s = jnp.where(valid[:, 0], s * cfg.softmax_scale, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            return jnp.einsum("bht,btc->bhc", p, c[..., :R])

        return _mla_step(cfg, layer, x, positions, put, read)

    logits, _, _ = plan.decode(FAMILY, cfg, params, tokens, pos, attend,
                               {}, {})
    return logits, held


decode_step = functools.partial(plan.decode_step, decode_step_ragged)
generate = functools.partial(llama.generate_loop, prefill, decode_step)


# ------------------------------------------------------------ paged cache
def paged_init_cache(cfg: KimiK2Config, n_pages: int, page_size: int) -> dict:
    """The latent pages [L, P, 1, page, W] (module docstring), the
    decode steps' routed pairs as ``plan.paged_init_cache`` keeps them,
    and the positions their attention read."""
    n_moe = cfg.n_layers - cfg.first_dense
    cache = {
        "latent": jnp.zeros((cfg.n_layers, n_pages, 1, page_size,
                             cfg.latent_pad), cfg.dtype),
        "moe_expert_tokens": jnp.zeros((n_moe, cfg.held[1]), jnp.int32),
        "mla_decode_positions": jnp.zeros((2,), jnp.int32)}
    if cfg.held_experts:
        cache["moe_pairs_elsewhere"] = jnp.zeros((n_moe,), jnp.int32)
    return cache


def _count_positions(counter: jax.Array, pos: jax.Array) -> jax.Array:
    """``counter`` (multiples of 2³⁰, remainder) plus the positions a
    step's live rows attend, 0..pos each."""
    low = counter[1] + jnp.sum(jnp.where(pos >= 0, pos + 1, 0))
    return jnp.stack([counter[0] + (low >> 30), low & ((1 << 30) - 1)])


def decode_step_paged(cfg: KimiK2Config, params: dict, cache: dict,
                      tokens: jax.Array, pos: jax.Array, tables: jax.Array):
    """`decode_step_ragged` over the page pool: row b's latents in its
    pages, the step's written by whole pages (``llama.paged_write_step``)
    and read by ``ops/mla_decode.py``'s kernel on a TPU, by the same
    sums over gathered pages elsewhere."""
    R = cfg.kv_lora_rank
    page = cache["latent"].shape[-2]
    positions, write_page, write_off, _ = llama.paged_coords(pos, tables,
                                                             page)
    impl = cfg.paged_attention_impl
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu"
                and R % LANES == 0 else "gather")
    attention = (mla_decode_attention if impl == "pallas"
                 else mla_decode_reference)
    held = {"latent": cache["latent"]}

    def attend(_, i, layer, x):
        def put(latent):
            held["latent"] = llama.paged_write_step(
                held["latent"], i, latent[:, None, :], write_page, write_off)

        def read(q):
            return attention(q, held["latent"], i, tables, pos,
                             scale=cfg.softmax_scale, value_width=R)

        return _mla_step(cfg, layer, x, positions, put, read)

    logits, _, counters = plan.decode(FAMILY, cfg, params, tokens, pos,
                                      attend, {}, plan.counters_of(cache))
    return logits, {
        **held, **counters, "mla_decode_positions": _count_positions(
            cache["mla_decode_positions"], pos)}


def _as_pages(latent: jax.Array) -> jax.Array:
    """[L, S, W] as llama's page writes take K or V: [L, S, 1, W]."""
    return latent[:, :, None, :]


def paged_prefill_kv(cfg: KimiK2Config, params: dict, prompt: jax.Array):
    """The prompt pass for one row [1, P], padded to whole flash tiles
    (causal: what lies behind the prompt changes nothing in it): the
    latents [L, P, 1, W] for `paged_insert_prefill`."""
    P = prompt.shape[1]
    padded = jnp.pad(prompt, ((0, 0), (0, -P % PREFILL_TILE)))
    _, _, _, kept = plan.sequence_layers(FAMILY, cfg, params, padded)
    return (_as_pages(_stacked_latent(kept)[:, 0, :P]),)


def paged_insert_prefill(cache: dict, latent: jax.Array,
                         page_ids: jax.Array, page_size: int) -> dict:
    """A prefilled row's latents into its pages, by whole pages
    (``llama.paged_write_span``)."""
    del page_size
    return {**cache, "latent": llama.paged_write_span(
        cache["latent"], latent, page_ids, 0)}


def paged_gather_prefix(cache: dict, page_ids: jax.Array) -> tuple:
    """What a suffix prefill reads of the matched pages ``page_ids``
    ([n], clamped to real ids, in chain order): their latents,
    token-major [L, n·page, W]."""
    return (llama.paged_gather(cache["latent"], page_ids)[:, :, 0],)


def paged_prefill_suffix_kv(cfg: KimiK2Config, params: dict,
                            suffix: jax.Array, prefix: jax.Array, m):
    """The tail ``suffix`` [1, S] of a prompt whose first ``m`` tokens'
    latents exist (`paged_gather_prefix`; columns at or past ``m`` are
    masked): the tail's latents [L, S, 1, W] for `paged_insert_suffix`."""
    _, _, _, kept = plan.sequence_layers(
        FAMILY, cfg, params, suffix, None, None,
        {"latent": prefix[:, None]}, m)
    return (_as_pages(_stacked_latent(kept)[:, 0]),)


def paged_insert_suffix(cache: dict, latent: jax.Array, page_ids: jax.Array,
                        start, page_size: int, real_len=None) -> dict:
    del page_size
    return {**cache, "latent": llama.paged_write_span(
        cache["latent"], latent, page_ids, start, real_len)}
