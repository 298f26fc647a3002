"""K-EXAONE-style decoder: rotary sliding-window attention in three
layers of four beside full attention without positions, q and k
normalised a head, a dense SwiGLU MLP in the leading layer and behind it
sigmoid-routed SwiGLU experts beside one shared expert.

The published ``exaone_moe`` architecture (LGAI-EXAONE/K-EXAONE-236B-A23B
``config.json``). With ``rms(x, g) = x / sqrt(mean(x²) + eps) · g``,
layer ``l`` is::

    h  = rms(x, attn_norm_l)
    q, k, v = h · W_q, h · W_k, h · W_v            # H, KV, KV heads of Hd
    q, k = rms(q, q_norm_l), rms(k, k_norm_l)      # over a head's Hd values
    if window_layout[l]:  q, k = rope(q), rope(k)  # the whole head
    a  = causal softmax(q·k / √Hd), keys within the last `sliding_window`
         positions if window_layout[l]; GQA
    x  = x + a · W_o
    g  = rms(x, ffn_norm_l)
    x  = x + W_down(silu(W_gate g) ⊙ W_up g)       # l < first_dense
    x  = x + Σ_{e in top K of sigmoid(g · W_r) + bias} w_e · E_e(g)
           + E_shared(g)                            # the others

and after the last layer ``rms(x, final_norm)`` and the untied head.
``w`` are the chosen experts' scores over their sum, times
``routed_scaling_factor`` (``models/moe.py route``). The published
pattern is ``L L L G``: three window layers (128 positions, rotary) and
one full layer (no position encoding: the family's "global NoPE"). The
model's next-token-prediction module (``num_nextn_predict_layers``) is
no part of this forward pass and is not built.

Nothing here is new mathematics to the tree, and nothing is copied:

- *Attention* is ``models/smallthinker.py``'s two mixers (llama's
  ``_qkv`` with the q/k norm gains this family's layers carry, the
  window and the rotary flag a layer from `layer_plan`), its decode
  over the two page spaces of ``serving/paged.py WindowedPagePool`` and
  its suffix surface behind a shared prefix, bound to this family's
  table. At a window of 128 the suffix program computes ``128 x (window
  layers)`` positions below a match again and nothing else of it.
- *The dense layer* is ``plan.DENSE`` (llama's ``_mlp``).
- *The expert block* is ``models/moe.py deepseek_expert_block``, shared
  with ``models/kimi_k2.py``: the router scores every expert, the chip
  computes those it holds (``held_experts = (first, count)``) and the
  shared expert, and leaves the rest out (``moe_pairs_elsewhere``
  counts them).

The walks over the plan are ``models/plan.py``'s (`FAMILY`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama, moe, plan, smallthinker
from polyaxon_tpu.models.common import (
    Variables,
    scaled_init,
    truncated_normal_init,
)

SEQ2SEQ = False
PREFILL_TILE = smallthinker.PREFILL_TILE


@dataclasses.dataclass(frozen=True)
class ExaoneMoEConfig:
    vocab_size: int = 153_600
    dim: int = 6144
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    # Per layer, 1 where the keys are those of the last `sliding_window`
    # positions. None: the published period of four, 1 1 1 0. The rotary
    # embedding turns q and k in those layers and in no other.
    window_layout: Optional[tuple] = None
    sliding_window: int = 128
    ffn_dim: int = 18_432  # the leading dense layers' MLP
    first_dense: int = 1  # how many leading layers are dense
    n_experts: int = 128  # what the router scores
    experts_per_token: int = 8
    moe_ffn_dim: int = 2048  # a routed expert, and the shared one
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    router_score: str = "sigmoid"
    # (first, count) of the routed experts held here; None: all.
    held_experts: Optional[tuple] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 262_144
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"  # the sequence passes': as LlamaConfig's
    paged_attention_impl: str = "auto"  # as LlamaConfig's
    loss_chunk: int = 256
    lm_logits_chunk: int = 4096

    def __post_init__(self):
        layout = self.window_layout
        if layout is None:
            layout = tuple(int(i % 4 != 3) for i in range(self.n_layers))
        if len(layout) != self.n_layers:
            raise ValueError(f"window_layout has {len(layout)} entries for "
                             f"{self.n_layers} layers")
        object.__setattr__(self, "window_layout",
                           tuple(int(v) for v in layout))
        if len(set(self.window_layout)) != 2:
            raise ValueError(
                "an exaone_moe model has window and full layers side by "
                "side; a window in every layer or in none is llama's")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")
        if self.router_score != "sigmoid":
            raise ValueError("an exaone_moe router scores by sigmoid")
        if not 0 <= self.first_dense < self.n_layers:
            raise ValueError("first_dense leaves no expert layer")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} lie outside "
                             f"the {self.n_experts} routed experts")

    @property
    def rope_layout(self) -> tuple:
        """Rotary positions in the window layers, none in the full."""
        return self.window_layout

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts held here."""
        return self.held_experts or (0, self.n_experts)


CONFIGS: dict[str, ExaoneMoEConfig] = {
    "k_exaone_236b_a23b": ExaoneMoEConfig(),
    "exaone_moe_tiny": ExaoneMoEConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=8, n_kv_heads=2,
        head_dim=16, sliding_window=16, ffn_dim=96, first_dense=1,
        n_experts=16, experts_per_token=4, moe_ffn_dim=32, max_seq_len=256),
}


def _layers(cfg: ExaoneMoEConfig) -> tuple:
    """Attention in every layer, stacked over every layer; the leading
    ``first_dense`` layers' FFN is dense, the others' the expert
    block."""
    return tuple(
        (kind, l, "dense", l) if l < cfg.first_dense
        else (kind, l, "moe", l - cfg.first_dense)
        for l, (kind, _, _) in enumerate(smallthinker.layer_plan(cfg)))


def init(cfg: ExaoneMoEConfig, rng: jax.Array) -> Variables:
    """Seeded float32 weights as the zoo draws them (truncated normal,
    1/sqrt(fan_in); the tables std 0.02), norm gains at ones; the
    selection bias, a learned buffer in the published model, is drawn
    around zero (std 0.02) so that it shows in the choice."""
    keys = jax.random.split(rng, 17)
    L, D, H, KV, Hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    Ld, Lm = cfg.first_dense, cfg.n_layers - cfg.first_dense
    E, held = cfg.n_experts, cfg.held[1]
    F, Fm = cfg.ffn_dim, cfg.moe_ffn_dim
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "attn": {
            "attn_norm": jnp.ones((L, D)),
            "wq": scaled_init(keys[1], (L, D, H * Hd), fan_in=D),
            "wk": scaled_init(keys[2], (L, D, KV * Hd), fan_in=D),
            "wv": scaled_init(keys[3], (L, D, KV * Hd), fan_in=D),
            "q_norm": jnp.ones((L, Hd)),
            "k_norm": jnp.ones((L, Hd)),
            "wo": scaled_init(keys[4], (L, H * Hd, D), fan_in=H * Hd),
        },
        "dense": {
            "mlp_norm": jnp.ones((Ld, D)),
            "w_gate": scaled_init(keys[5], (Ld, D, F), fan_in=D),
            "w_up": scaled_init(keys[6], (Ld, D, F), fan_in=D),
            "w_down": scaled_init(keys[7], (Ld, F, D), fan_in=F),
        },
        "moe": {
            "moe_norm": jnp.ones((Lm, D)),
            "router": scaled_init(keys[8], (Lm, D, E), fan_in=D),
            "expert_bias": truncated_normal_init(keys[9], (Lm, E)),
            "w_gate": scaled_init(keys[10], (Lm, held, D, Fm), fan_in=D),
            "w_up": scaled_init(keys[11], (Lm, held, D, Fm), fan_in=D),
            "w_down": scaled_init(keys[12], (Lm, held, Fm, D), fan_in=Fm),
            "ws_gate": scaled_init(keys[13], (Lm, D, Fm), fan_in=D),
            "ws_up": scaled_init(keys[14], (Lm, D, Fm), fan_in=D),
            "ws_down": scaled_init(keys[15], (Lm, Fm, D), fan_in=Fm),
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": truncated_normal_init(keys[16], (D, cfg.vocab_size)),
    }
    return {"params": params, "state": {}}


def logical_axes(cfg: ExaoneMoEConfig) -> Variables:
    del cfg
    return {
        "params": {
            "embed": ("vocab", "embed"),
            "attn": {
                "attn_norm": ("layers", "embed"),
                "wq": ("layers", "embed", "heads"),
                "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"),
                "q_norm": ("layers", None),
                "k_norm": ("layers", None),
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
    {"attn_norm", "q_norm", "k_norm", "mlp_norm", "moe_norm", "final_norm",
     "router", "expert_bias"})

# Leaves a server holds ``[.., N, D]``: the three projections of every
# layer, read by llama's `_qkv` (its table says why).
HELD_TRANSPOSED = llama.HELD_TRANSPOSED


FAMILY = plan.Family(
    name=__name__, configs=CONFIGS, init=init,
    logical_axes=logical_axes, layers=_layers,
    mixers=smallthinker.attention_mixers(),
    ffns={"dense": plan.DENSE,
          "moe": plan.Ffn(None, lambda cfg, params, i, x, _:
                          moe.deepseek_expert_block(cfg, params["moe"], i, x))},
    init_rows=lambda cfg, rows: {})

# The engine's names (``serving/batching.py`` finds a surface by
# ``hasattr``): `plan`'s walks and ``models/smallthinker.py``'s window
# surface over this family's table. The slot cache holds K/V [L, B, C,
# KV, Hd], every layer at the full length.
forward = functools.partial(plan.forward, FAMILY)
init_cache = cb_init_cache = functools.partial(plan.init_cache, FAMILY)
prefill = functools.partial(plan.prefill, FAMILY)
cb_prefill = functools.partial(plan.cb_prefill, prefill)
insert_cache_row = plan.insert_cache_row
cb_admission, cb_validate = llama.cb_admission, llama.cb_validate
apply = functools.partial(plan.apply, FAMILY)
model_def = functools.partial(plan.model_def, FAMILY)
decode_step_ragged = functools.partial(
    smallthinker.window_decode_step_ragged, FAMILY)
decode_step = functools.partial(plan.decode_step, decode_step_ragged)
generate = functools.partial(llama.generate_loop, prefill, decode_step)

# ------------------------------------------------------------ paged cache
paged_window = smallthinker.paged_window


def paged_init_cache(cfg: ExaoneMoEConfig, n_pages: int, page_size: int,
                     window_pages: int) -> dict:
    """The two page spaces and the decode steps' routed pairs: by expert
    held here and, where the config names a share of the experts, by
    layer those routed elsewhere (``plan.paged_init_cache``'s
    counters)."""
    n_moe = cfg.n_layers - cfg.first_dense
    cache = {
        **smallthinker.window_page_spaces(cfg, n_pages, page_size,
                                          window_pages),
        "moe_expert_tokens": jnp.zeros((n_moe, cfg.held[1]), jnp.int32)}
    if cfg.held_experts:
        cache["moe_pairs_elsewhere"] = jnp.zeros((n_moe,), jnp.int32)
    return cache


decode_step_paged = functools.partial(
    smallthinker.window_decode_step_paged, FAMILY)
paged_prefill_kv = functools.partial(
    smallthinker.window_paged_prefill_kv, FAMILY)
paged_insert_prefill = smallthinker.paged_insert_prefill
paged_gather_prefix = smallthinker.paged_gather_prefix
paged_prefill_suffix_kv = functools.partial(
    smallthinker.window_paged_prefill_suffix_kv, FAMILY)
paged_insert_suffix = smallthinker.paged_insert_suffix
