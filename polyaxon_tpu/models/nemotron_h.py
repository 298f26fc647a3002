"""Nemotron-H-style hybrid decoder: Mamba-2 layers beside grouped-query
attention and latent expert layers, each layer one mixer alone.

The published ``nemotron_h`` architecture (nvidia/NVIDIA-Nemotron-3-
Super-120B-A12B-BF16 ``config.json``). With ``rms(x, g) = x /
sqrt(mean(x²) + eps) · g``, layer ``l`` is ``x ← x + Mixer_l(rms(x,
norm_l))``, the mixer named by character ``l`` of
``hybrid_override_pattern``; after the last layer ``rms(x, norm_f)``
and the untied head.

- ``M``: the Mamba-2 mixer (``ops/mamba2.py``): what a sequence carries
  between tokens is the state ``S`` [H, P, N] float32 and the last K−1
  inputs of its convolution.
- ``*``: attention, q/k/v/o without bias, causal GQA softmax, **no
  rotary embedding** (``rope_theta`` None): llama's bodies.
- ``E``: the latent expert layer. ``s = sigmoid(u · W_r)`` over every
  routed expert in float32; chosen by ``top_k(s + bias)``, weighted by
  ``s`` alone, normalised over the chosen, times
  ``routed_scaling_factor`` (``models/moe.py route``); ``ℓ = u ·
  W_down`` to the latent width; ``r = Σ_k w_k · W2_e(relu(W1_e ℓ)²)``;
  ``out = r · W_up + Ws2(relu(Ws1 u)²)``, the shared expert on the full
  width.

**The chip's share of the experts.** ``held_experts = (first, count)``
names the routed experts whose weights this chip holds (``w1``/``w2``
are ``[L_moe, count, ...]``). The layer routes over all ``n_experts``
with the published router; a (token, choice) pair whose expert lies
elsewhere adds nothing here, and the partial sum goes up through
``W_up`` and on to the next layer. No code stands in for the absent
chips or their exchange. A decode step dispatches its rows through the
one-hot buffers at the no-drop capacity (``moe.dense_dispatch``), a
sequence through sorted pairs and grouped matmuls
(``moe.sorted_dispatch``: two a layer over the stacks handed whole, on
a TPU ``ops/grouped_matmul.py``'s kernel, which reads each expert that
holds a pair once; elsewhere ``jax.lax.ragged_dot``).

**Layers of different kinds.** Parameters are stacked by kind
(``attn``, ``ssm``, ``moe``) and a static plan (`layer_plan`) walks the
pattern.

**The cache.** ``k``/``v`` hold the attention layers' pages ``[L_attn,
P, KV, page, Hd]``; the Mamba-2 layers' state is *per row*, not per
page (a layer's state is 4 MB a sequence): ``rows`` holds ``ssm``
``[L_ssm, rows, H, P, N]`` float32 and ``conv`` ``[L_ssm, rows, K−1,
conv_dim]``, indexed by the engine's row. A decode step reads and
writes each live row's state in place; a prefill writes its row's; a
radix match has no state to resume from, so the pool matches nothing
for such a cache (``serving/paged.py``). ``moe_expert_tokens``
``[L_moe, count]`` counts the decode steps' (row, choice) pairs by held
expert, ``moe_pairs_elsewhere`` ``[L_moe]`` those routed to experts
this chip does not hold.

The walks over the plan and the engine's surfaces are ``models/
plan.py``'s, bound below to this family's table (`FAMILY`). Speculation
and chunked dense prefill need ``decode_chunk``, which this family does
not have (the state has no rollback), and the engine refuses them by
that.
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
    rms_norm,
    scaled_init,
    truncated_normal_init,
)
from polyaxon_tpu.ops import mamba2

SEQ2SEQ = False

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    dim: int = 4096
    # "M" Mamba-2 | "*" attention | "E" experts, per layer.
    pattern: str = PUBLISHED_PATTERN
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    n_experts: int = 512  # what the router scores
    experts_per_token: int = 22
    moe_latent_dim: int = 1024
    moe_ffn_dim: int = 2688  # per routed expert, on the latent width
    shared_ffn_dim: int = 5376  # the shared expert, on the full width
    # (first, count) of the routed experts held here; None: all.
    held_experts: Optional[tuple] = None
    # The router (models/moe.py `route`).
    router_score: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    # The seeded draw of dt_bias (Mamba-2's: Δ log-uniform between).
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rope_theta: Optional[float] = None  # no rotary embedding
    max_seq_len: int = 262_144
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    paged_attention_impl: str = "auto"  # as LlamaConfig's
    loss_chunk: int = 256
    lm_logits_chunk: int = 4096

    def __post_init__(self):
        unknown = set(self.pattern) - set("M*E")
        if unknown or not self.pattern:
            raise ValueError(f"pattern `{self.pattern}` names layers other "
                             "than M (Mamba-2), * (attention), E (experts)")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_heads is not a multiple of ssm_groups")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} lie outside "
                             f"the {self.n_experts} routed experts")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts held here."""
        return self.held_experts or (0, self.n_experts)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


CONFIGS: dict[str, NemotronHConfig] = {
    "nemotron3_super_120b_a12b": NemotronHConfig(),
    "nemotron_h_tiny": NemotronHConfig(
        vocab_size=256, dim=64, pattern="M*EME", n_heads=4, n_kv_heads=2,
        head_dim=16, ssm_heads=8, ssm_head_dim=8, ssm_state=16,
        ssm_groups=2, chunk_size=8, n_experts=16, experts_per_token=4,
        moe_latent_dim=32, moe_ffn_dim=48, shared_ffn_dim=96,
        max_seq_len=128),
}

_KINDS = {"M": "ssm", "*": "attn", "E": "moe"}


def _kinds(cfg: NemotronHConfig) -> tuple:
    return tuple(_KINDS[char] for char in cfg.pattern)


def layer_plan(cfg: NemotronHConfig) -> tuple:
    """Per layer, in published order: (kind, its index in that kind's
    stack)."""
    return plan.indexed(_kinds(cfg))


def kind_counts(cfg: NemotronHConfig) -> dict:
    return plan.kind_counts(_kinds(cfg), tuple(_KINDS.values()))


def init(cfg: NemotronHConfig, rng: jax.Array) -> Variables:
    """Seeded float32 weights, stacked by kind. Projections as the zoo
    draws them (truncated normal, 1/sqrt(fan_in); the tables std 0.02).
    What the published model learns as small vectors is drawn so that
    each shows in the result: ``A_log = log(A)``, A uniform in [1, 16),
    and ``dt_bias`` the inverse softplus of a Δ log-uniform in
    [time_step_min, time_step_max) (both Mamba-2's own initialisation);
    ``D`` around one and ``expert_bias`` around zero (std 0.02)."""
    keys = jax.random.split(rng, 22)
    n = kind_counts(cfg)
    D, H, KV, Hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    La, Ls, Le = n["attn"], n["ssm"], n["moe"]
    Hs, K = cfg.ssm_heads, cfg.conv_kernel
    d_in, conv_dim = cfg.ssm_inner, cfg.conv_dim
    E, held = cfg.n_experts, cfg.held[1]
    Dl, F, Fs = cfg.moe_latent_dim, cfg.moe_ffn_dim, cfg.shared_ffn_dim
    step = jnp.exp(
        jax.random.uniform(keys[9], (Ls, Hs))
        * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
        + math.log(cfg.time_step_min))
    step = jnp.maximum(step, cfg.time_step_floor)
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "attn": {
            "attn_norm": jnp.ones((La, D)),
            "wq": scaled_init(keys[1], (La, D, H * Hd), fan_in=D),
            "wk": scaled_init(keys[2], (La, D, KV * Hd), fan_in=D),
            "wv": scaled_init(keys[3], (La, D, KV * Hd), fan_in=D),
            "wo": scaled_init(keys[4], (La, H * Hd, D), fan_in=H * Hd),
        },
        "ssm": {
            "ssm_norm": jnp.ones((Ls, D)),
            "w_in": scaled_init(keys[5], (Ls, D, d_in + conv_dim + Hs),
                                fan_in=D),
            "conv_w": scaled_init(keys[6], (Ls, conv_dim, K), fan_in=K),
            "conv_b": truncated_normal_init(keys[7], (Ls, conv_dim)),
            "A_log": jnp.log(jax.random.uniform(
                keys[8], (Ls, Hs), minval=1.0, maxval=16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "D": 1.0 + truncated_normal_init(keys[10], (Ls, Hs)),
            "gate_norm": jnp.ones((Ls, d_in)),
            "w_out": scaled_init(keys[11], (Ls, d_in, D), fan_in=d_in),
        },
        "moe": {
            "moe_norm": jnp.ones((Le, D)),
            "router": scaled_init(keys[12], (Le, D, E), fan_in=D),
            "expert_bias": truncated_normal_init(keys[13], (Le, E)),
            "w_latent_down": scaled_init(keys[14], (Le, D, Dl), fan_in=D),
            "w_latent_up": scaled_init(keys[15], (Le, Dl, D), fan_in=Dl),
            "w1": scaled_init(keys[16], (Le, held, Dl, F), fan_in=Dl),
            "w2": scaled_init(keys[17], (Le, held, F, Dl), fan_in=F),
            "ws1": scaled_init(keys[18], (Le, D, Fs), fan_in=D),
            "ws2": scaled_init(keys[19], (Le, Fs, D), fan_in=Fs),
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": truncated_normal_init(keys[20], (D, cfg.vocab_size)),
    }
    return {"params": params, "state": {}}


def logical_axes(cfg: NemotronHConfig) -> Variables:
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
            },
            "ssm": {
                "ssm_norm": ("layers", "embed"),
                "w_in": ("layers", "embed", "mlp"),
                "conv_w": ("layers", "mlp", None),
                "conv_b": ("layers", "mlp"),
                "A_log": ("layers", None),
                "dt_bias": ("layers", None),
                "D": ("layers", None),
                "gate_norm": ("layers", "mlp"),
                "w_out": ("layers", "mlp", "embed"),
            },
            "moe": {
                "moe_norm": ("layers", "embed"),
                "router": ("layers", "embed", None),
                "expert_bias": ("layers", None),
                "w_latent_down": ("layers", "embed", None),
                "w_latent_up": ("layers", None, "embed"),
                "w1": ("layers", "expert", None, "mlp"),
                "w2": ("layers", "expert", "mlp", None),
                "ws1": ("layers", "embed", "mlp"),
                "ws2": ("layers", "mlp", "embed"),
            },
            "final_norm": ("embed",),
            "lm_head": ("embed", "vocab"),
        },
        "state": {},
    }


# Leaves read at float32: every norm gain, the recurrence's own vectors
# and the convolution's taps and bias (``ops/mamba2.py`` reads them with
# ``.astype(float32)``: the taps are summed in float32), and the
# router with its bias (the scores decide a top-k, so that matmul is
# float32 at full precision, as lfm2's). The rest are read at
# ``cfg.dtype`` and a server holds them so (``common.served_params``).
READ_AT_FLOAT32 = frozenset(
    {"attn_norm", "ssm_norm", "gate_norm", "moe_norm", "final_norm",
     "A_log", "dt_bias", "D", "conv_w", "conv_b", "router", "expert_bias"})

# Leaves a server holds ``[.., N, D]``: the attention layers' three
# projections, read by llama's `_qkv` (its table says why). Mamba-2's
# projections and the experts' stream from their stacks as they are.
HELD_TRANSPOSED = llama.HELD_TRANSPOSED


# ------------------------------------------------------------ the layers
def ssm_layer(cfg: NemotronHConfig, layer: dict, x: jax.Array,
              conv_tail: jax.Array, state: jax.Array, real_len=None):
    """The Mamba-2 layer over ``x`` [B, S, D] behind what the sequence
    carries (``ops/mamba2.py mixer``). Returns (x after the residual,
    new convolution tail, new state)."""
    u = rms_norm(x, layer["ssm_norm"], cfg.norm_eps)
    out, tail, state = mamba2.mixer(cfg, layer, u, conv_tail, state,
                                    real_len)
    return x + out, tail, state


def routed_experts(cfg: NemotronHConfig, stack: dict, i: int,
                   tokens: jax.Array, sequence: bool):
    """The held experts' part of the routed sum in expert layer ``i``
    of ``stack`` (``params["moe"]``) for ``tokens`` [T, D] (already
    normalised), back on the full width: (r · W_up [T, D], the held
    choices' one-hot [T, K, count] or None for a sequence)."""
    dt = cfg.dtype
    # The scores decide a top-k, where a rounding flips an expert: the
    # router's own matmul runs in float32 at full precision.
    logits = jnp.dot(tokens.astype(jnp.float32),
                     stack["router"][i].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_idx, top_w, _ = moe.route(cfg, logits, stack["expert_bias"][i])
    latent = tokens @ _w(stack["w_latent_down"][i], dt)
    first = cfg.held[0]
    if sequence:
        routed = moe.sorted_dispatch(latent, top_idx, top_w, None,
                                     stack["w1"], stack["w2"], first, dt,
                                     layer=i)
        onehot = None
    else:
        routed, onehot = moe.dense_dispatch(
            latent, top_idx, top_w, None, stack["w1"][i], stack["w2"][i],
            tokens.shape[0], dt, first=first,
            experts=moe.relu2_expert_ffn)
    return routed @ _w(stack["w_latent_up"][i], dt), onehot


def shared_expert(cfg: NemotronHConfig, stack: dict, i: int,
                  tokens: jax.Array) -> jax.Array:
    dt = cfg.dtype
    hidden = jnp.square(jax.nn.relu(tokens @ _w(stack["ws1"][i], dt)))
    return hidden @ _w(stack["ws2"][i], dt)


def expert_layer(cfg: NemotronHConfig, stack: dict, i: int, x: jax.Array):
    """Expert layer ``i``'s residual over ``x`` [B, S, D], its B·S
    tokens one dispatch group; nothing is dropped. A single position a
    row (a decode step) goes through the one-hot buffers, a sequence
    through sorted pairs. Returns (x after the residual, the held
    choices' one-hot [B·S, K, count] or None)."""
    B, S, D = x.shape
    tokens = rms_norm(x, stack["moe_norm"][i], cfg.norm_eps).reshape(B * S, D)
    routed, onehot = routed_experts(cfg, stack, i, tokens, sequence=S > 1)
    out = routed + shared_expert(cfg, stack, i, tokens)
    return x + out.reshape(B, S, D), onehot


def init_rows(cfg: NemotronHConfig, rows: int) -> dict:
    """What ``rows`` sequences carry through the Mamba-2 layers, zeroed:
    the state, float32, and the convolution's last K−1 inputs."""
    n = kind_counts(cfg)["ssm"]
    return {"ssm": jnp.zeros((n, rows, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((n, rows, cfg.conv_kernel - 1, cfg.conv_dim),
                              cfg.dtype)}


def _ssm_sequence(cfg: NemotronHConfig, layer: dict, x: jax.Array, i: int,
                  behind: plan.Behind):
    x, tail, state = ssm_layer(cfg, layer, x, behind.carried["conv"][i],
                               behind.carried["ssm"][i], behind.real_len)
    return x, {"ssm": state, "conv": tail}


def _ssm_step(cfg: NemotronHConfig, layer: dict, x: jax.Array, i: int,
              rows: dict, started: jax.Array):
    """`ssm_layer` for one position a row over the rows' leaves
    ([L_ssm, rows ≥ B, ...]), layer ``i`` of each updated in place."""
    B = x.shape[0]
    state = jnp.where(started[:, None, None, None], rows["ssm"][i, :B], 0.0)
    tail = jnp.where(started[:, None, None], rows["conv"][i, :B], 0)
    x, tail, state = ssm_layer(cfg, layer, x, tail, state)
    return x, {"ssm": put_layer(rows["ssm"], state, i),
               "conv": put_layer(rows["conv"], tail, i)}


def _layers(cfg: NemotronHConfig) -> tuple:
    """Each layer one mixer alone or one expert layer alone."""
    return tuple((None, None, kind, i) if kind == "moe"
                 else (kind, i, None, None) for kind, i in layer_plan(cfg))


FAMILY = plan.Family(
    name=__name__, configs=CONFIGS, init=init, logical_axes=logical_axes,
    layers=_layers,
    mixers={"attn": plan.ATTENTION,
            "ssm": plan.Mixer("ssm", _ssm_sequence, _ssm_step, None)},
    ffns={"moe": plan.Ffn(None, lambda cfg, params, i, x, _: expert_layer(
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
