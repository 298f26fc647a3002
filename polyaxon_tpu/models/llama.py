"""Llama-3-style decoder-only transformer (the flagship JAXJob model).

Target of the BASELINE north star [B]: "Llama-3-8B, FSDP over ICI on
v5e-64". TPU-first construction:

- stacked layer params + ``lax.scan`` body → one compiled block,
  remat-able per layer (``jax.checkpoint`` policies map to the spec's
  ``remat`` knob);
- GQA attention (RoPE, fp32 softmax) through ``ops.attention`` so the
  impl can swap xla ↔ Pallas flash ↔ ring (context parallel);
- bf16 activations/compute, fp32 master weights, fp32 loss;
- logical axes on every param so FSDP/TP/CP rule tables place them
  (``parallel.sharding``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from polyaxon_tpu.models.common import (
    Batch,
    _embed_rows,
    _w,
    ModelDef,
    Variables,
    chunked_lm_loss,
    lm_logits,
    project,
    rms_norm,
    rope,
    sample_logits,
    scaled_init,
    shift_right,
    truncated_normal_init,
)
from polyaxon_tpu.ops.attention import dot_product_attention


SEQ2SEQ = False  # serving contract: the prompt is continued in place


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    # Llama-3.1-style context-extension scaling (common.rope_frequencies):
    # {"factor", "low_freq_factor", "high_freq_factor",
    #  "original_max_position_embeddings"} or None.
    rope_scaling: Optional[dict] = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # Gemma-convention knobs (all default to the llama convention):
    # norm gains stored as deltas applied as (1 + w); tanh-approx GeGLU
    # instead of SwiGLU; embeddings scaled by sqrt(dim) on read.
    norm_offset: float = 0.0
    mlp_activation: str = "silu"  # silu | gelu_tanh
    scale_embeddings: bool = False
    # Sliding-window (Mistral-style) causal attention: each position
    # attends to its last `sliding_window` tokens. None = full causal.
    sliding_window: Optional[int] = None
    dtype: Any = jnp.bfloat16
    remat: str = "none"  # none | full | dots (checkpoint policy per layer)
    attention_impl: str = "xla"  # xla | flash | ring | ulysses
    # Paged decode attention: "auto" = the Pallas page-streaming kernel
    # on real TPU (ops/paged_attention.py), gather+masked-softmax
    # elsewhere; "gather" / "pallas" force one.
    paged_attention_impl: str = "auto"
    # Flash-kernel tuning (runtime keys flow here via model_overrides):
    # fwd tile sizes and backward implementation ("pallas" | "xla").
    # None (or "auto") = the kernel's own rule from the shapes
    # (flash.auto_blocks; pallas bwd on real TPU).
    # Sweepable per-run from bench.py; setting one with a non-flash
    # attention_impl is an error.
    flash_block_q: Optional[int | str] = None
    flash_block_k: Optional[int | str] = None
    flash_bwd_impl: Optional[str] = None
    # Chunked lm-head loss slab length (peak HBM holds [B, chunk, V]
    # fp32); sweepable alongside the flash tiles.
    loss_chunk: int = 256
    # Vocab-chunk length for QUANTIZED decode logits (common.lm_logits:
    # the scan structure that keeps int8 on decode-loop carries).
    # Bigger chunks = fewer, larger matmuls per step; sweepable on chip
    # via bench_decode --lm-chunk. Ignored for unquantized heads.
    lm_logits_chunk: int = 4096
    # Pipeline parallelism over the `pp` mesh axis (parallel/pipeline.py):
    # >1 splits the layer stack into that many ppermute-chained stages.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 4
    # Double-buffered schedule (parallel/pipeline.py): each tick's
    # stage→stage ppermute carries the PREVIOUS tick's output, so the
    # hop overlaps stage compute. Per-microbatch outputs are identical
    # to the single-buffered schedule; the knob exists for parity
    # drills and as an escape hatch.
    pipeline_double_buffer: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


# Named configs. llama3_8b matches the Llama-3-8B architecture; the
# smaller ones are proxies for single-chip benchmarking and tests.
_LLAMA31_SCALING = {
    "factor": 8.0, "low_freq_factor": 1.0, "high_freq_factor": 4.0,
    "original_max_position_embeddings": 8192,
}

CONFIGS: dict[str, LlamaConfig] = {
    "llama3_8b": LlamaConfig(),
    # Llama-3.1 8B: 128k context via scaled RoPE (public rope_scaling rule).
    "llama31_8b": LlamaConfig(max_seq_len=131_072,
                              rope_scaling=_LLAMA31_SCALING),
    # Mistral-7B architecture: sliding-window attention, 32k context.
    "mistral_7b": LlamaConfig(
        vocab_size=32_000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_dim=14_336, max_seq_len=32_768, rope_theta=10_000.0,
        sliding_window=4096,
    ),
    "llama3_1b": LlamaConfig(
        vocab_size=128_256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        ffn_dim=8192, max_seq_len=8192,
    ),
    "llama_200m": LlamaConfig(
        vocab_size=32_000, dim=1024, n_layers=12, n_heads=16, n_kv_heads=8,
        ffn_dim=2816, max_seq_len=2048, rope_theta=10_000.0,
    ),
    # Llama-3-vocab small model: the speculative DRAFT for llama3_*
    # targets (drafting requires an identical token space; the other
    # small configs carry the 32k vocab).
    "llama3_draft_200m": LlamaConfig(
        vocab_size=128_256, dim=768, n_layers=10, n_heads=12, n_kv_heads=4,
        ffn_dim=2048, max_seq_len=8192,
    ),
    "llama_tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq_len=128, rope_theta=10_000.0,
    ),
    # Tied-embeddings variant (Gemma/Qwen-small convention: lm_head IS
    # embed.T): exercises the transposed head path everywhere —
    # training loss, decode logits, and the quantized serving branch
    # where the [V, D] table must stay int8 on decode-loop carries.
    "llama_tiny_tied": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq_len=128, rope_theta=10_000.0,
        tie_embeddings=True,
    ),
    # Gemma-2B architecture (public config): MQA (1 kv head), GeGLU,
    # (1+w) norms, sqrt(dim)-scaled embeddings, tied head, 256k vocab,
    # rms_norm_eps 1e-6 (the llama default 1e-5 deviates from the
    # published config — ADVICE r5).
    # head_dim = dim / n_heads = 256, matching the published value.
    "gemma_2b": LlamaConfig(
        vocab_size=256_000, dim=2048, n_layers=18, n_heads=8, n_kv_heads=1,
        ffn_dim=16_384, max_seq_len=8192, rope_theta=10_000.0,
        tie_embeddings=True, norm_offset=1.0, mlp_activation="gelu_tanh",
        scale_embeddings=True, norm_eps=1e-6,
    ),
    "gemma_tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=1,
        ffn_dim=128, max_seq_len=128, rope_theta=10_000.0,
        tie_embeddings=True, norm_offset=1.0, mlp_activation="gelu_tanh",
        scale_embeddings=True, norm_eps=1e-6,
    ),
}


def train_flops_per_token(cfg: LlamaConfig, seq: int,
                          param_count: int) -> int:
    """6N for the matmul params (fwd 2N + bwd 4N) plus the causal-
    attention score/value matmuls (6 * n_layers * seq * d_model fwd+bwd
    after halving for causality). Read by ``runtime/flops.py``."""
    return 6 * param_count + 6 * cfg.n_layers * seq * cfg.dim


def init(cfg: LlamaConfig, rng: jax.Array) -> Variables:
    keys = jax.random.split(rng, 10)
    L, D, F = cfg.n_layers, cfg.dim, cfg.ffn_dim
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # Identity-at-init norm gains: weight w applies as (norm_offset + w),
    # so llama (offset 0) initializes ones, Gemma (offset 1) zeros.
    gain = jnp.full((L, D), 1.0 - cfg.norm_offset)
    params = {
        "embed": truncated_normal_init(keys[0], (cfg.vocab_size, D)),
        "layers": {
            "attn_norm": gain,
            "wq": scaled_init(keys[1], (L, D, H * Hd), fan_in=D),
            "wk": scaled_init(keys[2], (L, D, KV * Hd), fan_in=D),
            "wv": scaled_init(keys[3], (L, D, KV * Hd), fan_in=D),
            "wo": scaled_init(keys[4], (L, H * Hd, D), fan_in=H * Hd),
            "mlp_norm": gain,
            "w_gate": scaled_init(keys[5], (L, D, F), fan_in=D),
            "w_up": scaled_init(keys[6], (L, D, F), fan_in=D),
            "w_down": scaled_init(keys[7], (L, F, D), fan_in=F),
        },
        "final_norm": jnp.full((D,), 1.0 - cfg.norm_offset),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = truncated_normal_init(keys[8], (D, cfg.vocab_size))
    return {"params": params, "state": {}}


def logical_axes(cfg: LlamaConfig) -> Variables:
    params = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = ("embed", "vocab")
    return {"params": params, "state": {}}


# Leaves the bodies read at float32 (``rms_norm`` on every gain); all
# others are read through ``_w`` / ``lm_head(...).astype(dt)`` /
# ``_embed_rows`` at ``cfg.dtype``, which is what a server holds them
# in (``common.served_params``, applied by ``serving/server.py
# load_params``). ``q_norm``/``k_norm``: lfm2's attention layers, which
# run these bodies.
READ_AT_FLOAT32 = frozenset(
    {"attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm"})

# Leaves a server holds ``[.., N, D]`` (``common.served_params``): the
# three projections `_qkv` splits into heads. The chip's compiler folds
# that split into the dot and wants the contracted dimension minor in
# the weight; held ``[D, N]`` it copies each layer's slice transposed in
# every decode and prefill program (0.29 ms a step for Mistral's ``wq``
# alone). ``wo``, the MLP's and the head stream straight from the stack
# into their dots as they are and stay out.
HELD_TRANSPOSED = frozenset({"wq", "wk", "wv"})


_rope = rope  # shared impl (models.common.rope)


def _norm(cfg, x: jax.Array, weight: jax.Array) -> jax.Array:
    """Config-routed rms_norm: llama weights apply as w, Gemma-style
    as (1 + w) (cfg.norm_offset). getattr keeps the shared attention
    kernels usable from moe/t5 configs that carry no offset."""
    return rms_norm(x, weight, cfg.norm_eps,
                    offset=getattr(cfg, "norm_offset", 0.0))


def _qk_norm(cfg, layer: dict, q: jax.Array, k: jax.Array):
    """RMSNorm over each head's own dims on q and on k, before RoPE, for
    a layer that carries ``q_norm``/``k_norm`` gains ([Hd]); a layer
    without them (every llama and moe preset) gets q and k back."""
    if "q_norm" not in layer:
        return q, k
    return _norm(cfg, q, layer["q_norm"]), _norm(cfg, k, layer["k_norm"])


def _qkv(cfg, layer: dict, h: jax.Array, positions: jax.Array,
         rotary: bool = True):
    """The three projections of ``h`` [B, T, D] (already normalised) as
    every attention walk below takes them: q [B, T, H, Hd], k and v
    [B, T, KV, Hd], q and k normalised per head where the layer has the
    gains (`_qk_norm`) and turned by the rotary embedding at
    ``positions`` [B, T], and the output gate [B, T, H·Hd] or None.

    Three things are read from what the walk is handed, and a layer or
    a config without them is the plain path: a ``wq`` twice as wide
    holds, for each head, its query and behind it the gate of that
    head's output (`_attn_out`); ``cfg.partial_rotary_factor`` is the
    share of a head's dimensions the rotary embedding turns
    (``common.rope``); a layer that carries ``wq_t`` / ``wk_t`` /
    ``wv_t`` holds the projections ``[N, D]`` as a server does
    (``common.project``: the same product either way).
    ``rotary`` False is a layer without positions in a model whose
    other layers have them (a static layer plan's word, ``models/
    smallthinker.py``): q and k go on unturned."""
    dt = cfg.dtype
    B, T = h.shape[:2]
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = project(layer, "wq", h, dt)
    gate = None
    if q.shape[-1] == 2 * H * Hd:
        q, gate = jnp.split(q.reshape(B, T, H, 2 * Hd), 2, axis=-1)
        gate = gate.reshape(B, T, H * Hd)
    q = q.reshape(B, T, H, Hd)
    k = project(layer, "wk", h, dt).reshape(B, T, KV, Hd)
    v = project(layer, "wv", h, dt).reshape(B, T, KV, Hd)
    q, k = _qk_norm(cfg, layer, q, k)
    scaling = getattr(cfg, "rope_scaling", None)
    factor = getattr(cfg, "partial_rotary_factor", None)
    turned = None if factor is None else int(Hd * factor)
    theta = cfg.rope_theta if rotary else None
    q = _rope(q, positions, theta, scaling, turned)
    k = _rope(k, positions, theta, scaling, turned)
    return q, k, v, gate


def _attn_out(cfg, layer: dict, x: jax.Array, attn: jax.Array,
              gate: Optional[jax.Array]) -> jax.Array:
    """The attention residual: ``x + W_o · attn`` for the heads' outputs
    ``attn`` [B, T, H, Hd], each first scaled by the sigmoid of its gate
    where `_qkv` gave one."""
    dt = cfg.dtype
    B, T = attn.shape[:2]
    attn = attn.reshape(B, T, -1)
    if gate is not None:
        attn = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
    return x + attn @ _w(layer["wo"], dt)


def _act(cfg):
    """MLP gate activation: SwiGLU (silu) or Gemma's tanh-approx GeGLU."""
    kind = getattr(cfg, "mlp_activation", "silu")
    if kind == "silu":
        return jax.nn.silu
    if kind == "gelu_tanh":
        return functools.partial(jax.nn.gelu, approximate=True)
    raise ValueError(f"unknown mlp_activation `{kind}`")


def _embed(cfg, params: dict, tokens: jax.Array, dt) -> jax.Array:
    """Embedding read with the optional Gemma sqrt(dim) scaling —
    every forward/decode path reads through here so the convention
    cannot diverge between prefill and decode."""
    x = _embed_rows(params["embed"], tokens, dt)
    if getattr(cfg, "scale_embeddings", False):
        x = x * jnp.asarray(cfg.dim ** 0.5, dt)
    return x


def _window(cfg) -> Optional[int]:
    """The sliding window, None for full causal attention and for a
    family whose config carries no such field (moe)."""
    return getattr(cfg, "sliding_window", None)


def _head(cfg, params: dict) -> tuple:
    """(the lm-head table as stored, whether it is ``embed`` and so
    [V, D]); a config without ``tie_embeddings`` (moe) is untied."""
    tied = getattr(cfg, "tie_embeddings", False)
    return params["embed"] if tied else params["lm_head"], tied


def _mlp(cfg, x: jax.Array, layer: dict) -> jax.Array:
    """The gated-MLP residual block (norm → act(gate)·up → down),
    shared by the training layer and every decode flavour so the
    convention can never desync between them. It is the default of the
    serving bodies' ``ffn`` argument, ``(cfg, x [B, T, D], layer) -> x``:
    the one seam between the decoder families. ``models/moe.py`` passes
    its expert block and serves through the same bodies."""
    dt = cfg.dtype
    h = _norm(cfg, x, layer["mlp_norm"])
    gate = _act(cfg)(h @ _w(layer["w_gate"], dt))
    up = h @ _w(layer["w_up"], dt)
    return x + (gate * up) @ _w(layer["w_down"], dt)


def _layer(cfg: LlamaConfig, x: jax.Array, layer: dict, positions: jax.Array,
           segment_ids: Optional[jax.Array] = None) -> jax.Array:
    h = _norm(cfg, x, layer["attn_norm"])
    q, k, v, gate = _qkv(cfg, layer, h, positions)
    # dot_product_attention owns the impl support matrix (xla and flash
    # both handle packed segment_ids; ring/ulysses raise).
    attn = dot_product_attention(q, k, v, causal=True,
                                 impl=cfg.attention_impl,
                                 segment_ids=segment_ids,
                                 window=cfg.sliding_window,
                                 block_q=cfg.flash_block_q,
                                 block_k=cfg.flash_block_k,
                                 bwd_impl=cfg.flash_bwd_impl)
    x = _attn_out(cfg, layer, x, attn, gate)

    x = _mlp(cfg, x, layer)
    return x


def _layer_body(cfg: LlamaConfig):
    body = functools.partial(_layer, cfg)
    if cfg.remat == "full":
        body = jax.checkpoint(body, static_argnums=())
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return body


def _pipelined_layers(cfg: LlamaConfig, body, layer_params, x: jax.Array) -> jax.Array:
    """Run the layer stack as a `pp` pipeline (parallel/pipeline.py).

    Assumes contiguous positions 0..S-1 (the pretraining case): each
    microbatch rebuilds them locally instead of threading them through
    the ppermute chain.
    """
    from polyaxon_tpu.parallel.compat import ambient_mesh
    from polyaxon_tpu.parallel.pipeline import pipeline_forward, stack_stages

    mesh = ambient_mesh()
    if mesh is None or "pp" not in mesh.axis_names:
        raise ValueError(
            f"pipeline_stages={cfg.pipeline_stages} needs a mesh with a "
            "`pp` axis in context (`with mesh:`)")
    stacked = stack_stages(layer_params, cfg.pipeline_stages)

    def stage_fn(local_layers, x_mb):
        mb, S, _ = x_mb.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (mb, S))

        def scan_body(carry, layer):
            return body(carry, layer, positions), None

        out, _ = jax.lax.scan(scan_body, x_mb, local_layers)
        return out

    return pipeline_forward(
        mesh, stage_fn, stacked, x,
        n_microbatches=cfg.pipeline_microbatches,
        double_buffer=cfg.pipeline_double_buffer)


def segment_starts(segment_ids: jax.Array) -> jax.Array:
    """Boolean [..., S] marking the first position of each segment."""
    return jnp.concatenate(
        [jnp.ones_like(segment_ids[..., :1], dtype=bool),
         segment_ids[..., 1:] != segment_ids[..., :-1]], axis=-1)


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """Within-segment positions for packed rows: [0,0,0,1,1] → [0,1,2,0,1]."""
    S = segment_ids.shape[-1]
    idx = jnp.arange(S, dtype=jnp.int32)
    starts = jax.lax.cummax(jnp.where(segment_starts(segment_ids), idx, 0),
                            axis=segment_ids.ndim - 1)
    return idx - starts


def hidden_states(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # [B, S] int32 input ids
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,  # [B, S] packed-sequence ids
) -> jax.Array:
    """Token ids → final-norm hidden states [B, S, D] (compute dtype).

    ``segment_ids`` enables packed-sequence pretraining: attention is
    restricted within each segment and RoPE positions restart per
    segment (derived automatically unless ``positions`` is given).
    """
    dt = cfg.dtype
    B, S = tokens.shape
    if cfg.pipeline_stages > 1 and (positions is not None
                                    or segment_ids is not None):
        raise ValueError(
            "the pipelined path assumes contiguous positions 0..S-1 and "
            "cannot honor explicit `positions`/`segment_ids` (packed "
            "sequences / decode offsets); use pipeline_stages=1 for those")
    if positions is None:
        if segment_ids is not None:
            positions = segment_positions(segment_ids)
        else:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed(cfg, params, tokens, dt)

    body = _layer_body(cfg)

    if cfg.pipeline_stages > 1:
        x = _pipelined_layers(cfg, body, params["layers"], x)
    else:
        def scan_body(carry, layer_params):
            return body(carry, layer_params, positions, segment_ids), None

        x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return _norm(cfg, x, params["final_norm"])


def lm_head(cfg: LlamaConfig, params: dict) -> jax.Array:
    """Materialized head table — for OUT-OF-LOOP callers only (prefill,
    training forward). Decode loops must go through ``decode_logits``:
    a quantized table dequantized here is loop-invariant, so XLA
    hoists the full-precision [D, V] table onto the loop carry
    (ADVICE r4 #1; see common.lm_logits)."""
    w, tied = _head(cfg, params)
    if hasattr(w, "dequantize"):
        # Unwrap at consumption (same contract as _w): callers sit
        # inside jit, so the convert+scale fuses into the logits
        # matmul's operand read and int8 stays the HBM format.
        w = w.dequantize()
    return w.T if tied else w


def decode_logits(cfg: LlamaConfig, params: dict, x: jax.Array) -> jax.Array:
    """Hidden states [..., D] → fp32 logits [..., V], safe inside
    decode loops (common.lm_logits keeps a quantized head int8 on the
    loop carry via chunked consumption)."""
    w, tied = _head(cfg, params)
    return lm_logits(x, w, cfg.dtype, transpose=tied,
                     chunk=cfg.lm_logits_chunk)


def forward(
    cfg: LlamaConfig,
    params: dict,
    tokens: jax.Array,  # [B, S] int32 input ids
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Token ids → logits [B, S, vocab]."""
    x = hidden_states(cfg, params, tokens, positions)
    # fp32 logits: the MXU matmul stays bf16; accumulate/softmax in fp32.
    return (x @ lm_head(cfg, params).astype(cfg.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------- decode
def cache_len(cfg: LlamaConfig, max_len: int) -> int:
    """KV-cache length: with a sliding window the cache is a ring buffer
    of `sliding_window` slots (bounded memory for long generations);
    otherwise the full sequence length."""
    window = _window(cfg)
    return max_len if window is None else min(max_len, window)


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> dict:
    """KV cache [L, B, C, KV, Hd] per tensor (C = cache_len), compute dtype."""
    C = cache_len(cfg, max_len)
    shape = (cfg.n_layers, batch, C, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def decode_step(
    cfg: LlamaConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B] int32 current-position token ids
    pos: jax.Array,  # scalar int32 position being written
) -> tuple[jax.Array, dict]:
    """One autoregressive step: returns (logits [B, V] fp32, new cache).

    The cache is addressed as a ring buffer: slot ``pos % C``. With a
    full-length cache this is plain positional indexing; with a
    sliding-window cache (C == window) old entries are overwritten in
    place, so memory stays O(window) for arbitrarily long generations.

    A scalar position is the all-rows-in-lockstep special case of
    ``decode_step_ragged`` — one body, no duplicated decode math.
    """
    B = tokens.shape[0]
    return decode_step_ragged(
        cfg, params, cache, tokens,
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)))


def ragged_cache_coords(pos: jax.Array, C: int):
    """Per-row ring-buffer addressing shared by every cached decode
    path (llama + moe): for rows at positions ``pos`` ([B], -1 = idle)
    over a C-slot ring cache, returns (positions [B,1] for RoPE,
    slot [B] to write, valid [B,1,1,C] attention mask). Slot s holds
    position pos - ((pos - s) mod C) after this write; negative =
    never written. A sliding window needs no extra mask: C <= window
    by cache_len(), so every live slot is inside the band by
    construction."""
    pos_safe = jnp.maximum(pos, 0)
    slot = jnp.mod(pos_safe, C)  # [B]
    delta = jnp.mod(pos_safe[:, None] - jnp.arange(C)[None, :], C)  # [B, C]
    stored = pos_safe[:, None] - delta
    valid = ((stored >= 0) & (pos[:, None] >= 0))[:, None, None, :]
    return pos_safe[:, None], slot, valid


def cached_attn_step(cfg, layer: dict, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, positions: jax.Array,
                     slot: jax.Array, valid: jax.Array, rotary: bool = True):
    """One cached-attention sublayer for ragged decode — the shared
    QKV/RoPE/cache-write/masked-softmax kernel both decoder families
    (llama dense MLP, moe expert FFN) build their decode steps on.
    ``cfg`` needs n_heads/n_kv_heads/head_dim/dtype/norm_eps/rope_*.
    Returns (x after the attention residual, new k_cache, new v_cache).
    """
    from polyaxon_tpu.ops.attention import repeat_kv

    dt = cfg.dtype
    B = x.shape[0]
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_rep = H // KV
    rows = jnp.arange(B)

    h = _norm(cfg, x, layer["attn_norm"])
    q, k, v, gate = _qkv(cfg, layer, h, positions, rotary)
    k_cache = k_cache.at[rows, slot].set(k[:, 0])
    v_cache = v_cache.at[rows, slot].set(v[:, 0])

    keys = repeat_kv(k_cache, n_rep)
    vals = repeat_kv(v_cache, n_rep)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, keys).astype(jnp.float32)
    logits = logits * (Hd ** -0.5)
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vals)
    return _attn_out(cfg, layer, x, attn, gate), k_cache, v_cache


def decode_step_ragged(
    cfg: LlamaConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B] int32 current-position token ids
    pos: jax.Array,  # [B] int32 per-row position being written (-1 = idle)
    ffn=_mlp,
) -> tuple[jax.Array, dict]:
    """One autoregressive step with PER-ROW positions — the kernel under
    continuous batching (serving/batching.py), where each cache slot
    holds a different request at its own depth. Same ring-buffer cache
    semantics as ``decode_step``, addressed per row; idle rows
    (``pos < 0``) write only their own slot-0 entry (overwritten by the
    next admission's prefill insert) and their outputs are ignored by
    the engine. A row at position p matches ``decode_step`` at scalar
    position p exactly."""
    dt = cfg.dtype
    C = cache["k"].shape[2]
    positions, slot, valid = ragged_cache_coords(pos, C)
    x = _embed(cfg, params, tokens, dt)[:, None, :]  # [B, 1, D]

    def layer_step(x, inputs):
        layer, k_cache, v_cache = inputs  # caches [B, C, KV, Hd]
        x, k_cache, v_cache = cached_attn_step(
            cfg, layer, x, k_cache, v_cache, positions, slot, valid)
        x = ffn(cfg, x, layer)
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["layers"], cache["k"], cache["v"]))
    x = _norm(cfg, x, params["final_norm"])
    logits = decode_logits(cfg, params, x[:, 0])
    return logits, {"k": new_k, "v": new_v}


def _prompt_pass(cfg: LlamaConfig, params: dict, prompt: jax.Array,
                 ffn=_mlp):
    """The shared causal prompt sweep: one batched pass over [B, P]
    token ids → (final hidden x [B, P, D], k_all, v_all [L, B, P, KV,
    Hd]). Both prefill flavours (ring-buffer assembly below, raw-KV
    paged insert) build on this one body so the prompt math can never
    diverge between the dense and paged engines."""
    dt = cfg.dtype
    B, P = prompt.shape
    positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (B, P))
    x = _embed(cfg, params, prompt, dt)

    def layer_step(x, layer):
        h = _norm(cfg, x, layer["attn_norm"])
        q, k, v, gate = _qkv(cfg, layer, h, positions)
        attn = dot_product_attention(
            q, k, v, causal=True, impl=cfg.attention_impl,
            window=_window(cfg),
            block_q=getattr(cfg, "flash_block_q", None),
            block_k=getattr(cfg, "flash_block_k", None),
            bwd_impl=getattr(cfg, "flash_bwd_impl", None))
        x = _attn_out(cfg, layer, x, attn, gate)
        x = ffn(cfg, x, layer)
        return x, (k, v)

    x, (k_all, v_all) = jax.lax.scan(layer_step, x, params["layers"])
    return x, k_all, v_all


def prefill(
    cfg: LlamaConfig,
    params: dict,
    prompt: jax.Array,  # [B, P] int32
    max_len: int,
    ffn=_mlp,
) -> tuple[jax.Array, dict]:
    """One batched causal pass over the prompt, filling the KV cache:
    returns (last-position logits [B, V] fp32, cache). O(1) layer sweeps
    instead of P sequential decode steps."""
    dt = cfg.dtype
    B, P = prompt.shape
    Hd = cfg.head_dim
    window = _window(cfg)
    x, k_all, v_all = _prompt_pass(cfg, params, prompt, ffn)
    # Ring-buffer cache assembly: position p lands in slot p % C. With a
    # full-length cache that is the identity; with a sliding-window ring
    # only the last C prompt positions are kept (older ones can never be
    # attended again).
    C = cache_len(cfg, max_len)
    if window is None and P > max_len:
        raise ValueError(
            f"prompt length {P} exceeds cache length {max_len} "
            "(full attention cannot drop prompt positions)")
    if window is not None and C < min(P, window):
        raise ValueError(
            f"cache length {C} (max_len {max_len}) cannot hold the last "
            f"min(P={P}, window={window}) prompt positions "
            "that remain attendable — raise max_len")
    keep = min(P, C)
    if P <= C:
        # Common no-wrap case (slots are 0..P-1): cheap pad, no scatter.
        pad = ((0, 0), (0, 0), (0, C - P), (0, 0), (0, 0))
        cache = {"k": jnp.pad(k_all, pad), "v": jnp.pad(v_all, pad)}
    else:
        pos_kept = jnp.arange(P - keep, P)
        slots = jnp.mod(pos_kept, C)
        zeros = jnp.zeros(
            (cfg.n_layers, B, C, cfg.n_kv_heads, Hd), dtype=k_all.dtype)
        cache = {
            "k": zeros.at[:, :, slots].set(k_all[:, :, P - keep:]),
            "v": zeros.at[:, :, slots].set(v_all[:, :, P - keep:]),
        }
    x = _norm(cfg, x, params["final_norm"])
    logits = (x[:, -1] @ lm_head(cfg, params).astype(dt)).astype(jnp.float32)
    return logits, cache


# ------------------------------------------- continuous batching surface
# Hooks the slot-pool engine (serving/batching.py) drives; moe reuses
# these verbatim (same decoder cache shape and admission semantics).
def cb_validate(cfg, prompt_len: int, max_new: int, max_len: int) -> None:
    """Decoder-only budget rule: prompt and generation share the cache."""
    if prompt_len + max_new > max_len:
        raise ValueError(
            f"prompt {prompt_len} + max_new_tokens {max_new} exceeds "
            f"max_len {max_len}")


def cb_admission(prompt: list) -> tuple:
    """(start position, first decode token, prefill tokens): the last
    prompt token is the first decode input; the rest prefill the cache
    (none for single-token prompts)."""
    return (len(prompt) - 1, prompt[-1],
            list(prompt[:-1]) if len(prompt) > 1 else None)


def cb_init_cache(cfg, slots: int, max_len: int) -> dict:
    return init_cache(cfg, slots, max_len)


def cb_prefill(cfg, params: dict, prompt: jax.Array, max_len: int) -> dict:
    _, cache = prefill(cfg, params, prompt, max_len)
    return cache


def insert_cache_row(cache: dict, row: dict, b) -> dict:
    return {
        key: jax.lax.dynamic_update_slice(
            cache[key], row[key], (0, b, 0, 0, 0))
        for key in ("k", "v")
    }


# ------------------------------------------------- speculative decoding
def decode_chunk(
    cfg: LlamaConfig,
    params: dict,
    cache: dict,  # full-length cache: slot == position (C == max_len)
    tokens: jax.Array,  # [B, c] int32 — c tokens per row
    pos0: jax.Array,  # [B] int32 — position of tokens[:, 0] per row
    ffn=_mlp,
) -> tuple[jax.Array, dict]:
    """Cached forward over a SHORT chunk of c tokens per row (the
    speculative-decoding verify step): writes their KV at positions
    pos0..pos0+c-1 and returns logits [B, c, V] — logits[:, i] predicts
    position pos0+i+1. Requires a full-length cache (slot == position;
    no ring wrap, no sliding window), which is what makes acceptance
    rollback-free: stale entries beyond the accepted prefix sit at
    positions the next chunk rewrites before anything attends them."""
    if _window(cfg) is not None:
        raise ValueError("speculative decode_chunk requires a full-length "
                         "cache (no sliding_window)")
    dt = cfg.dtype
    B, c = tokens.shape
    C = cache["k"].shape[2]
    positions = pos0[:, None] + jnp.arange(c)[None, :]  # [B, c]
    x = _embed(cfg, params, tokens, dt)  # [B, c, D]

    cols = jnp.arange(C)[None, None, :]  # [1, 1, C]
    # Column j visible to the query at position p iff j <= p: unwritten
    # slots sit at positions > p by the slot==position invariant.
    valid = (cols <= positions[:, :, None])[:, None]  # [B, 1, c, C]

    def layer_step(x, inputs):
        layer, k_cache, v_cache = inputs  # caches [B, C, KV, Hd]
        x, k_cache, v_cache = chunk_attn_step(
            cfg, layer, x, k_cache, v_cache, positions, valid)
        x = ffn(cfg, x, layer)
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["layers"], cache["k"], cache["v"]))
    x = _norm(cfg, x, params["final_norm"])
    logits = decode_logits(cfg, params, x)
    return logits, {"k": new_k, "v": new_v}


def chunk_attn_step(cfg, layer: dict, x: jax.Array, k_cache: jax.Array,
                    v_cache: jax.Array, positions: jax.Array,
                    valid: jax.Array):
    """One cached-attention sublayer for a c-token chunk (the
    speculative-verify analogue of ``cached_attn_step``) — shared by
    both decoder families' ``decode_chunk``. ``positions`` [B, c],
    ``valid`` [B, 1, c, C]; writes slot == position."""
    from polyaxon_tpu.ops.attention import repeat_kv

    dt = cfg.dtype
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_rep = H // KV
    rows = jnp.arange(positions.shape[0])

    h = _norm(cfg, x, layer["attn_norm"])
    q, k, v, gate = _qkv(cfg, layer, h, positions)
    k_cache = k_cache.at[rows[:, None], positions].set(k)
    v_cache = v_cache.at[rows[:, None], positions].set(v)
    keys = repeat_kv(k_cache, n_rep)
    vals = repeat_kv(v_cache, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys).astype(jnp.float32)
    s = s * (Hd ** -0.5)
    s = jnp.where(valid, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(dt)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vals)
    return _attn_out(cfg, layer, x, attn, gate), k_cache, v_cache


# ------------------------------------------------- paged KV decode surface
# vLLM-style paged attention, TPU-first: the KV cache is a shared pool
# of fixed-size pages ([L, P, KV, page, Hd]) addressed through per-row
# block tables, so serving memory scales with tokens actually held, not
# slots x max_len reservations (the allocator lives in serving/paged.py;
# the reference orchestrator has no serving path at all — net-new
# surface, SURVEY.md §2). Page 0 is scratch: idle rows and unallocated
# coordinates write there, and masks keep it unread.
#
# The layout is kv-head-major within a page because the decode kernel
# (ops/paged_attention.py) takes a page as one contiguous [KV, page, Hd]
# block, and a Mosaic block's two trailing dims must be whole. It is
# known to the functions below, as far as `paged_init_cache`, and to
# the kernel, and to nothing else.
#
# Every program writes the pool by whole pages: the decode program
# through `paged_write_step` (only it and the kernel touch the pool
# there), a prefill program through `paged_write_span` (or
# `paged_write_pages`, where the pages are fresh). A write whose unit
# is one token (a scatter at (page, :, offset), or a
# dynamic_update_slice of [KV, 1, Hd]) makes the TPU compiler lay the
# pool out token-major for it and copy all of it into the kernel's
# order and back around every call: two whole pools copied a layer with
# the pool carried through the decode program's layer scan, four a
# prefill program (jax 0.9.0 / libtpu 0.0.34, compiled for a described
# v5e). A page is contiguous in the kernel's order, so a page-wise
# write leaves the layout alone and updates the pool in place
# (tests/test_aot_tpu_compile.py holds the decode and the prefill
# programs to that).

def paged_pool_shape(cfg, n_pages: int, page_size: int) -> tuple:
    return (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)


def paged_page_size(cache: dict) -> int:
    return cache["k"].shape[-2]


def paged_gather(pages: jax.Array, page_ids: jax.Array) -> jax.Array:
    """The pages ``page_ids`` ([..., n], already clamped to real ids) of
    a pool ``[..., P, KV, page, Hd]`` as token-major KV
    ``[..., n·page, KV, Hd]``."""
    got = jnp.take(pages, page_ids, axis=-4)  # [..., n, KV, page, Hd]
    got = jnp.swapaxes(got, -3, -2)
    return got.reshape(*got.shape[:-4], -1, *got.shape[-2:])


def paged_gather_prefix(cache: dict, page_ids: jax.Array) -> tuple:
    """What a suffix prefill reads of the matched pages ``page_ids``
    ([n], clamped to real ids, in chain order): their K and V,
    token-major [L, n·page, KV, Hd] each, in the order
    ``paged_prefill_suffix_kv`` takes them."""
    return (paged_gather(cache["k"], page_ids),
            paged_gather(cache["v"], page_ids))


def paged_write_step(pool: jax.Array, layer, kv: jax.Array,
                     write_page: jax.Array, write_off: jax.Array) -> jax.Array:
    """A decode step's ``kv`` [B, KV, Hd] into layer ``layer`` (int or
    traced scalar) of the whole pool [L, P, KV, page, Hd], row b's at
    (write_page[b], write_off[b]), by whole pages: the B pages are read,
    each takes its row's token at its offset, and they are put back.

    What that leans on (serving/paged.py): a live row appends only to a
    page it alone references (the radix tree holds whole pages, and a
    divergence inside a page forks a copy), so no two live rows name the
    same page and each page put back is its old content and one new
    token. Idle and unallocated rows all name scratch page 0; their
    writes may land in any order, because that page is never read
    unmasked and holds finite values whichever wins. Hence no
    ``unique_indices`` hint."""
    page = pool.shape[-2]
    pages = pool[layer, write_page]  # [B, KV, page, Hd]
    here = jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page, 1), 2) == write_off[:, None, None, None]
    pages = jnp.where(here, kv[:, :, None, :].astype(pool.dtype), pages)
    return pool.at[layer, write_page].set(pages)


def paged_write_pages(pool: jax.Array, kv: jax.Array,
                      page_ids: jax.Array) -> jax.Array:
    """A prefill's ``kv`` [L, n·page, KV, Hd], token-major and whole
    pages long, into pages ``page_ids`` [n] (real ids, no two alike) of
    the whole pool [L, P, KV, page, Hd], by whole pages: each page is
    written as the contiguous block it is in the kernel's order, so the
    pool keeps its layout and is updated in place (the paged surface's
    comment). Nothing is read: every slot of every page named is
    written, so the pages are the row's own and fresh
    (`paged_write_span` merges into what a page holds)."""
    L, T, KV, Hd = kv.shape
    page = pool.shape[-2]
    pages = kv.reshape(L, T // page, page, KV, Hd).swapaxes(2, 3)
    return pool.at[:, page_ids].set(pages.astype(pool.dtype))


def paged_write_span(pool: jax.Array, kv: jax.Array, page_ids: jax.Array,
                     start, real_len=None, first=None) -> jax.Array:
    """A prefill's ``kv`` [L, S, KV, Hd] into the whole pool
    [L, P, KV, page, Hd] at absolute positions start..start+S-1 of the
    row whose block-table row is ``page_ids`` [maxp] (-1 = not
    allocated), by whole pages: the pages the span touches are read,
    every slot whose position lies in [start, start + real_len) takes
    its token, every other slot keeps what the page held, and the pages
    are put back. ``start`` is a plain int or a traced scalar,
    ``real_len`` (traced, or None: all S) how much of ``kv`` is real.
    ``first`` (None: ``start``) is the first position written where the
    span was computed from further back than it may write: below it lie
    pages other rows share (a suffix behind a match under a window,
    ``models/smallthinker.py``).

    The count of pages is static: a span from inside a page touches at
    most ceil(S / page) + 1. What a page holds outside the span stays
    because it is somebody's: before ``start`` the matched prefix the
    engine forked into the row's own page (serving/batching.py
    `_admit_prefill`), past the end whatever a page other rows share
    holds there. A touched page past the row's table, wholly past the
    last real position, or not allocated names scratch page 0; several
    may, and land in any order, as `paged_write_step`'s idle rows do.
    Hence no ``unique_indices`` hint."""
    L, S, KV, Hd = kv.shape
    page = pool.shape[-2]
    maxp = page_ids.shape[0]
    n = (-(-(start % page + S) // page) if isinstance(start, int)
         else -(-S // page) + 1)
    end = start + (S if real_len is None else real_len)
    slots = start // page + jnp.arange(n)  # the row's logical pages
    mine = (slots < maxp) & (slots * page < end)
    if first is not None:
        mine &= (slots + 1) * page > first
    ids = jnp.where(
        mine, jnp.maximum(page_ids[jnp.minimum(slots, maxp - 1)], 0), 0)
    # Slot j of touched page i is position (start // page + i)·page + j,
    # which is token i·page + j - start % page of `kv`: the span shifted
    # into its first page, cut into pages.
    shifted = jax.lax.dynamic_slice_in_dim(
        jnp.pad(kv, ((0, 0), (page, n * page - S), (0, 0), (0, 0))),
        page - start % page, n * page, axis=1)
    new = shifted.reshape(L, n, page, KV, Hd).swapaxes(2, 3)
    at = slots[:, None] * page + jnp.arange(page)[None, :]  # [n, page]
    real = ((at >= (start if first is None else first))
            & (at < end))[None, :, None, :, None]
    pages = jnp.where(real, new.astype(pool.dtype), pool[:, ids])
    return pool.at[:, ids].set(pages)


def paged_init_cache(cfg: LlamaConfig, n_pages: int, page_size: int) -> dict:
    if _window(cfg) is not None:
        raise ValueError(
            "this family's paged cache keeps every layer's pages in one "
            "space and its decode attends all of them: a sliding_window "
            "in every layer is not served from it. A family that "
            "declares window layers (`paged_window`, models/"
            "smallthinker.py) is given a window space beside the full "
            "one (serving/paged.py WindowedPagePool)")
    shape = paged_pool_shape(cfg, n_pages, page_size)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def paged_attn_step(cfg, layer: dict, x: jax.Array, k_pool: jax.Array,
                    v_pool: jax.Array, layer_idx, positions: jax.Array,
                    write_page: jax.Array, write_off: jax.Array,
                    tables: jax.Array, valid: jax.Array, *,
                    window: Optional[int] = None, rotary: bool = True):
    """Paged analogue of ``cached_attn_step``: writes this step's K/V
    into each row's current page slot of layer ``layer_idx`` (int or
    traced scalar) of the whole pools [L, P, KV, page, Hd] and attends
    over the row's pages of that layer via its block table; returns the
    whole pools. ``tables`` [B, maxp] (-1 = not allocated, clamped to
    scratch page 0 for the gather), ``valid`` [B, 1, 1, maxp*page]
    masks real positions. A window layer of a static layer plan says
    so: ``window`` (the kernel's lower bound; ``valid`` and ``tables``
    are then `paged_coords`' for that window and the window space's),
    ``rotary`` (`_qkv`)."""
    from polyaxon_tpu.ops.attention import repeat_kv

    dt = cfg.dtype
    B = x.shape[0]
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_rep = H // KV

    h = _norm(cfg, x, layer["attn_norm"])
    q, k, v, gate = _qkv(cfg, layer, h, positions, rotary)
    k_pool = paged_write_step(k_pool, layer_idx, k[:, 0], write_page,
                              write_off)
    v_pool = paged_write_step(v_pool, layer_idx, v[:, 0], write_page,
                              write_off)

    impl = getattr(cfg, "paged_attention_impl", "gather")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "gather"
    if impl == "pallas":
        # Stream pages straight from the pool (skipping holes and
        # pages past pos) instead of materializing the gather — see
        # ops/paged_attention.py. `pos` is recovered from the RoPE
        # positions + the valid mask's idle bit.
        from polyaxon_tpu.ops.paged_attention import paged_decode_attention

        live = valid[:, 0, 0, :].any(axis=-1)  # [B] — idle rows all-False
        pos_vec = jnp.where(live, positions[:, 0], -1)
        attn = paged_decode_attention(
            q[:, 0].reshape(B, H, Hd), k_pool, v_pool, layer_idx, tables,
            pos_vec, window=window).astype(dt)[:, None]
    else:
        gathered = jnp.maximum(tables, 0)  # [B, maxp] — scratch for holes
        keys = repeat_kv(paged_gather(k_pool[layer_idx], gathered), n_rep)
        vals = repeat_kv(paged_gather(v_pool[layer_idx], gathered), n_rep)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, keys).astype(jnp.float32)
        logits = logits * (Hd ** -0.5)
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(dt)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vals)
    return _attn_out(cfg, layer, x, attn, gate), k_pool, v_pool


def paged_coords(pos: jax.Array, tables: jax.Array, page: int,
                 window: Optional[int] = None):
    """Shared paged addressing: per-row positions [B] (-1 = idle) +
    block tables [B, maxp] → (positions [B,1] for RoPE, write_page [B],
    write_off [B], attention mask [B,1,1,maxp*page]). Idle/unallocated
    writes land on scratch page 0; the mask admits exactly positions
    0..pos through allocated pages, under a ``window`` the last that
    many of them."""
    B, maxp = tables.shape
    pos_safe = jnp.maximum(pos, 0)
    rows = jnp.arange(B)
    write_page = jnp.where(
        pos >= 0, tables[rows, pos_safe // page], 0)
    write_page = jnp.maximum(write_page, 0)  # unallocated → scratch
    write_off = pos_safe % page
    j = jnp.arange(maxp * page)[None, :]  # global position per column
    allocated = jnp.repeat(tables >= 0, page, axis=1)  # [B, maxp*page]
    valid = (j <= pos_safe[:, None]) & (pos[:, None] >= 0) & allocated
    if window is not None:
        valid &= j > pos_safe[:, None] - window
    return pos_safe[:, None], write_page, write_off, valid[:, None, None, :]


def decode_step_paged(
    cfg: LlamaConfig,
    params: dict,
    cache: dict,  # {"k"/"v": [L, P, KV, page, Hd]}
    tokens: jax.Array,  # [B] int32
    pos: jax.Array,  # [B] int32 per-row position being written (-1 idle)
    tables: jax.Array,  # [B, maxp] int32 page ids (-1 = unallocated)
    ffn=_mlp,
) -> tuple[jax.Array, dict]:
    """`decode_step_ragged` over the paged pool: a row at position p
    with pages covering 0..p matches the dense ragged step at p exactly
    (parity-tested)."""
    dt = cfg.dtype
    page = paged_page_size(cache)
    positions, write_page, write_off, valid = paged_coords(pos, tables, page)
    x = _embed(cfg, params, tokens, dt)[:, None, :]

    # The pools ride the layer walk as a carry, whole: as scanned
    # inputs and outputs every layer's pool was sliced out and stacked
    # back, half of what the program did (the paged surface's comment).
    def layer_step(carry, inputs):
        x, k_pool, v_pool = carry
        layer, layer_idx = inputs
        x, k_pool, v_pool = paged_attn_step(
            cfg, layer, x, k_pool, v_pool, layer_idx, positions,
            write_page, write_off, tables, valid)
        x = ffn(cfg, x, layer)
        return (x, k_pool, v_pool), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer_step, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cache["k"].shape[0])))
    x = _norm(cfg, x, params["final_norm"])
    logits = decode_logits(cfg, params, x[:, 0])
    return logits, {"k": new_k, "v": new_v}


def paged_prefill_kv(cfg: LlamaConfig, params: dict, prompt: jax.Array,
                     ffn=_mlp):
    """Prompt pass returning raw per-position KV (no ring assembly):
    (k_all, v_all) [L, P, KV, Hd] for a single row [1, P] — the paged
    insert scatters these into the row's pages. Same ``_prompt_pass``
    body as ``prefill``, so the engines cannot diverge."""
    _, k_all, v_all = _prompt_pass(cfg, params, prompt, ffn)
    return k_all[:, 0], v_all[:, 0]  # [L, P, KV, Hd]


def paged_insert_prefill(cache: dict, k_all: jax.Array, v_all: jax.Array,
                         page_ids: jax.Array, page_size: int) -> dict:
    """A prefilled row's KV ([L, P, KV, Hd]) into its allocated pages,
    by whole pages (`paged_write_span`). ``page_ids`` [maxp] int32 (-1
    padding beyond the row's pages; positions < P always map into real
    ids). What the last page holds past the prompt stays."""
    return {
        "k": paged_write_span(cache["k"], k_all, page_ids, 0),
        "v": paged_write_span(cache["v"], v_all, page_ids, 0),
    }


def suffix_attn_step(cfg, layer: dict, x: jax.Array, k_prefix: jax.Array,
                     v_prefix: jax.Array, positions: jax.Array,
                     valid: jax.Array):
    """One attention sublayer for a prefill SUFFIX [B, S] whose prefix
    KV already exists (radix-cache hit): queries at absolute positions
    ``positions`` attend [prefix; suffix]. ``k_prefix``/``v_prefix``
    [B, Mpad, KV, Hd] were written by a completed prefill, so they are
    already roped at their absolute positions — only the suffix K gets
    roped here. ``valid`` [B, 1, S, Mpad+S] masks prefix padding and
    keeps the suffix causal. Returns (x, k_suffix, v_suffix)."""
    from polyaxon_tpu.ops.attention import repeat_kv

    dt = cfg.dtype
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_rep = H // KV

    h = _norm(cfg, x, layer["attn_norm"])
    q, k, v, gate = _qkv(cfg, layer, h, positions)
    keys = repeat_kv(jnp.concatenate([k_prefix, k], axis=1), n_rep)
    vals = repeat_kv(jnp.concatenate([v_prefix, v], axis=1), n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys).astype(jnp.float32)
    s = s * (Hd ** -0.5)
    s = jnp.where(valid, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(dt)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vals)
    return _attn_out(cfg, layer, x, attn, gate), k, v


def _suffix_mask(S: int, m_pad: int, m: jax.Array) -> jax.Array:
    """[1, 1, S, m_pad+S] validity for a suffix prefill: prefix column
    j is real iff j < m (traced scalar — the gather pads to whole
    pages), suffix columns are causal."""
    pref_ok = jnp.broadcast_to(
        jnp.arange(m_pad, dtype=jnp.int32)[None, :] < m, (S, m_pad))
    tri = jnp.tril(jnp.ones((S, S), bool))
    return jnp.concatenate([pref_ok, tri], axis=1)[None, None]


def paged_prefill_suffix_kv(cfg: LlamaConfig, params: dict,
                            suffix: jax.Array, k_prefix: jax.Array,
                            v_prefix: jax.Array, m: jax.Array, ffn=_mlp):
    """Prefill only the NOVEL tail of a prompt whose first ``m`` tokens
    hit the radix prefix cache: ``suffix`` [1, S] holds the token ids at
    absolute positions m..m+S-1, ``k_prefix``/``v_prefix`` [L, Mpad, KV,
    Hd] are the matched pages gathered in chain order (Mpad = whole
    pages ≥ m; columns past m are masked, not read). Returns (k_suf,
    v_suf) [L, S, KV, Hd] for ``paged_insert_suffix`` — compute is
    O(S·(m+S)) instead of the full O(P²) recompute."""
    dt = cfg.dtype
    B, S = suffix.shape
    m_pad = k_prefix.shape[1]
    positions = jnp.broadcast_to(
        m + jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    valid = _suffix_mask(S, m_pad, m)
    x = _embed(cfg, params, suffix, dt)

    def layer_step(x, inputs):
        layer, kp, vp = inputs
        x, k, v = suffix_attn_step(
            cfg, layer, x, kp[None], vp[None], positions, valid)
        x = ffn(cfg, x, layer)
        return x, (k, v)

    _, (k_all, v_all) = jax.lax.scan(
        layer_step, x, (params["layers"], k_prefix, v_prefix))
    return k_all[:, 0], v_all[:, 0]  # [L, S, KV, Hd]


def paged_insert_suffix(cache: dict, k_suf: jax.Array, v_suf: jax.Array,
                        page_ids: jax.Array, start: jax.Array,
                        page_size: int,
                        real_len: Optional[jax.Array] = None) -> dict:
    """Suffix KV ([L, S, KV, Hd]) into the row's pages at absolute
    positions start..start+S-1 (``start`` traced int32 — the
    cached-token count varies per admission without recompiling), by
    whole pages (`paged_write_span`): what the first page holds before
    ``start`` stays.

    ``real_len`` (traced int32) supports BUCKETED suffixes: positions
    at or past it are padding whose KV is garbage — they are written
    nowhere (a page that holds none but them is scratch page 0, never
    allocated, never read; serving/paged.py), so a padded suffix
    writes exactly the same real pages as the unpadded one. Without it
    every position is real (the pre-bucketing shape). A padded tail can
    reach past the row's block table: such a page is scratch too, not
    the table's last entry, a real page."""
    return {
        "k": paged_write_span(cache["k"], k_suf, page_ids, start, real_len),
        "v": paged_write_span(cache["v"], v_suf, page_ids, start, real_len),
    }


def generate_loop(
    prefill_fn,  # (cfg, params, prompt, max_len) -> (logits [B, V], cache)
    decode_step_fn,  # (cfg, params, cache, tokens [B], pos) -> (logits, cache)
    cfg,
    params: dict,
    prompt: jax.Array,  # [B, P] int32
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_p: float = 1.0,
    top_k: int = 0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Greedy (temperature 0) or sampled continuation: [B, max_new] —
    one family's prefill, then its scalar-position decode step in a
    scan; every decoder family's ``generate`` is this loop.

    ``temperature``/``top_p``/``top_k`` may be traced scalars (the
    serving path passes them as jitted arguments so sweeping knobs
    reuses one executable); the greedy/sampling choice itself is
    static — a Python float 0.0 selects greedy, anything else selects
    sampling. ``top_p``/``top_k`` filter inside the compiled loop
    (models/common.py sample_logits) — no host round-trip.
    """
    B, P = prompt.shape
    sampling = isinstance(temperature, jax.Array) or temperature > 0
    if sampling and rng is None:
        raise ValueError("sampling (temperature > 0) needs an rng key")
    rng = rng if rng is not None else jax.random.key(0)

    logits, cache = prefill_fn(cfg, params, prompt, P + max_new_tokens)

    def sample(logits, key):
        if sampling:
            return sample_logits(logits, key, temperature, top_p, top_k)
        return jnp.argmax(logits, axis=-1)

    def decode_loop(carry, t):
        cache, logits, key = carry
        key, sub = jax.random.split(key)
        token = sample(logits, sub).astype(jnp.int32)
        logits, cache = decode_step_fn(cfg, params, cache, token, P + t)
        return (cache, logits, key), token

    (_, logits, _), tokens = jax.lax.scan(
        decode_loop, (cache, logits, rng), jnp.arange(max_new_tokens))
    return tokens.T  # [B, max_new]


def generate(cfg: LlamaConfig, params: dict, prompt: jax.Array, **sampling):
    """``generate_loop`` over this family's prefill and decode step."""
    return generate_loop(prefill, decode_step, cfg, params, prompt,
                         **sampling)


def apply(
    cfg: LlamaConfig,
    variables: Variables,
    batch: Batch,
    train: bool = True,
    rng: Optional[jax.Array] = None,
):
    tokens = batch["tokens"]
    inputs = shift_right(tokens)
    segments = batch.get("segments")
    if segments is not None:
        # Packed sequences: each segment starts from BOS (no token leaks
        # across the boundary), attention is segment-restricted, and
        # RoPE restarts — every segment trains exactly like an unpacked
        # sequence of its own.
        inputs = jnp.where(segment_starts(segments),
                           jnp.zeros_like(inputs), inputs)
    # Chunked lm-head loss: the [B, S, V] fp32 logits tensor is never
    # materialized (common.chunked_lm_loss) — the dominant HBM saving at
    # pretraining shapes.
    x = hidden_states(cfg, variables["params"], inputs, segment_ids=segments)
    head = lm_head(cfg, variables["params"]).astype(cfg.dtype)
    loss, acc = chunked_lm_loss(x, head, tokens, batch.get("mask"),
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
