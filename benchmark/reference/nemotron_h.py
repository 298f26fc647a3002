"""Nemotron-3-Super-120B-A12B's plain reference: a hybrid decoder of
Mamba-2, attention and latent expert layers, written out plainly.

``config.json`` of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(``model_type: nemotron_h``) and the family's published description: 88
pre-norm layers of hidden 4,096, each *one mixer alone*, ``x ← x +
Mixer_l(rms(x, norm_l))`` with ``rms(x, g) = x / sqrt(mean(x²) + eps) ·
g``; character ``l`` of ``hybrid_override_pattern`` names the mixer
(40 ``M``, 40 ``E``, 8 ``*``); after the last layer ``rms(·, norm_f)``
and an untied head over 131,072 ids.

- ``M``, Mamba-2 (128 heads of 64, 8 groups, state 128, kernel 4):
  ``[z | xBC | dt] = u · W_in``; ``xBC ← silu(conv1d(xBC) + b)``
  (depthwise, causal, zeros before the sequence); ``[x | B | C] =
  xBC``, head ``h`` reads group ``h // 16``; ``Δ = softplus(dt +
  dt_bias)``, ``A = −exp(A_log)``; per head ``S_t = exp(Δ_t A) S_{t−1} +
  Δ_t x_t ⊗ B_t`` from zeros, ``y_t = S_t C_t + D x_t``; ``y ← y ⊙
  silu(z)``, RMS-normalised within each of the 8 groups of 1,024 (gate
  before norm), ``out = y · W_out``. A sequential ``lax.scan`` over the
  positions: no chunks.
- ``*``, attention: q, k, v, o without bias, 32 query heads on 2
  key/value heads of 128, causal softmax at ``128^-½``, no rotary
  embedding.
- ``E``, the latent expert layer: ``s = sigmoid(u · W_r)`` over all 512
  experts; the 22 chosen by ``top22(s + bias)``, weighted by ``s`` alone
  over their sum (+1e-6), times ``routed_scaling_factor`` 5; ``ℓ = u ·
  W_down`` (4,096 → 1,024); ``r = Σ_k w_k W2_e(relu(W1_e ℓ)²)``; ``out =
  r · W_up + Ws2(relu(Ws1 u)²)``. Every held expert over every token,
  masked by the routing: no sort, no capacity, nothing dropped.

**The chip's share.** The configuration's ``n_routed_experts`` counts
the experts held here, ``reduced.n_routed_experts.source`` those the
router scores, and ``deployment.rank`` which block of them this chip
holds (`held`). A pair whose expert lies elsewhere adds nothing; the
partial sum goes up through ``W_up`` and on. ``vocab_size`` is the
slice's: ids, table and head are over it.

**How the weights are held.** Made at float32 from the seed with the
program's own ``jax.random`` calls (one jitted program, as the server
makes them), then rounded once to ``torch_dtype`` (bfloat16, the
published checkpoint's storage type) but for the leaves the program
reads at float32 (`FLOAT32`): at float32 the share would be 18.6 GB.
Every use casts back to float32 and computes there at
``Precision.HIGHEST``. No cache, no batching: one full forward a row.

Departures from the published description, each in the configuration's
``assumed``: no rotary embedding (``rope_theta`` unread); the router
reads the full hidden state and the shared expert the full width; the
state and the recurrence in float32; seeded weights, with ``A_log``,
``dt_bias`` (Mamba-2's own initialisation), ``D`` (around one) and the
selection bias (around zero) drawn so that each shows in the result;
the multi-token-prediction module left out.

``precision``: "highest" is the reference; "int8" the control (both
inputs of every projection's and every expert's matmul rounded to int8,
``reference/plain.py``): the step below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.plain import _trunc, matmul, rms_norm

HI = jax.lax.Precision.HIGHEST
KINDS = {"M": "ssm", "*": "attn", "E": "moe"}
# What the program reads at float32, and so holds at float32.
FLOAT32 = {"attn_norm", "ssm_norm", "gate_norm", "moe_norm", "final_norm",
           "A_log", "dt_bias", "D", "conv_w", "conv_b", "router",
           "expert_bias"}


def held(config: dict) -> tuple:
    """(first, count, routed): the experts held here among those the
    router scores."""
    count = config["n_routed_experts"]
    cut = config.get("reduced", {}).get("n_routed_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def layer_kinds(config: dict, layers: int) -> list:
    pattern = config["hybrid_override_pattern"][:layers]
    if len(pattern) != layers:
        raise ValueError(f"the configuration's pattern names {len(pattern)} "
                         f"layers, asked for {layers}")
    return [KINDS[char] for char in pattern]


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded weights stacked by kind, in the program's order of draws
    (module docstring: float32 draws, rounded once to ``torch_dtype``
    but for `FLOAT32`)."""
    d = config["hidden_size"]
    hd = config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    hs, K = config["mamba_num_heads"], config["conv_kernel"]
    d_in = hs * config["mamba_head_dim"]
    conv_dim = d_in + 2 * config["n_groups"] * config["ssm_state_size"]
    _, count, routed = held(config)
    dl, f, fs = (config["moe_latent_size"], config["moe_intermediate_size"],
                 config["moe_shared_expert_intermediate_size"])
    kinds = layer_kinds(config, layers)
    la, ls, le = (kinds.count("attn"), kinds.count("ssm"), kinds.count("moe"))
    lo, hi = config["time_step_min"], config["time_step_max"]
    held_as = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    fan = lambda n: 1.0 / math.sqrt(n)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def make():
        k = jax.random.split(jax.random.key(seed), 22)
        step = jnp.exp(jax.random.uniform(k[9], (ls, hs))
                       * (math.log(hi) - math.log(lo)) + math.log(lo))
        step = jnp.maximum(step, config["time_step_floor"])
        tree = {
            "embed": _trunc(k[0], (config["vocab_size"], d), 0.02),
            "attn": {
                "attn_norm": ones(la, d),
                "wq": _trunc(k[1], (la, d, q), fan(d)),
                "wk": _trunc(k[2], (la, d, kv), fan(d)),
                "wv": _trunc(k[3], (la, d, kv), fan(d)),
                "wo": _trunc(k[4], (la, q, d), fan(q))},
            "ssm": {
                "ssm_norm": ones(ls, d),
                "w_in": _trunc(k[5], (ls, d, d_in + conv_dim + hs), fan(d)),
                "conv_w": _trunc(k[6], (ls, conv_dim, K), fan(K)),
                "conv_b": _trunc(k[7], (ls, conv_dim), 0.02),
                "A_log": jnp.log(jax.random.uniform(
                    k[8], (ls, hs), minval=1.0, maxval=16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "D": 1.0 + _trunc(k[10], (ls, hs), 0.02),
                "gate_norm": ones(ls, d_in),
                "w_out": _trunc(k[11], (ls, d_in, d), fan(d_in))},
            "moe": {
                "moe_norm": ones(le, d),
                "router": _trunc(k[12], (le, d, routed), fan(d)),
                "expert_bias": _trunc(k[13], (le, routed), 0.02),
                "w_latent_down": _trunc(k[14], (le, d, dl), fan(d)),
                "w_latent_up": _trunc(k[15], (le, dl, d), fan(dl)),
                "w1": _trunc(k[16], (le, count, dl, f), fan(dl)),
                "w2": _trunc(k[17], (le, count, f, dl), fan(f)),
                "ws1": _trunc(k[18], (le, d, fs), fan(d)),
                "ws2": _trunc(k[19], (le, fs, d), fan(fs))},
            "final_norm": ones(d),
            "lm_head": _trunc(k[20], (d, config["vocab_size"]), 0.02),
        }
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if path[-1].key in FLOAT32
            else leaf.astype(held_as), tree)

    return jax.jit(make)()


def _mm(x, w, precision):
    return matmul(x, w.astype(jnp.float32), precision)


# --------------------------------------------------------------- one row
def mamba2(config, layer, x, precision):
    """x [S, D] → (x after the residual, the state after the last
    position [H, P, N], the convolution's last K−1 inputs [K−1,
    conv_dim])."""
    S = x.shape[0]
    H, P, G, N, K = (config["mamba_num_heads"], config["mamba_head_dim"],
                     config["n_groups"], config["ssm_state_size"],
                     config["conv_kernel"])
    d_in, eps = H * P, config["layer_norm_epsilon"]
    u = rms_norm(x, layer["ssm_norm"], eps)
    zxbcdt = _mm(u, layer["w_in"], precision)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + d_in + 2 * G * N],
                  zxbcdt[:, 2 * d_in + 2 * G * N:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(layer["conv_w"][:, j] * padded[j:j + S] for j in range(K))
    conv = jax.nn.silu(conv + layer["conv_b"])
    xs = conv[:, :d_in].reshape(S, H, P)
    bs = jnp.repeat(conv[:, d_in:d_in + G * N].reshape(S, G, N), H // G, 1)
    cs = jnp.repeat(conv[:, d_in + G * N:].reshape(S, G, N), H // G, 1)
    delta = jax.nn.softplus(dt + layer["dt_bias"])           # [S, H]
    a = -jnp.exp(layer["A_log"])

    def step(state, inputs):
        x_t, b_t, c_t, d_t = inputs
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.sum(state * c_t[:, None, :], -1) + layer["D"][:, None] * x_t
        return state, y

    state, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, bs, cs, delta))
    gated = (y.reshape(S, d_in) * jax.nn.silu(z)).reshape(S, G, -1)
    normed = rms_norm(gated, layer["gate_norm"].reshape(G, -1), eps)
    out = _mm(normed.reshape(S, d_in), layer["w_out"], precision)
    return x + out, state, padded[S:]


def attention(config, layer, x, precision):
    """x [S, D] → x after the residual: causal GQA, no rotary
    embedding."""
    S = x.shape[0]
    H, KV, Hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    u = rms_norm(x, layer["attn_norm"], config["layer_norm_epsilon"])
    q = _mm(u, layer["wq"], precision).reshape(S, H, Hd)
    k = _mm(u, layer["wk"], precision).reshape(S, KV, Hd)
    v = _mm(u, layer["wv"], precision).reshape(S, KV, Hd)
    rep = H // KV
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):      # one key/value head and the query heads on it
        qg, kg, vg = args

        def head(qh):
            scores = jnp.einsum("qd,kd->qk", qh, kg,
                                precision=HI) / math.sqrt(Hd)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("qk,kd->qd", probs, vg, precision=HI)

        return jax.lax.map(jax.checkpoint(head), qg)

    qg = q.reshape(S, KV, rep, Hd).transpose(1, 2, 0, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(S, H * Hd)
    return x + _mm(out, layer["wo"], precision)


def router(config, layer, u, precision):
    """u [S, D] → (chosen experts [S, k] among all the router scores,
    combine weights [S, routed], zero where not chosen)."""
    K = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(u, layer["router"], precision))
    _, idx = jax.lax.top_k(s + layer["expert_bias"], K)
    w = jnp.take_along_axis(s, idx, -1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * config["routed_scaling_factor"]
    return idx, jnp.einsum("ske,sk->se", jax.nn.one_hot(idx, s.shape[-1]), w,
                           precision=HI)


def relu2_mlp(u, up, down, precision):
    return _mm(jnp.square(jax.nn.relu(_mm(u, up, precision))), down,
               precision)


def routed_part(config, layer, u, precision):
    """What the held experts add for u [S, D] (normalised), on the full
    width: every held expert over the whole row, weighted (zero where
    not chosen: computed and discarded, plain not fast)."""
    first, count, _ = held(config)
    _, weights = router(config, layer, u, precision)
    latent = _mm(u, layer["w_latent_down"], precision)

    def one(total, expert):
        up, down, w = expert
        return total + w[:, None] * relu2_mlp(latent, up, down, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent), (
        layer["w1"], layer["w2"], weights[:, first:first + count].T))
    return _mm(routed, layer["w_latent_up"], precision)


def shared_part(config, layer, u, precision):
    return relu2_mlp(u, layer["ws1"], layer["ws2"], precision)


def expert_layer(config, layer, x, precision):
    u = rms_norm(x, layer["moe_norm"], config["layer_norm_epsilon"])
    return (x + routed_part(config, layer, u, precision)
            + shared_part(config, layer, u, precision))


def _at(stack: dict, i: int) -> dict:
    return {name: leaf[i] for name, leaf in stack.items()}


def hidden(config, weights, tokens, precision="highest", keep=None):
    """tokens [B, S] → final-norm hidden [B, S, D]. ``keep``, a dict, is
    given every Mamba-2 layer's state after the last position [B, H, P,
    N] (under ``ssm``) and its convolution's last inputs [B, K−1,
    conv_dim] (under ``conv``), and every expert layer's chosen experts
    [B, S, k] (under ``experts``): the tests read them."""
    layers = sum(weights[kind][norm].shape[0] for kind, norm in (
        ("attn", "attn_norm"), ("ssm", "ssm_norm"), ("moe", "moe_norm")))
    x = weights["embed"][tokens].astype(jnp.float32)
    seen = {"attn": 0, "ssm": 0, "moe": 0}
    eps = config["layer_norm_epsilon"]
    for kind in layer_kinds(config, layers):
        layer = _at(weights[kind], seen[kind])
        seen[kind] += 1
        if kind == "attn":
            x = jax.lax.map(
                lambda row: attention(config, layer, row, precision), x)
        elif kind == "ssm":
            x, state, tail = jax.lax.map(
                lambda row: mamba2(config, layer, row, precision), x)
            if keep is not None:
                keep.setdefault("ssm", []).append(state)
                keep.setdefault("conv", []).append(tail)
        else:
            if keep is not None:
                keep.setdefault("experts", []).append(jax.vmap(
                    lambda row: router(config, layer, rms_norm(
                        row, layer["moe_norm"], eps), precision)[0])(x))
            x = jax.lax.map(
                lambda row: expert_layer(config, layer, row, precision), x)
    return rms_norm(x, weights["final_norm"], eps)


def logits(config, weights, tokens, precision="highest"):
    """tokens [B, S] → float32 logits [B, S, V] (the untied head)."""
    x = hidden(config, weights, tokens, precision)
    return jax.lax.map(
        lambda row: _mm(row, weights["lm_head"], precision), x)
