"""Qwen3-Next-80B-A3B-Instruct's plain reference: a hybrid decoder of
Gated DeltaNet and gated attention layers, each followed by a block of
routed SwiGLU experts, written out plainly.

``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type:
qwen3_next``) and the published modelling code: 48 pre-norm layers of
hidden 2,048. With ``rms(x, g) = x / sqrt(mean(x²) + eps) · (1 + g)``
(eps 1e-6; every norm but the delta layer's output norm applies its
gain as ``1 + g``), layer ``l`` is ``x ← x + Mixer_l(rms(x, norm_l))``,
then ``x ← x + Experts_l(rms(x, norm'_l))``; after the last layer
``rms(·, norm_f)`` and an untied head over 151,936 ids. The mixer is
full attention where ``(l + 1) % full_attention_interval == 0`` (a
period is ``D D D A``), Gated DeltaNet elsewhere; every layer has the
expert block (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty).

- *Gated DeltaNet* (16 key heads and 32 value heads of 128; key head
  ``h // 2`` serves value head ``h``): ``[q | k | v | z] = u · W_qkvz``
  and ``[b | a] = u · W_ba``, each laid out key head by key head as the
  published checkpoint has them; ``[q | k | v] ← silu(conv1d(·))``,
  depthwise, causal, kernel 4, no bias, zeros before the sequence; per
  value head ``q, k ← x · rsqrt(Σx² + 1e-6)``, ``q ← q / √128``; ``β =
  sigmoid(b)``; ``g = −exp(A_log) · softplus(a + dt_bias)``; from zeros
  ``S_t = e^{g_t} S_{t−1} + k_t ⊗ β_t (v_t − (e^{g_t} S_{t−1})ᵀ k_t)``,
  ``o_t = S_tᵀ q_t``; ``out = [rms_128(o_t) · w ⊙ silu(z_t)] · W_out``
  (that norm's gain applies as ``w``, one vector of 128 for every
  head). A sequential ``lax.scan`` over the positions: no chunks, no
  triangular solve.
- *Gated attention* (16 query heads on 2 key/value heads of 256):
  ``u · W_q`` is ``[16, 512]``, each head's first 256 its query, the
  next 256 its gate; ``rms`` (1 + g) over the head on q and on k; the
  rotary embedding (theta 1e7, rotate-half) turns dimensions 0-63 and
  passes the other 192 (``partial_rotary_factor`` 0.25); causal softmax
  at ``256^-½``; ``out = (attn ⊙ sigmoid(gate)) · W_o``.
- *Experts*: ``p = softmax(u · W_r)`` over all 512; the 10 largest,
  renormalised to sum to one; ``r = Σ_k w_k · W_down,e(silu(W_gate,e u)
  ⊙ W_up,e u)`` at width 512; ``out = r + sigmoid(u · w_sg) ·
  W_sd(silu(W_sg u) ⊙ W_su u)``, one shared expert of width 512 behind a
  scalar gate a token. Every held expert over every token, masked by
  the routing: no sort, no capacity, nothing dropped.

**The chip's share.** The configuration's ``num_experts`` counts the
experts held here, ``reduced.num_experts.source`` those the router
scores, and ``deployment.rank`` which block of them this chip holds
(`held`). A pair whose expert lies elsewhere adds nothing; the partial
sum goes on. The shared expert is whole here. ``vocab_size`` is the
slice's: ids, table and head are over it.

**How the weights are held.** Made at float32 from the seed with the
program's own ``jax.random`` calls (one jitted program, as the server
makes them), then rounded once to ``torch_dtype`` (bfloat16) but for
the leaves the program reads at float32 (`FLOAT32`). Every use casts
back to float32 and computes there at ``Precision.HIGHEST``. No cache,
no batching: one full forward a row.

Departures from the published description, each in the configuration's
``assumed``: the state and the recurrence in float32; seeded weights,
with ``A_log`` and ``dt_bias`` drawn as the gated delta rule's own
initialisation draws them so that each shows in the result; the
multi-token-prediction module left out (no key of the ``config``
describes it and the next-token pass does not run it).

``precision``: "highest" is the reference; "int8" the control (both
inputs of every projection's and every expert's matmul rounded to int8,
``reference/plain.py``): the step below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.plain import _trunc, matmul, rope

HI = jax.lax.Precision.HIGHEST
# What the program reads at float32, and so holds at float32.
FLOAT32 = {"gdn_norm", "attn_norm", "moe_norm", "final_norm", "q_norm",
           "k_norm", "out_norm", "A_log", "dt_bias", "conv_w", "router"}
# The seeded draw of dt_bias: a time step log-uniform between, held
# over a floor (the gated delta rule's own initialisation; no key of
# the published config).
TIME_STEP = (0.001, 0.1, 1e-4)


def held(config: dict) -> tuple:
    """(first, count, routed): the experts held here among those the
    router scores."""
    count = config["num_experts"]
    cut = config.get("reduced", {}).get("num_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def layer_kinds(config: dict, layers: int) -> list:
    every = config["full_attention_interval"]
    return ["attn" if (i + 1) % every == 0 else "gdn" for i in range(layers)]


def rms(x, gain, eps, offset=1.0):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (offset + gain))


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded weights, the mixers' stacked by kind, in the program's
    order of draws (module docstring: float32 draws, rounded once to
    ``torch_dtype`` but for `FLOAT32`)."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    K = config["linear_conv_kernel_dim"]
    conv_dim = 2 * hk * dk + hv * dv
    _, count, routed = held(config)
    f, fs = (config["moe_intermediate_size"],
             config["shared_expert_intermediate_size"])
    kinds = layer_kinds(config, layers)
    L, lg, la = layers, kinds.count("gdn"), kinds.count("attn")
    lo, hi, floor = TIME_STEP
    held_as = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    fan = lambda n: 1.0 / math.sqrt(n)
    zeros = lambda *shape: jnp.zeros(shape, jnp.float32)

    def make():
        k = jax.random.split(jax.random.key(seed), 20)
        step = jnp.exp(jax.random.uniform(k[5], (lg, hv))
                       * (math.log(hi) - math.log(lo)) + math.log(lo))
        step = jnp.maximum(step, floor)
        tree = {
            "embed": _trunc(k[0], (config["vocab_size"], d), 0.02),
            "gdn": {
                "gdn_norm": zeros(lg, d),
                "w_qkvz": _trunc(k[1], (lg, d, 2 * hk * dk + 2 * hv * dv),
                                 fan(d)),
                "w_ba": _trunc(k[2], (lg, d, 2 * hv), fan(d)),
                "conv_w": _trunc(k[3], (lg, conv_dim, K), fan(K)),
                "A_log": jnp.log(jax.random.uniform(
                    k[4], (lg, hv), minval=1.0, maxval=16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "out_norm": jnp.ones((lg, dv), jnp.float32),
                "w_out": _trunc(k[6], (lg, hv * dv, d), fan(hv * dv))},
            "attn": {
                "attn_norm": zeros(la, d),
                "wq": _trunc(k[7], (la, d, 2 * h * hd), fan(d)),
                "wk": _trunc(k[8], (la, d, kv * hd), fan(d)),
                "wv": _trunc(k[9], (la, d, kv * hd), fan(d)),
                "q_norm": zeros(la, hd),
                "k_norm": zeros(la, hd),
                "wo": _trunc(k[10], (la, h * hd, d), fan(h * hd))},
            "moe": {
                "moe_norm": zeros(L, d),
                "router": _trunc(k[11], (L, d, routed), fan(d)),
                "w_gate": _trunc(k[12], (L, count, d, f), fan(d)),
                "w_up": _trunc(k[13], (L, count, d, f), fan(d)),
                "w_down": _trunc(k[14], (L, count, f, d), fan(f)),
                "ws_gate": _trunc(k[15], (L, d, fs), fan(d)),
                "ws_up": _trunc(k[16], (L, d, fs), fan(d)),
                "ws_down": _trunc(k[17], (L, fs, d), fan(fs)),
                "shared_gate": _trunc(k[18], (L, d), fan(d))},
            "final_norm": zeros(d),
            "lm_head": _trunc(k[19], (d, config["vocab_size"]), 0.02),
        }
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if path[-1].key in FLOAT32
            else leaf.astype(held_as), tree)

    return jax.jit(make)()


def _mm(x, w, precision):
    return matmul(x, w.astype(jnp.float32), precision)


# --------------------------------------------------------------- one row
def gated_delta(config, layer, x, precision):
    """x [S, D] → (x after the residual, the state after the last
    position [Hv, dk, dv], the convolution's last K−1 inputs [K−1,
    conv_dim])."""
    S = x.shape[0]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    K, eps, rep = config["linear_conv_kernel_dim"], config["rms_norm_eps"], hv // hk
    u = rms(x, layer["gdn_norm"], eps)
    # Key head by key head: its q, its k, its value heads' v, their z.
    by_head = _mm(u, layer["w_qkvz"], precision).reshape(
        S, hk, 2 * dk + 2 * rep * dv)
    ba = _mm(u, layer["w_ba"], precision).reshape(S, hk, 2 * rep)
    q, k = by_head[..., :dk], by_head[..., dk:2 * dk]
    v = by_head[..., 2 * dk:2 * dk + rep * dv]
    z = by_head[..., 2 * dk + rep * dv:].reshape(S, hv, dv)
    b, a = ba[..., :rep].reshape(S, hv), ba[..., rep:].reshape(S, hv)
    mixed = jnp.concatenate([q.reshape(S, -1), k.reshape(S, -1),
                             v.reshape(S, -1)], -1)
    padded = jnp.concatenate([jnp.zeros((K - 1, mixed.shape[1])), mixed])
    conv = jax.nn.silu(sum(layer["conv_w"][:, j] * padded[j:j + S]
                           for j in range(K)))
    q = conv[:, :hk * dk].reshape(S, hk, dk)
    k = conv[:, hk * dk:2 * hk * dk].reshape(S, hk, dk)
    v = conv[:, 2 * hk * dk:].reshape(S, hv, dv)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) / math.sqrt(dk), rep, 1)
    k = jnp.repeat(unit(k), rep, 1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(a + layer["dt_bias"])

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t, precision=HI)
        write = beta_t[:, None] * (v_t - read)
        state = state + k_t[:, :, None] * write[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=HI)

    state, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv)),
                            (q, k, v, g, beta))
    normed = rms(o, layer["out_norm"], eps, offset=0.0)
    out = _mm((normed * jax.nn.silu(z)).reshape(S, hv * dv), layer["w_out"],
              precision)
    return x + out, state, padded[S:]


def attention(config, layer, x, precision):
    """x [S, D] → x after the residual: causal GQA with a per-head
    output gate, q/k norms and a rotary embedding over the first
    ``partial_rotary_factor`` of each head."""
    S = x.shape[0]
    H, KV, Hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    eps = config["rms_norm_eps"]
    turned = int(Hd * config["partial_rotary_factor"])
    u = rms(x, layer["attn_norm"], eps)
    qg = _mm(u, layer["wq"], precision).reshape(S, H, 2 * Hd)
    q, gate = qg[..., :Hd], qg[..., Hd:].reshape(S, H * Hd)
    k = _mm(u, layer["wk"], precision).reshape(S, KV, Hd)
    v = _mm(u, layer["wv"], precision).reshape(S, KV, Hd)
    q, k = rms(q, layer["q_norm"], eps), rms(k, layer["k_norm"], eps)
    pos = jnp.arange(S)
    turn = lambda t: jnp.concatenate(
        [rope(t[..., :turned], pos, config["rope_theta"]), t[..., turned:]],
        -1)
    q, k = turn(q), turn(k)
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
    return x + _mm(out * jax.nn.sigmoid(gate), layer["wo"], precision)


def router(config, layer, u, precision):
    """u [S, D] → (chosen experts [S, k] among all the router scores,
    combine weights [S, routed], zero where not chosen)."""
    K = config["num_experts_per_tok"]
    p = jax.nn.softmax(matmul(u, layer["router"], precision), -1)
    w, idx = jax.lax.top_k(p, K)
    if config["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, jnp.einsum("ske,sk->se", jax.nn.one_hot(idx, p.shape[-1]), w,
                           precision=HI)


def swiglu(u, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(u, gate, precision)) * _mm(u, up, precision),
               down, precision)


def routed_part(config, layer, u, precision):
    """What the held experts add for u [S, D] (normalised): every held
    expert over the whole row, weighted (zero where not chosen:
    computed and discarded, plain not fast)."""
    first, count, _ = held(config)
    _, weights = router(config, layer, u, precision)

    def one(total, expert):
        gate, up, down, w = expert
        return total + w[:, None] * swiglu(u, gate, up, down, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        layer["w_gate"], layer["w_up"], layer["w_down"],
        weights[:, first:first + count].T))
    return routed


def shared_part(config, layer, u, precision):
    gate = jax.nn.sigmoid(_mm(u, layer["shared_gate"][:, None], precision))
    return gate * swiglu(u, layer["ws_gate"], layer["ws_up"],
                         layer["ws_down"], precision)


def expert_block(config, layer, x, precision):
    u = rms(x, layer["moe_norm"], config["rms_norm_eps"])
    return (x + routed_part(config, layer, u, precision)
            + shared_part(config, layer, u, precision))


def _at(stack: dict, i: int) -> dict:
    return {name: leaf[i] for name, leaf in stack.items()}


def hidden(config, weights, tokens, precision="highest", keep=None):
    """tokens [B, S] → final-norm hidden [B, S, D]. ``keep``, a dict, is
    given every delta layer's state after the last position [B, Hv, dk,
    dv] (under ``gdn``) and its convolution's last inputs [B, K−1,
    conv_dim] (under ``conv``), and every layer's chosen experts [B, S,
    k] (under ``experts``): the tests read them."""
    layers = weights["moe"]["moe_norm"].shape[0]
    x = weights["embed"][tokens].astype(jnp.float32)
    seen = {"attn": 0, "gdn": 0}
    eps = config["rms_norm_eps"]
    for i, kind in enumerate(layer_kinds(config, layers)):
        layer = _at(weights[kind], seen[kind])
        seen[kind] += 1
        if kind == "attn":
            x = jax.lax.map(
                lambda row: attention(config, layer, row, precision), x)
        else:
            x, state, tail = jax.lax.map(
                lambda row: gated_delta(config, layer, row, precision), x)
            if keep is not None:
                keep.setdefault("gdn", []).append(state)
                keep.setdefault("conv", []).append(tail)
        block = _at(weights["moe"], i)
        if keep is not None:
            keep.setdefault("experts", []).append(jax.vmap(
                lambda row: router(config, block, rms(
                    row, block["moe_norm"], eps), precision)[0])(x))
        x = jax.lax.map(
            lambda row: expert_block(config, block, row, precision), x)
    return rms(x, weights["final_norm"], eps)


def logits(config, weights, tokens, precision="highest"):
    """tokens [B, S] → float32 logits [B, S, V] (the untied head)."""
    x = hidden(config, weights, tokens, precision)
    return jax.lax.map(
        lambda row: _mm(row, weights["lm_head"], precision), x)
