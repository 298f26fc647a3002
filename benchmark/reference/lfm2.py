"""LFM2-8B-A1B's plain reference: a hybrid decoder written out plainly.

``config.json`` of LiquidAI/LFM2-8B-A1B (``model_type: lfm2_moe``) and
the published ``lfm2_moe`` model code: 24 pre-norm layers of hidden
2,048; ``layer_types`` says which operator a layer has, a gated short
convolution (18) or grouped-query attention (6: 32 query and 8 key/value
heads of 64); the first ``num_dense_layers`` layers carry a dense SwiGLU
of 7,168, the others 32 SwiGLU experts of 1,792, 4 a token; vocabulary
65,536, ``rope_theta`` 1e6, ``norm_eps`` 1e-5, the head tied to the
embedding table.

With ``rms(x, g) = x / sqrt(mean(x²) + eps) · g``, layer ``l`` is ``h =
x + Op_l(rms(x, operator_norm_l))``, ``y = h + FFN_l(rms(h,
ffn_norm_l))``; after the last layer ``rms(·, embedding_norm)`` and the
table transposed.

- Short convolution (``conv_L_cache`` 3, ``conv_bias`` false): ``[B, C,
  X] = split3(u · W_in)``; ``z = B ⊙ X``; ``c_t = w[:, 0] ⊙ z_{t-2} +
  w[:, 1] ⊙ z_{t-1} + w[:, 2] ⊙ z_t`` (depthwise, causal, zeros before
  the sequence); ``out = (C ⊙ c) · W_out``. Written as shifted adds.
- Attention: q, k, v without bias; RMSNorm over the 64 of each head on
  q and on k before RoPE (rotate-half pairs); causal GQA softmax at
  ``64^-½``; the output projection.
- Expert block: ``s = sigmoid(h · W_r)``; the 4 experts are
  ``top4(s + expert_bias)``; their weights are ``s`` alone, ``s[idx] /
  (Σ s[idx] + 1e-6) · routed_scaling_factor`` (``norm_topk_prob`` true).
  A loop over the 32 experts; no capacity, nothing dropped.

float32 throughout at ``Precision.HIGHEST``; no cache, no dispatch, no
batching: a Python loop over the layers, one full forward a row.

Departures from the published description, each noted in the
configuration's ``assumed``: the weights are seeded (the recipe of
``init_weights``, the program's documented one, drawn with the same
``jax.random`` calls so that both sides hold the same model without
handing each other an array); ``expert_bias``, a learned buffer in the
published model, is drawn non-zero (truncated normal, std 0.02) so that
choosing by ``s + b`` and weighting by ``s`` can be told apart; the head
is tied (``tie_embedding`` true is the family's convention; the catalog
row leaves the key out). The catalog row checked: every number of its
``config`` is the file's; its ``described_as`` ("32 experts, top-4, 0
shared; expert bias", "18 conv + 6 attn") agrees.

``precision``: "highest" is the reference; "int8" the control (both
inputs of every projection's and every expert's matmul rounded to int8,
``reference/plain.py``): the step below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.plain import _trunc, matmul, rms_norm, rope

HI = jax.lax.Precision.HIGHEST


def layer_kinds(config: dict, layers: int) -> list:
    """[(operator kind, FFN kind)] of the first `layers` layers of the
    configuration's own ``layer_types``."""
    kinds = config["layer_types"][:layers]
    if len(kinds) != layers:
        raise ValueError(f"the configuration names {len(kinds)} layer types, "
                         f"asked for {layers}")
    return [("attn" if kind == "full_attention" else "conv",
             "dense" if l < config["num_dense_layers"] else "moe")
            for l, kind in enumerate(kinds)]


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded float32 weights, stacked by kind of layer part (attention
    operators, convolution operators, dense FFNs, expert FFNs), in the
    program's order of draws: truncated normal at two sigmas, std 0.02
    for the table and the expert bias, 1/sqrt(fan_in) for the
    projections (the convolution's fan-in is its kernel); gains one."""
    d, f, fm = (config["hidden_size"], config["intermediate_size"],
                config["moe_intermediate_size"])
    E, K = config["num_experts"], config["conv_L_cache"]
    hd = config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    kinds = layer_kinds(config, layers)
    la = sum(op == "attn" for op, _ in kinds)
    lc = len(kinds) - la
    ld = sum(ffn == "dense" for _, ffn in kinds)
    lm = len(kinds) - ld
    k = jax.random.split(jax.random.key(seed), 16)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    return {
        "embed": _trunc(k[0], (config["vocab_size"], d), 0.02),
        "attn": {
            "attn_norm": ones(la, d),
            "wq": _trunc(k[1], (la, d, q), d ** -0.5),
            "wk": _trunc(k[2], (la, d, kv), d ** -0.5),
            "wv": _trunc(k[3], (la, d, kv), d ** -0.5),
            "wo": _trunc(k[4], (la, q, d), q ** -0.5),
            "q_norm": ones(la, hd), "k_norm": ones(la, hd)},
        "conv": {
            "conv_norm": ones(lc, d),
            "w_in": _trunc(k[5], (lc, d, 3 * d), d ** -0.5),
            "w_conv": _trunc(k[6], (lc, d, K), K ** -0.5),
            "w_out": _trunc(k[7], (lc, d, d), d ** -0.5)},
        "dense": {
            "mlp_norm": ones(ld, d),
            "w_gate": _trunc(k[8], (ld, d, f), d ** -0.5),
            "w_up": _trunc(k[9], (ld, d, f), d ** -0.5),
            "w_down": _trunc(k[10], (ld, f, d), f ** -0.5)},
        "moe": {
            "moe_norm": ones(lm, d),
            "router": _trunc(k[11], (lm, d, E), d ** -0.5),
            "expert_bias": _trunc(k[12], (lm, E), 0.02),
            "w_gate": _trunc(k[13], (lm, E, d, fm), d ** -0.5),
            "w_up": _trunc(k[14], (lm, E, d, fm), d ** -0.5),
            "w_down": _trunc(k[15], (lm, E, fm, d), fm ** -0.5)},
        "final_norm": ones(d),
    }


# --------------------------------------------------------------- one row
def short_conv(config, layer, x, precision):
    """x [S, D] → (x after the residual, z [S, D])."""
    S = x.shape[0]
    K = config["conv_L_cache"]
    u = rms_norm(x, layer["conv_norm"], config["norm_eps"])
    gate_b, gate_c, inner = jnp.split(matmul(u, layer["w_in"], precision),
                                      3, axis=-1)
    z = gate_b * inner
    padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1])), z])
    c = sum(layer["w_conv"][:, j] * padded[j:j + S] for j in range(K))
    return x + matmul(gate_c * c, layer["w_out"], precision), z


def attention(config, layer, x, precision):
    """x [S, D] → x after the residual: QK-norm, RoPE, causal GQA."""
    S = x.shape[0]
    H, KV, Hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    eps, theta = config["norm_eps"], config["rope_theta"]
    u = rms_norm(x, layer["attn_norm"], eps)
    pos = jnp.arange(S)
    q = matmul(u, layer["wq"], precision).reshape(S, H, Hd)
    k = matmul(u, layer["wk"], precision).reshape(S, KV, Hd)
    v = matmul(u, layer["wv"], precision).reshape(S, KV, Hd)
    q = rope(rms_norm(q, layer["q_norm"], eps), pos, theta)
    k = rope(rms_norm(k, layer["k_norm"], eps), pos, theta)
    rep = H // KV
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):      # one key/value head and the query heads on it
        qg, kg, vg = args
        scores = jnp.einsum("rqd,kd->rqk", qg, kg,
                            precision=HI) / math.sqrt(Hd)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("rqk,kd->rqd", probs, vg, precision=HI)

    qg = q.reshape(S, KV, rep, Hd).transpose(1, 2, 0, 3)
    out = jax.lax.map(jax.checkpoint(group),
                      (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(S, H * Hd)
    return x + matmul(out, layer["wo"], precision)


def swiglu(u, gate, up, down, precision):
    return matmul(jax.nn.silu(matmul(u, gate, precision))
                  * matmul(u, up, precision), down, precision)


def dense_ffn(config, layer, x, precision):
    u = rms_norm(x, layer["mlp_norm"], config["norm_eps"])
    return x + swiglu(u, layer["w_gate"], layer["w_up"], layer["w_down"],
                      precision)


def router(config, layer, u, precision):
    """u [S, D] → (chosen experts [S, k], combine weights [S, E], zero
    where not chosen)."""
    E, K = config["num_experts"], config["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(u, layer["router"], precision))
    chosen_by = s + layer["expert_bias"] if config["use_expert_bias"] else s
    _, idx = jax.lax.top_k(chosen_by, K)
    w = jnp.take_along_axis(s, idx, -1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * config["routed_scaling_factor"]
    return idx, jnp.einsum("ske,sk->se", jax.nn.one_hot(idx, E), w,
                           precision=HI)


def expert_ffn(config, layer, x, precision):
    """Every expert over the whole row, weighted (zero where not chosen:
    computed and discarded, plain not fast)."""
    u = rms_norm(x, layer["moe_norm"], config["norm_eps"])
    _, weights = router(config, layer, u, precision)

    def one(total, expert):
        gate, up, down, w = expert
        return total + w[:, None] * swiglu(u, gate, up, down, precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        layer["w_gate"], layer["w_up"], layer["w_down"], weights.T))
    return x + out


def _at(stack: dict, i: int) -> dict:
    return {name: leaf[i] for name, leaf in stack.items()}


def hidden(config, weights, tokens, precision="highest", keep=None):
    """tokens [B, S] → final-norm hidden [B, S, D]. ``keep``, a dict,
    is given every convolution layer's ``z`` [B, S, D] (under ``z``) and
    every expert layer's chosen experts [B, S, k] (under ``experts``):
    the tests read the carried state and the routing from it."""
    layers = (weights["attn"]["wq"].shape[0]
              + weights["conv"]["w_in"].shape[0])
    x = weights["embed"][tokens]
    seen = {"attn": 0, "conv": 0, "dense": 0, "moe": 0}
    rows = lambda fn: jax.lax.map(jax.checkpoint(fn), x)
    for op, ffn in layer_kinds(config, layers):
        layer = _at(weights[op], seen[op])
        if op == "attn":
            x = rows(lambda row: attention(config, layer, row, precision))
        else:
            x, z = jax.lax.map(jax.checkpoint(
                lambda row: short_conv(config, layer, row, precision)), x)
            if keep is not None:
                keep.setdefault("z", []).append(z)
        seen[op] += 1
        block = _at(weights[ffn], seen[ffn])
        if ffn == "dense":
            x = rows(lambda row: dense_ffn(config, block, row, precision))
        else:
            if keep is not None:
                keep.setdefault("experts", []).append(jax.vmap(
                    lambda row: router(config, block, rms_norm(
                        row, block["moe_norm"], config["norm_eps"]),
                        precision)[0])(x))
            x = rows(lambda row: expert_ffn(config, block, row, precision))
        seen[ffn] += 1
    return rms_norm(x, weights["final_norm"], config["norm_eps"])


def logits(config, weights, tokens, precision="highest"):
    """tokens [B, S] → float32 logits [B, S, V] (the tied head)."""
    x = hidden(config, weights, tokens, precision)
    return jax.lax.map(
        lambda row: matmul(row, weights["embed"].T, precision), x)
