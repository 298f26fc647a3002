"""Kimi-K2.6's plain reference: a decoder of multi-head latent attention
(MLA) under a YaRN rotary rule, a dense SwiGLU MLP in the first layer
and sigmoid-routed SwiGLU experts beside one shared expert in the
others, written out plainly.

``config.json`` of moonshotai/Kimi-K2.6 (``model_type: kimi_k2``, the
DeepSeek-V3 block): 61 pre-norm layers of hidden 7,168, 64 heads, 384
routed experts of width 2,048, 8 a token, one shared, an untied head
over 163,840 ids. With ``rms(x, g) = x / sqrt(mean(x²) + eps) · g``
(eps 1e-5), layer ``l`` is::

    h    = rms(x, w_in)
    c_q  = rms(h · W_qa, g_q)                           # 1,536
    [q_nope (128) ‖ q_pe (64)] a head = c_q · W_qb      # 64 heads
    [c_kv (512) ‖ k_pe (64)] = h · W_kva;  c_kv = rms(c_kv, g_kv)
    q_pe, k_pe = rope(q_pe, pos), rope(k_pe, pos)       # k_pe one for all heads
    k_nope a head = c_kv · W_UK,h;  v a head = c_kv · W_UV,h      # 128, 128
    a    = softmax over j <= i of (q_nope·k_nope + q_pe·k_pe) · s, times v
    x1   = x + concat(a) · W_o
    g    = rms(x1, w_post)
    x2   = x1 + W_down(silu(W_gate g) ⊙ W_up g)                   # l = 0
    x2   = x1 + Σ_{e in top 8 of sigmoid(g · W_r) + bias} w_e · E_e(g)
              + E_shared(g)                                       # l > 0

``w`` are the chosen experts' scores (without the bias) over their sum,
times ``routed_scaling_factor`` 2.827; ``n_group = topk_group = 1``, so
the published group step chooses among all. The rotary rule is YaRN:
``theta`` 50,000 over the 64 rotary dimensions, ``factor`` 64 over an
original context of 4,096, ``beta_fast`` 32, ``beta_slow`` 1
(`yarn_frequencies`); ``mscale = mscale_all_dim = 1`` leave cos and sin
unscaled and give ``s = 192^-0.5 · (0.1 · ln 64 + 1)²``.

This is the *up-projected* form only: every position's keys and values
made from its ``c_kv`` a head, every position against every earlier one.
No cache, no absorption of ``W_UK`` into the query, no kernel; every
expert held here runs over every token of the row, weighted (zero where
not chosen: computed and discarded, plain not fast).

**One chip's share.** The configuration holds ``n_routed_experts`` of
the ``reduced.n_routed_experts.source`` experts the router scores (the
block of ``deployment.rank``), and ``vocab_size`` rows of the tables;
what a token routes to experts held elsewhere adds nothing here.

**How the weights are held.** Made at float32 from the seed with the
program's own ``jax.random`` calls (one jitted program, as the server
makes them), then rounded once to ``torch_dtype`` (bfloat16) but for
the leaves the program reads at float32 (`FLOAT32`). ``kv_b_proj`` is
held as the server holds it, split a head into ``w_uk`` and ``w_uv``
[H, 512, 128]. Every use casts back to float32 and computes there at
``Precision.HIGHEST``.

**In blocks.** A served request is up to 17,664 positions beside 7 GB
of weights. Attention runs a head at a time and `QUERY_ROWS` queries at
a time (a [rows, S] score block, never [S, S]), the head's keys and
values made inside its turn; the dense MLP and the experts run
`TOKEN_ROWS` tokens at a time; `logits` gives back the final hidden
states and the head unmultiplied (``reference/smallthinker.py Logits``),
and the rows that are read are multiplied then, 512 at a time.

Departures from the published code, each in the configuration's
``assumed``: the MoonViT tower is outside (token ids only); the rotary
pairs are (i, i + 32) (``rotate_half``) where the published code turns
interleaved pairs (2i, 2i + 1), which with seeded weights is a
permutation of ``W_qb``'s and ``W_kva``'s columns; the chosen weights'
sum carries ``+ 1e-6`` as the program's ``route`` does (published
``+ 1e-20``).

``precision``: "highest" is the reference; "int8" the control (both
inputs of every projection's, the router's, every expert's and the
head's matmul rounded to int8, ``reference/plain.py``): the step below
bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.plain import _trunc, matmul
from reference.plain import rms_norm as rms
from reference.smallthinker import Logits

HI = jax.lax.Precision.HIGHEST
# What the program reads at float32, and so holds at float32.
FLOAT32 = {"attn_norm", "q_norm", "kv_norm", "mlp_norm", "moe_norm",
           "final_norm", "router", "expert_bias"}
QUERY_ROWS = 2048   # queries a block of a head's attention
TOKEN_ROWS = 2048   # tokens a block of the MLP and of the experts


def held(config: dict) -> tuple:
    """(first, count, routed): the routed experts held here among those
    the router scores."""
    count = config["n_routed_experts"]
    cut = config.get("reduced", {}).get("n_routed_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def yarn_frequencies(config: dict):
    """Inverse frequencies of the 32 rotary pairs under the published
    ``rope_scaling`` (module docstring)."""
    rule = config["rope_scaling"]
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    factor = float(rule["factor"])
    orig = float(rule["original_max_position_embeddings"])

    def pair(turns):
        return dim * math.log(orig / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(pair(rule["beta_fast"])), 0)
    high = min(math.ceil(pair(rule["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    freqs = theta ** (-2 * i / dim)
    ramp = np.clip((i - low) / (high - low if high != low else 1e-3), 0, 1)
    return jnp.asarray(freqs / factor * ramp + freqs * (1 - ramp),
                       jnp.float32)


def softmax_scale(config: dict) -> float:
    rule = config["rope_scaling"]
    m = 0.1 * rule["mscale_all_dim"] * math.log(rule["factor"]) + 1.0
    return (config["qk_nope_head_dim"]
            + config["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, positions, freqs):
    """x [S, H, 64]; pairs are (i, i + 32), as ``rotate_half`` pairs them."""
    half = x.shape[-1] // 2
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded weights stacked by kind over the layers, in the program's
    order of draws (module docstring: float32 draws, rounded once to
    ``torch_dtype`` but for `FLOAT32`)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    f, fm = config["intermediate_size"], config["moe_intermediate_size"]
    _, count, routed = held(config)
    L, ld = layers, min(config["first_k_dense_replace"], layers)
    lm = L - ld
    held_as = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    fan = lambda n: 1.0 / math.sqrt(n)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def make():
        k = jax.random.split(jax.random.key(seed), 19)
        tree = {
            "embed": _trunc(k[0], (config["vocab_size"], d), 0.02),
            "attn": {
                "attn_norm": ones(L, d),
                "wq_a": _trunc(k[1], (L, d, rq), fan(d)),
                "q_norm": ones(L, rq),
                "wq_b": _trunc(k[2], (L, rq, h * (dn + dr)), fan(rq)),
                "wkv_a": _trunc(k[3], (L, d, r + dr), fan(d)),
                "kv_norm": ones(L, r),
                "w_uk": _trunc(k[4], (L, h, r, dn), fan(r)),
                "w_uv": _trunc(k[5], (L, h, r, dv), fan(r)),
                "wo": _trunc(k[6], (L, h * dv, d), fan(h * dv))},
            "dense": {
                "mlp_norm": ones(ld, d),
                "w_gate": _trunc(k[7], (ld, d, f), fan(d)),
                "w_up": _trunc(k[8], (ld, d, f), fan(d)),
                "w_down": _trunc(k[9], (ld, f, d), fan(f))},
            "moe": {
                "moe_norm": ones(lm, d),
                "router": _trunc(k[10], (lm, d, routed), fan(d)),
                "expert_bias": _trunc(k[11], (lm, routed), 0.02),
                "w_gate": _trunc(k[12], (lm, count, d, fm), fan(d)),
                "w_up": _trunc(k[13], (lm, count, d, fm), fan(d)),
                "w_down": _trunc(k[14], (lm, count, fm, d), fan(fm)),
                "ws_gate": _trunc(k[15], (lm, d, fm), fan(d)),
                "ws_up": _trunc(k[16], (lm, d, fm), fan(d)),
                "ws_down": _trunc(k[17], (lm, fm, d), fan(fm))},
            "final_norm": ones(d),
            "lm_head": _trunc(k[18], (d, config["vocab_size"]), 0.02),
        }
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if path[-1].key in FLOAT32
            else leaf.astype(held_as), tree)

    return jax.jit(make)()


def _mm(x, w, precision):
    return matmul(x, w.astype(jnp.float32), precision)


def _blocks(fn, x, rows: int):
    """``fn`` over ``x`` [S, ...] `rows` rows at a time (one shape a
    block: the last is padded and cut)."""
    S = x.shape[0]
    if S <= rows:
        return fn(x)
    n = -(-S // rows)
    padded = jnp.pad(x, ((0, n * rows - S),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape(n, rows, *x.shape[1:]))
    return out.reshape(n * rows, *out.shape[2:])[:S]


# --------------------------------------------------------------- one row
def attention(config, layer, x, h, precision):
    """x [S, D] and its normed ``h`` → x after the attention residual:
    the up-projected form, a head at a time."""
    S = x.shape[0]
    H = config["num_attention_heads"]
    R, dn = config["kv_lora_rank"], config["qk_nope_head_dim"]
    eps = config["rms_norm_eps"]
    freqs, scale = yarn_frequencies(config), softmax_scale(config)
    pos = jnp.arange(S)
    c_q = rms(_mm(h, layer["wq_a"], precision), layer["q_norm"], eps)
    q = _mm(c_q, layer["wq_b"], precision).reshape(S, H, -1)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, freqs)
    kv = _mm(h, layer["wkv_a"], precision)
    c_kv = rms(kv[:, :R], layer["kv_norm"], eps)
    k_pe = rope(kv[:, None, R:], pos, freqs)[:, 0]

    def head(args):
        qn, qp, w_uk, w_uv = args             # [S, 128], [S, 64], [512, 128] x2
        k_nope = _mm(c_kv, w_uk, precision)
        v = _mm(c_kv, w_uv, precision)

        def rows(block):
            qn_b, qp_b, at = block
            scores = (jnp.einsum("qd,kd->qk", qn_b, k_nope, precision=HI)
                      + jnp.einsum("qd,kd->qk", qp_b, k_pe, precision=HI))
            mask = pos[None, :] <= at[:, None]
            probs = jax.nn.softmax(
                jnp.where(mask, scores * scale, -jnp.inf), -1)
            return jnp.einsum("qk,kd->qd", probs, v, precision=HI)

        n = -(-S // QUERY_ROWS)
        pad = n * QUERY_ROWS - S
        cut = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)
                                ).reshape(n, QUERY_ROWS, *t.shape[1:])
        # A padded query row sits at a position past the row's end: it
        # sees every key, and is cut off.
        at = jnp.pad(pos, (0, pad), constant_values=S)
        out = jax.lax.map(jax.checkpoint(rows),
                          (cut(qn), cut(qp), at.reshape(n, QUERY_ROWS)))
        return out.reshape(n * QUERY_ROWS, -1)[:S]

    out = jax.lax.map(head, (q_nope.transpose(1, 0, 2),
                             q_pe.transpose(1, 0, 2),
                             layer["w_uk"], layer["w_uv"]))   # [H, S, 128]
    return x + _mm(out.transpose(1, 0, 2).reshape(S, -1), layer["wo"],
                   precision)


def swiglu(g, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(g, gate, precision)) * _mm(g, up, precision),
               down, precision)


def dense_mlp(config, layer, x, precision):
    def rows(block):
        g = rms(block, layer["mlp_norm"], config["rms_norm_eps"])
        return block + swiglu(g, layer["w_gate"], layer["w_up"],
                              layer["w_down"], precision)

    return _blocks(rows, x, TOKEN_ROWS)


def router(config, block, g, precision):
    """The block's normed input g [S, D] → (chosen experts [S, K] among
    all the router scores, combine weights [S, E_all], zero where not
    chosen)."""
    K = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(g, block["router"], precision))
    _, idx = jax.lax.top_k(s + block["expert_bias"], K)
    w = jnp.take_along_axis(s, idx, -1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * config["routed_scaling_factor"]
    return idx, jnp.einsum("ske,sk->se", jax.nn.one_hot(idx, s.shape[-1]), w,
                           precision=HI)


def experts(config, block, x, precision, keep=None):
    """x [S, D] after attention → x after the expert residual: the
    experts held here, weighted, and the shared expert."""
    first, count, _ = held(config)

    def rows(xb):
        g = rms(xb, block["moe_norm"], config["rms_norm_eps"])
        idx, combine = router(config, block, g, precision)
        mine = combine[:, first:first + count]

        def one(total, expert):
            gate, up, down, w = expert
            return total + w[:, None] * swiglu(g, gate, up, down,
                                               precision), None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(xb), (
            block["w_gate"], block["w_up"], block["w_down"], mine.T))
        routed = routed + swiglu(g, block["ws_gate"], block["ws_up"],
                                 block["ws_down"], precision)
        return xb + routed, idx

    if keep is None:
        return _blocks(lambda xb: rows(xb)[0], x, TOKEN_ROWS)
    out, idx = rows(x)
    keep.setdefault("experts", []).append(idx)
    return out


def _at(stack: dict, i: int) -> dict:
    return {name: leaf[i] for name, leaf in stack.items()}


def row_hidden(config, weights, tokens, precision, keep=None):
    """tokens [S] → final-norm hidden [S, D]."""
    layers = weights["attn"]["attn_norm"].shape[0]
    dense = weights["dense"]["mlp_norm"].shape[0]
    eps = config["rms_norm_eps"]
    x = weights["embed"][tokens].astype(jnp.float32)
    for l in range(layers):
        layer = _at(weights["attn"], l)
        h = rms(x, layer["attn_norm"], eps)
        x = attention(config, layer, x, h, precision)
        if l < dense:
            x = dense_mlp(config, _at(weights["dense"], l), x, precision)
        else:
            x = experts(config, _at(weights["moe"], l - dense), x, precision,
                        keep)
    return rms(x, weights["final_norm"], eps)


def hidden(config, weights, tokens, precision="highest", keep=None):
    """tokens [B, S] → final-norm hidden [B, S, D]. ``keep``, a dict, is
    given every expert layer's chosen experts [B, S, K] (under
    ``experts``): the tests read them."""
    if keep is None:
        return jax.lax.map(
            lambda row: row_hidden(config, weights, row, precision), tokens)
    rows = [{} for _ in tokens]
    out = jnp.stack([row_hidden(config, weights, row, precision, kept)
                     for row, kept in zip(tokens, rows)])
    keep["experts"] = [jnp.stack(layer) for layer
                       in zip(*(kept["experts"] for kept in rows))]
    return out


def logits(config, weights, tokens, precision="highest"):
    """tokens [B, S] → the float32 logits [B, S, V] (the untied head),
    as `Logits`."""
    return Logits(hidden(config, weights, tokens, precision),
                  weights["lm_head"], precision)
