"""K-EXAONE-236B-A23B's plain reference: a decoder of rotary
sliding-window attention in three layers of four beside full attention
without positions, q and k normalised a head, a dense SwiGLU MLP in the
first layer and sigmoid-routed SwiGLU experts beside one shared expert
in the others, written out plainly.

``config.json`` of LGAI-EXAONE/K-EXAONE-236B-A23B (``model_type:
exaone_moe``): 48 layers of hidden 6,144, 64 query heads on 8 key/value
heads of 128, 128 routed experts of width 2,048, 8 a token, one shared,
an untied head over 153,600 ids. With ``rms(x, g) = x / sqrt(mean(x²) +
eps) · g`` (eps 1e-5), layer ``l`` is::

    h  = rms(x, w_in)
    q, k, v = h · W_q, h · W_k, h · W_v            # no bias
    q, k = rms(q, g_q), rms(k, g_k)                # over a head's 128 values
    if layer_types[l] is sliding_attention:
        q, k = rope(q, pos), rope(k, pos)          # theta 1e6, rotate-half,
                                                   # all 128
    a  = softmax over j <= i (and i - j < sliding_window if
         layer_types[l] is sliding_attention) of q·k / √128; query head h
         reads key/value head h // 8
    x1 = x + a · W_o
    g  = rms(x1, w_post)
    x2 = x1 + W_down(silu(W_gate g) ⊙ W_up g)                    # l = 0
    x2 = x1 + Σ_{e in top 8 of sigmoid(g · W_r) + bias} w_e · E_e(g)
            + E_shared(g)                                        # l > 0

``w`` are the chosen experts' scores (without the bias) over their sum,
times ``routed_scaling_factor`` 2.5; ``n_group = topk_group = 1``, so
the group step chooses among all. ``layer_types`` is ``L L L G``
repeated: a mask is built from the layer's own entry, every position
against every earlier one. No cache, no pages, no prefix, no kernel;
every expert held here runs over every token of the row, weighted (zero
where not chosen: computed and discarded, plain not fast).

**One chip's share.** The configuration holds ``num_experts`` of the
``reduced.num_experts.source`` experts the router scores (the block of
``deployment.rank``), and ``vocab_size`` rows of the tables; what a
token routes to experts held elsewhere adds nothing here.

**How the weights are held.** Made at float32 from the seed with the
program's own ``jax.random`` calls (one jitted program, as the server
makes them), then rounded once to ``torch_dtype`` (bfloat16) but for
the leaves the program reads at float32 (`FLOAT32`). Every use casts
back to float32 and computes there at ``Precision.HIGHEST``.

**In blocks.** A served request is up to 13,568 positions beside 7.7 GB
of weights. Attention runs a key/value head's eight query heads one at
a time and `QUERY_ROWS` queries at a time (a [rows, S] score block,
never [S, S]); the dense MLP and the experts run ``TOKEN_ROWS`` tokens
at a time (``reference/kimi_k2.py``'s, whose expert block this is at
other numbers); `logits` gives back the final hidden states and the
head unmultiplied (``reference/smallthinker.py Logits``), and the rows
that are read are multiplied then, 512 at a time.

Departures from the published code, each in the configuration's
``assumed``: the next-token-prediction module (``num_nextn_predict_
layers`` 1, one full-attention layer sharing the head) is no part of the
next-token forward pass and is left out; both norms of a layer stand
before their sublayers (pre-norm), where the catalog's row does not say;
the q/k norm and rotary positions in the window layers only are the
family's convention, not keys of the row; the chosen weights' sum
carries ``+ 1e-6`` as the program's ``route`` does.

``precision``: "highest" is the reference; "int8" the control (both
inputs of every projection's, the router's, every expert's and the
head's matmul rounded to int8, ``reference/plain.py``): the step below
bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.kimi_k2 import TOKEN_ROWS, _blocks, dense_mlp, router, swiglu
from reference.plain import _trunc, matmul, rope
from reference.plain import rms_norm as rms
from reference.smallthinker import Logits

HI = jax.lax.Precision.HIGHEST
# What the program reads at float32, and so holds at float32.
FLOAT32 = {"attn_norm", "q_norm", "k_norm", "mlp_norm", "moe_norm",
           "final_norm", "router", "expert_bias"}
QUERY_ROWS = 2048   # queries a block of a head's attention


def held(config: dict) -> tuple:
    """(first, count, routed): the routed experts held here among those
    the router scores."""
    count = config["num_experts"]
    cut = config.get("reduced", {}).get("num_experts")
    if not cut:
        return 0, count, count
    return config["deployment"]["rank"] * count, count, cut["source"]


def windowed(config: dict, l: int) -> bool:
    return config["layer_types"][l] == "sliding_attention"


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded weights stacked by kind over the layers, in the program's
    order of draws (module docstring: float32 draws, rounded once to
    ``torch_dtype`` but for `FLOAT32`)."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    f, fm = config["intermediate_size"], config["moe_intermediate_size"]
    _, count, routed = held(config)
    L, ld = layers, min(config["first_k_dense_replace"], layers)
    lm = L - ld
    v = config["vocab_size"]
    held_as = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    fan = lambda n: 1.0 / math.sqrt(n)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def make():
        k = jax.random.split(jax.random.key(seed), 17)
        tree = {
            "embed": _trunc(k[0], (v, d), 0.02),
            "attn": {
                "attn_norm": ones(L, d),
                "wq": _trunc(k[1], (L, d, h * hd), fan(d)),
                "wk": _trunc(k[2], (L, d, kv * hd), fan(d)),
                "wv": _trunc(k[3], (L, d, kv * hd), fan(d)),
                "q_norm": ones(L, hd),
                "k_norm": ones(L, hd),
                "wo": _trunc(k[4], (L, h * hd, d), fan(h * hd))},
            "dense": {
                "mlp_norm": ones(ld, d),
                "w_gate": _trunc(k[5], (ld, d, f), fan(d)),
                "w_up": _trunc(k[6], (ld, d, f), fan(d)),
                "w_down": _trunc(k[7], (ld, f, d), fan(f))},
            "moe": {
                "moe_norm": ones(lm, d),
                "router": _trunc(k[8], (lm, d, routed), fan(d)),
                "expert_bias": _trunc(k[9], (lm, routed), 0.02),
                "w_gate": _trunc(k[10], (lm, count, d, fm), fan(d)),
                "w_up": _trunc(k[11], (lm, count, d, fm), fan(d)),
                "w_down": _trunc(k[12], (lm, count, fm, d), fan(fm)),
                "ws_gate": _trunc(k[13], (lm, d, fm), fan(d)),
                "ws_up": _trunc(k[14], (lm, d, fm), fan(d)),
                "ws_down": _trunc(k[15], (lm, fm, d), fan(fm))},
            "final_norm": ones(d),
            "lm_head": _trunc(k[16], (d, v), 0.02),
        }
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if path[-1].key in FLOAT32
            else leaf.astype(held_as), tree)

    return jax.jit(make)()


def _mm(x, w, precision):
    return matmul(x, w.astype(jnp.float32), precision)


# --------------------------------------------------------------- one row
def attention(config, layer, l, x, h, precision):
    """x [S, D] and its normed ``h`` → x after the attention residual, a
    query head at a time, `QUERY_ROWS` queries at a time."""
    S = x.shape[0]
    H, KV, Hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    eps = config["rms_norm_eps"]
    window = config["sliding_window"] if windowed(config, l) else None
    pos = jnp.arange(S)
    q = rms(_mm(h, layer["wq"], precision).reshape(S, H, Hd),
            layer["q_norm"], eps)
    k = rms(_mm(h, layer["wk"], precision).reshape(S, KV, Hd),
            layer["k_norm"], eps)
    v = _mm(h, layer["wv"], precision).reshape(S, KV, Hd)
    if window is not None:
        theta = float(config["rope_parameters"]["rope_theta"])
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    n = -(-S // QUERY_ROWS)
    pad = n * QUERY_ROWS - S
    # A padded query row sits at the row's last position and is cut off.
    at = jnp.pad(pos, (0, pad), constant_values=S - 1).reshape(n, QUERY_ROWS)

    def group(args):      # one key/value head and the query heads on it
        qg, kg, vg = args  # [rep, S, Hd], [S, Hd], [S, Hd]

        def head(qh):
            def rows(block):
                qb, ab = block
                scores = jnp.einsum("qd,kd->qk", qb, kg,
                                    precision=HI) / math.sqrt(Hd)
                mask = pos[None, :] <= ab[:, None]
                if window is not None:
                    mask &= ab[:, None] - pos[None, :] < window
                probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
                return jnp.einsum("qk,kd->qd", probs, vg, precision=HI)

            cut = jnp.pad(qh, ((0, pad), (0, 0))).reshape(n, QUERY_ROWS, Hd)
            out = jax.lax.map(jax.checkpoint(rows), (cut, at))
            return out.reshape(n * QUERY_ROWS, Hd)[:S]

        return jax.lax.map(head, qg)

    rep = H // KV
    qg = q.reshape(S, KV, rep, Hd).transpose(1, 2, 0, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(S, H * Hd)
    return x + _mm(out, layer["wo"], precision)


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
        x = attention(config, layer, l, x, h, precision)
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
