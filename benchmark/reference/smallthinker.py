"""SmallThinker-21BA3B-Instruct's plain reference: a decoder whose
layers mix full attention without positions and rotary sliding-window
attention, each followed by softmax-routed ReGLU experts whose router
reads the layer's input, written out plainly.

``config.json`` of PowerInfer/SmallThinker-21BA3B-Instruct and the
catalog's description of the family: 52 pre-norm layers of hidden 2,560,
28 query heads on 4 key/value heads of 128, 64 experts of width 768, 6 a
token, an untied head over 151,936 ids. With ``rms(x, g) = x /
sqrt(mean(x²) + eps) · g`` (eps 1e-6), layer ``l`` is::

    h  = rms(x, w_in)
    r  = h · W_r                                  # 64 router logits
    q, k, v = h · W_q, h · W_k, h · W_v           # no bias, no q/k norm
    if rope_layout[l]:  q, k = rope(q, pos), rope(k, pos)   # theta 1.5e6,
                                                  # rotate-half, all 128
    a  = softmax over j <= i (and i - j < sliding_window_size if
         sliding_window_layout[l]) of q·k / √128; query head h reads
         key/value head h // 7
    x1 = x + a · W_o
    g  = rms(x1, w_post)
    p  = softmax(r); the 6 largest, renormalised to sum to one
    x2 = x1 + Σ_e p_e · W_down,e(relu(W_gate,e g) ⊙ W_up,e g)

and after the last layer ``rms(·, w_f)`` and the head. Both layouts are
0 on layers 0, 4, 8, ... and 1 elsewhere: a period is ``G W W W``. The
mask of a layer is built from the two layouts' own entries, one [S, S]
square a layer; every expert runs over every token of the row, weighted
(zero where not chosen: computed and discarded, plain not fast): no
sort, no capacity, nothing dropped, no cache, no kernel.

**How the weights are held.** Made at float32 from the seed with the
program's own ``jax.random`` calls (one jitted program, as the server
makes them), then rounded once to ``torch_dtype`` (bfloat16) but for
the leaves the program reads at float32 (`FLOAT32`). Every use casts
back to float32 and computes there at ``Precision.HIGHEST``.

**The head.** A served request is up to 14,336 positions, and its
logits over 151,936 ids would be 8.7 GB beside 7.9 GB of weights. So
`logits` gives back the final hidden states and the head unmultiplied
(`Logits`), and the rows that are read, ``[b, rows]``, are multiplied
then, 512 at a time, and come back as a numpy array: only the served
positions are ever computed.

Departures from the published description, each in the configuration's
``assumed``: where the router taps; ReGLU; no bias and no q/k norm; the
rotary embedding over the whole head; primary experts only.

``precision``: "highest" is the reference; "int8" the control (both
inputs of every projection's, the router's, every expert's and the
head's matmul rounded to int8, ``reference/plain.py``): the step below
bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.plain import _trunc, matmul, rope

HI = jax.lax.Precision.HIGHEST
# What the program reads at float32, and so holds at float32.
FLOAT32 = {"attn_norm", "moe_norm", "final_norm", "router"}
HEAD_ROWS = 512   # rows of logits a block (`Logits`)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded weights stacked over the layers, in the program's order of
    draws (module docstring: float32 draws, rounded once to
    ``torch_dtype`` but for `FLOAT32`)."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    e, f = config["moe_num_primary_experts"], config["moe_ffn_hidden_size"]
    v, L = config["vocab_size"], layers
    held_as = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    fan = lambda n: 1.0 / math.sqrt(n)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def make():
        k = jax.random.split(jax.random.key(seed), 10)
        tree = {
            "embed": _trunc(k[0], (v, d), 0.02),
            "attn": {
                "attn_norm": ones(L, d),
                "router": _trunc(k[1], (L, d, e), fan(d)),
                "wq": _trunc(k[2], (L, d, h * hd), fan(d)),
                "wk": _trunc(k[3], (L, d, kv * hd), fan(d)),
                "wv": _trunc(k[4], (L, d, kv * hd), fan(d)),
                "wo": _trunc(k[5], (L, h * hd, d), fan(h * hd))},
            "moe": {
                "moe_norm": ones(L, d),
                "w_gate": _trunc(k[6], (L, e, d, f), fan(d)),
                "w_up": _trunc(k[7], (L, e, d, f), fan(d)),
                "w_down": _trunc(k[8], (L, e, f, d), fan(f))},
            "final_norm": ones(d),
            "lm_head": _trunc(k[9], (d, v), 0.02),
        }
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if path[-1].key in FLOAT32
            else leaf.astype(held_as), tree)

    return jax.jit(make)()


def _mm(x, w, precision):
    return matmul(x, w.astype(jnp.float32), precision)


# --------------------------------------------------------------- one row
def layer_mask(config: dict, l: int, S: int):
    """[S, S]: query i sees key j."""
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if config["sliding_window_layout"][l]:
        mask &= i - j < config["sliding_window_size"]
    return mask


def attention(config, layer, l, x, h, precision):
    """x [S, D] and its normed ``h`` → x after the attention residual."""
    S = x.shape[0]
    H, KV, Hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    q = _mm(h, layer["wq"], precision).reshape(S, H, Hd)
    k = _mm(h, layer["wk"], precision).reshape(S, KV, Hd)
    v = _mm(h, layer["wv"], precision).reshape(S, KV, Hd)
    if config["rope_layout"][l]:
        pos = jnp.arange(S)
        q = rope(q, pos, config["rope_theta"])
        k = rope(k, pos, config["rope_theta"])
    mask = layer_mask(config, l, S)
    rep = H // KV

    def group(args):      # one key/value head and the query heads on it
        qg, kg, vg = args

        def head(qh):
            scores = jnp.einsum("qd,kd->qk", qh, kg,
                                precision=HI) / math.sqrt(Hd)
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            return jnp.einsum("qk,kd->qd", probs, vg, precision=HI)

        return jax.lax.map(jax.checkpoint(head), qg)

    qg = q.reshape(S, KV, rep, Hd).transpose(1, 2, 0, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(S, H * Hd)
    return x + _mm(out, layer["wo"], precision)


def router(config, layer, h, precision):
    """The layer's normed input h [S, D] → (chosen experts [S, K],
    combine weights [S, E], zero where not chosen)."""
    K = config["moe_num_active_primary_experts"]
    p = jax.nn.softmax(matmul(h, layer["router"], precision), -1)
    w, idx = jax.lax.top_k(p, K)
    if config["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, jnp.einsum("ske,sk->se", jax.nn.one_hot(idx, p.shape[-1]), w,
                           precision=HI)


def experts(config, block, x, weights, precision):
    """x [S, D] after attention → x after the expert residual, under
    the combine weights [S, E] the layer's input gave."""
    g = rms(x, block["moe_norm"], config["rms_norm_eps"])

    def one(total, expert):
        gate, up, down, w = expert
        hidden = (jax.nn.relu(_mm(g, gate, precision))
                  * _mm(g, up, precision))
        return total + w[:, None] * _mm(hidden, down, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        block["w_gate"], block["w_up"], block["w_down"], weights.T))
    return x + routed


def _at(stack: dict, i: int) -> dict:
    return {name: leaf[i] for name, leaf in stack.items()}


def row_hidden(config, weights, tokens, precision, keep=None):
    """tokens [S] → final-norm hidden [S, D]."""
    layers = weights["moe"]["moe_norm"].shape[0]
    eps = config["rms_norm_eps"]
    x = weights["embed"][tokens].astype(jnp.float32)
    for l in range(layers):
        layer, block = _at(weights["attn"], l), _at(weights["moe"], l)
        h = rms(x, layer["attn_norm"], eps)
        chosen, combine = router(config, layer, h, precision)
        if keep is not None:
            keep.setdefault("experts", []).append(chosen)
        x = attention(config, layer, l, x, h, precision)
        x = experts(config, block, x, combine, precision)
    return rms(x, weights["final_norm"], eps)


def hidden(config, weights, tokens, precision="highest", keep=None):
    """tokens [B, S] → final-norm hidden [B, S, D]. ``keep``, a dict, is
    given every layer's chosen experts [B, S, K] (under ``experts``): the
    tests read them."""
    if keep is None:
        return jax.lax.map(
            lambda row: row_hidden(config, weights, row, precision), tokens)
    rows = [{} for _ in tokens]
    out = jnp.stack([row_hidden(config, weights, row, precision, kept)
                     for row, kept in zip(tokens, rows)])
    keep["experts"] = [jnp.stack(layer) for layer
                       in zip(*(kept["experts"] for kept in rows))]
    return out


@jax.tree_util.register_pytree_node_class
class Logits:
    """The logits [B, S, V] of a pass, unmultiplied: the final hidden
    states [B, S, D] and the head [D, V]. ``[b, rows]`` multiplies the
    rows read, `HEAD_ROWS` at a time, and gives a float32 numpy array;
    ``numpy.asarray`` of the whole gives them all (the tests' sizes)."""

    def __init__(self, states, head, precision):
        self.states, self.head, self.precision = states, head, precision

    def tree_flatten(self):
        return (self.states, self.head), self.precision

    @classmethod
    def tree_unflatten(cls, precision, leaves):
        return cls(*leaves, precision)

    @property
    def shape(self):
        return (*self.states.shape[:-1], self.head.shape[-1])

    def __getitem__(self, index):
        rows = self.states[index]
        flat = rows.reshape(-1, rows.shape[-1])
        blocks = []
        for i in range(0, flat.shape[0], HEAD_ROWS):
            block = flat[i:i + HEAD_ROWS]
            pad = HEAD_ROWS - block.shape[0]      # one shape, one program
            out = _head_rows(jnp.pad(block, ((0, pad), (0, 0))), self.head,
                             self.precision)
            blocks.append(np.asarray(out)[:block.shape[0]])
        return np.concatenate(blocks).reshape(*rows.shape[:-1], -1)

    def __array__(self, dtype=None, copy=None):
        out = self[...]
        return out if dtype is None else out.astype(dtype)


@functools.partial(jax.jit, static_argnames="precision")
def _head_rows(rows, head, precision: str):
    return _mm(rows, head, precision)


def logits(config, weights, tokens, precision="highest"):
    """tokens [B, S] → the float32 logits [B, S, V] (the untied head),
    as `Logits`."""
    return Logits(hidden(config, weights, tokens, precision),
                  weights["lm_head"], precision)
