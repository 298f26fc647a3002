"""The decoder both references share, written out plainly.

Mistral-7B (arXiv 2310.06825) and Mixtral-8x7B (arXiv 2401.04088) as
their published ``config.json`` and modeling code describe them:
pre-norm decoder blocks, RMSNorm, rotary embeddings on half-split pairs
(``rotate_half``), grouped-query causal attention, SwiGLU MLP or a
top-2-of-8 mixture of SwiGLU experts with softmax-then-renormalise
routing weights, untied output head. float32 throughout,
``jax.default_matmul_precision("highest")`` (a TPU otherwise runs a
float32 matmul in bfloat16 passes). No kernels, no cache, no batching:
one full forward pass a row.

Weights come from ``--seed`` by the recipe below, which is the recipe
the program's zoo documents for its random init (truncated normal at two
sigmas; std 0.02 for the two vocabulary tables, 1/sqrt(fan_in) for the
projections; norm gains one), drawn with the same ``jax.random`` calls
so that the reference and the program hold the same model without
either handing the other an array.

Departures from the published description, each because the program's
documented training objective is what is being checked:
- the router's auxiliary loss is Switch eq. 4 over first choices
  (``E * sum_e f_e * P_e``, f from the top-1 choice), where the HF code
  counts all top-k choices;
- an expert holds at most ``ceil(T * capacity_factor * k / E)`` tokens a
  step, taken in token order, and a (token, choice) pair beyond that is
  dropped (its weight contributes nothing); the published model drops
  nothing. ``moe_mlp`` reports how many pairs were dropped.

``precision``: "highest" is the reference. "int8" is the *control*: the
same arithmetic with both inputs of every projection's matmul rounded to
int8 first (weights per output channel, activations per row), in the
backward pass's two matmuls as well: the step below bfloat16 that a
later change would be tempted by on a chip with an int8 unit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _trunc(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)


def init_weights(config: dict, layers: int, seed: int) -> dict:
    """Seeded float32 weights, layers stacked on the leading axis."""
    d, f = config["hidden_size"], config["intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    v, L = config["vocab_size"], layers
    experts = config.get("num_local_experts")
    key = jax.random.key(seed)
    ones = lambda: jnp.ones((L, d), jnp.float32)   # a buffer each: donated
    if experts:
        k = jax.random.split(key, 12)
        E = experts
        layer = {
            "attn_norm": ones(),
            "wq": _trunc(k[1], (L, d, q), d ** -0.5),
            "wk": _trunc(k[2], (L, d, kv), d ** -0.5),
            "wv": _trunc(k[3], (L, d, kv), d ** -0.5),
            "wo": _trunc(k[4], (L, q, d), q ** -0.5),
            "moe_norm": ones(),
            "router": _trunc(k[5], (L, d, E), d ** -0.5),
            "w_gate": _trunc(k[6], (L, E, d, f), d ** -0.5),
            "w_up": _trunc(k[7], (L, E, d, f), d ** -0.5),
            "w_down": _trunc(k[8], (L, E, f, d), f ** -0.5),
        }
        head_key = k[9]
    else:
        k = jax.random.split(key, 10)
        layer = {
            "attn_norm": ones(),
            "wq": _trunc(k[1], (L, d, q), d ** -0.5),
            "wk": _trunc(k[2], (L, d, kv), d ** -0.5),
            "wv": _trunc(k[3], (L, d, kv), d ** -0.5),
            "wo": _trunc(k[4], (L, q, d), q ** -0.5),
            "mlp_norm": ones(),
            "w_gate": _trunc(k[5], (L, d, f), d ** -0.5),
            "w_up": _trunc(k[6], (L, d, f), d ** -0.5),
            "w_down": _trunc(k[7], (L, f, d), f ** -0.5),
        }
        head_key = k[8]
    return {"embed": _trunc(k[0], (v, d), 0.02), "layers": layer,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": _trunc(head_key, (d, v), 0.02)}


def synthetic_batch(seed: int, index: int, batch: int, seq_len: int,
                    vocab: int) -> np.ndarray:
    """Batch `index` of the job's data: a Zipf(1) token stream, batch i
    a pure function of (seed, i) (the documented contract of the
    program's ``lm_synthetic`` dataset)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    u = np.random.default_rng((seed, index)).random((batch, seq_len))
    return np.searchsorted(cdf, u, side="right").astype(np.int32)


# ------------------------------------------------------------ arithmetic
def _int8(x, axis):
    """Round to 127 levels a side, scale from the largest magnitude
    along `axis`."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _int8_matmul(x, w):
    """x [T, in] @ w [in, out] with both inputs in int8; the backward
    pass's two matmuls take int8 inputs too (the incoming gradient
    rounded per row, per column for the weight gradient)."""
    return _mm(_int8(x, -1), _int8(w, 0))


def _int8_fwd(x, w):
    xq, wq = _int8(x, -1), _int8(w, 0)
    return _mm(xq, wq), (xq, wq)


def _int8_bwd(saved, g):
    xq, wq = saved
    return _mm(_int8(g, -1), wq.T), _mm(xq.T, _int8(g, 0))


_int8_matmul.defvjp(_int8_fwd, _int8_bwd)


def matmul(x, w, precision: str):
    """x [T, in] @ w [in, out]."""
    if precision == "int8":
        return _int8_matmul(x, w)
    return _mm(x, w)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, positions, theta):
    """x [S, H, Hd]; pairs are (i, i + Hd/2), as ``rotate_half`` pairs them."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(config, layer, x, precision):
    """One row [S, D]: grouped-query causal attention, full square (query
    head h reads key/value head h // (H / KV), as ``repeat_kv`` lays
    them out)."""
    S = x.shape[0]
    H, KV, Hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    h = rms_norm(x, layer["attn_norm"], config["rms_norm_eps"])
    pos = jnp.arange(S)
    q = rope(matmul(h, layer["wq"], precision).reshape(S, H, Hd), pos,
             config["rope_theta"])
    k = rope(matmul(h, layer["wk"], precision).reshape(S, KV, Hd), pos,
             config["rope_theta"])
    v = matmul(h, layer["wv"], precision).reshape(S, KV, Hd)
    # One key/value head and the query heads that share it at a time:
    # the full [S, S] square of a group, never of all heads at once.
    rep = H // KV
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):
        qg, kg, vg = args                     # [rep, S, Hd], [S, Hd], [S, Hd]
        scores = jnp.einsum("rqd,kd->rqk", qg, kg,
                            precision=jax.lax.Precision.HIGHEST) / math.sqrt(Hd)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("rqk,kd->rqd", probs, vg,
                          precision=jax.lax.Precision.HIGHEST)

    qg = q.reshape(S, KV, rep, Hd).transpose(1, 2, 0, 3)
    out = jax.lax.map(jax.checkpoint(group),
                      (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(S, H * Hd)
    return x + matmul(out, layer["wo"], precision)


def dense_mlp(config, layer, x, precision):
    h = rms_norm(x, layer["mlp_norm"], config["rms_norm_eps"])
    gate = jax.nn.silu(matmul(h, layer["w_gate"], precision))
    return x + matmul(gate * matmul(h, layer["w_up"], precision),
                      layer["w_down"], precision)


def route(config, layer, h, capacity_factor, precision):
    """h [T, D] (all the step's tokens, in order) -> combine weights
    [T, E] (zero where not chosen or dropped), the auxiliary loss, and
    the number of (token, choice) pairs dropped."""
    E, K = config["num_local_experts"], config["num_experts_per_tok"]
    T = h.shape[0]
    probs = jax.nn.softmax(matmul(h, layer["router"], precision), -1)
    top_p, top_i = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, E, dtype=jnp.float32)      # [T, K, E]
    capacity = max(int(math.ceil(T * capacity_factor * K / E)), K)
    flat = chosen.reshape(T * K, E)                # token-major pairs
    before = jnp.cumsum(flat, 0) - flat
    kept = (jnp.sum(before * flat, -1) < capacity).reshape(T, K)
    weights = jnp.einsum("tke,tk->te", chosen,
                         top_p * kept.astype(jnp.float32))
    aux = E * jnp.sum(jnp.mean(chosen[:, 0, :], 0) * jnp.mean(probs, 0))
    return weights, aux, jnp.sum(1.0 - kept.astype(jnp.float32))


def experts(config, layer, h, weights, precision):
    """Every expert's SwiGLU over the row, weighted: rows of `weights`
    that are zero contribute nothing (computed and discarded: plain, not
    fast). One contraction over the expert axis, so that where the
    expert weights are split over chips each chip computes its own."""
    if precision == "int8":
        run = jax.vmap(lambda g, u, d: matmul(
            jax.nn.silu(matmul(h, g, precision)) * matmul(h, u, precision),
            d, precision))
        y = run(layer["w_gate"], layer["w_up"], layer["w_down"])
    else:
        hi = jax.lax.Precision.HIGHEST
        gate = jax.nn.silu(jnp.einsum("sd,edf->esf", h, layer["w_gate"],
                                      precision=hi))
        up = jnp.einsum("sd,edf->esf", h, layer["w_up"], precision=hi)
        y = jnp.einsum("esf,efd->esd", gate * up, layer["w_down"],
                       precision=hi)
    return jnp.einsum("se,esd->sd", weights, y,
                      precision=jax.lax.Precision.HIGHEST)


def hidden(config, weights, tokens, precision="highest",
           capacity_factor=1.25):
    """tokens [B, S] -> (final hidden [B, S, D], mean aux loss, pairs
    dropped). Layer by layer (a scan over the stacked weights, so one
    layer is compiled once); within a layer one row at a time."""
    B, S = tokens.shape
    x = weights["embed"][tokens]
    moe = bool(config.get("num_local_experts"))
    L = weights["layers"]["wq"].shape[0]

    def block(carry, layer):
        x, aux_sum, dropped = carry
        x = jax.lax.map(jax.checkpoint(
            lambda row: attention(config, layer, row, precision)), x)
        if moe:
            h = rms_norm(x, layer["moe_norm"], config["rms_norm_eps"])
            w, aux, drop = route(config, layer, h.reshape(B * S, -1),
                                 capacity_factor, precision)
            x = x + jax.lax.map(jax.checkpoint(
                lambda hw: experts(config, layer, hw[0], hw[1], precision)),
                (h, w.reshape(B, S, -1)))
            aux_sum, dropped = aux_sum + aux, dropped + drop
        else:
            x = jax.lax.map(jax.checkpoint(
                lambda row: dense_mlp(config, layer, row, precision)), x)
        return (x, aux_sum, dropped), None

    (x, aux_sum, dropped), _ = jax.lax.scan(
        block, (x, jnp.zeros(()), jnp.zeros(())), weights["layers"])
    x = rms_norm(x, weights["final_norm"], config["rms_norm_eps"])
    return x, aux_sum / L, dropped


def logits(config, weights, tokens, precision="highest"):
    """tokens [B, S] -> float32 logits [B, S, V]."""
    x, _, _ = hidden(config, weights, tokens, precision)
    return matmul(x, weights["lm_head"], precision)


def lm_loss(config, weights, tokens, precision="highest",
            capacity_factor=1.25):
    """Next-token loss of rows [B, S]: the input is the row shifted
    right behind token 0, every position counts. Returns (loss, (cross
    entropy, aux, pairs dropped))."""
    inputs = jnp.concatenate([jnp.zeros_like(tokens[:, :1]), tokens[:, :-1]], 1)
    x, aux, dropped = hidden(config, weights, inputs, precision,
                             capacity_factor)

    def row_nll(args):
        row, labels = args
        lg = matmul(row, weights["lm_head"], precision)
        return -jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                                    labels[:, None], 1)[:, 0].sum()

    ce = jnp.sum(jax.lax.map(jax.checkpoint(row_nll), (x, tokens))) \
        / tokens.size
    coef = config.get("router_aux_loss_coef", 0.0) \
        if config.get("num_local_experts") else 0.0
    return ce + coef * aux, (ce, aux, dropped)


# -------------------------------------------------------------- training
def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def adamw_step(weights, grads, m, v, step, *, lr, wd, clip, b1=0.9, b2=0.95,
               eps=1e-8):
    """Clip by global norm, then AdamW (decoupled decay on every leaf)."""
    norm = global_norm(grads)
    scale = jnp.where(norm < clip, 1.0, clip / norm) if clip else 1.0
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    t = step + 1
    new = jax.tree.map(
        lambda w, a, b: w - lr * ((a / (1 - b1 ** t))
                                  / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
                                  + wd * w),
        weights, m, v)
    return new, m, v


def train_steps(config, layers, seed, *, steps, batch, seq_len, lr, wd, clip,
                precision="highest", capacity_factor=1.25, shardings=None):
    """Follow the job's first `steps` steps; per step the loss, the
    gradient's global norm as the optimizer gets it, and the pairs
    dropped; leaf by leaf, the norm of the first gradient as Adam got it
    (its first moment after one step over 1 - b1, the way it is read
    from the job's state) and of the parameters' change after `steps`.
    The state is updated in place (donated): float32 weights and both
    moments of the cell's model are most of a chip."""
    weights = init_weights(config, layers, seed)
    placed = None
    if shardings is not None:
        placed = shardings(weights)
        weights = jax.device_put(weights, placed)
    m = jax.tree.map(jnp.zeros_like, weights)
    v = jax.tree.map(jnp.zeros_like, weights)

    def one(weights, m, v, tokens, step):
        (loss, (ce, aux, dropped)), grads = jax.value_and_grad(
            lambda w: lm_loss(config, w, tokens, precision, capacity_factor),
            has_aux=True)(weights)
        norm = global_norm(grads)
        weights, m, v = adamw_step(weights, grads, m, v, step, lr=lr, wd=wd,
                                   clip=clip)
        return weights, m, v, loss, norm, dropped

    # Where the weights are split over chips (placement only), the
    # updated weights and moments stay where they were.
    one = jax.jit(one, donate_argnums=(0, 1, 2),
                  out_shardings=None if placed is None else
                  (placed, placed, placed, None, None, None))
    out, grad0 = [], {}
    for i in range(steps):
        tokens = jnp.asarray(synthetic_batch(seed, i, batch, seq_len,
                                             config["vocab_size"]))
        weights, m, v, loss, norm, dropped = one(weights, m, v, tokens,
                                                 jnp.float32(i))
        out.append({"step": i, "loss": float(loss), "grad_norm": float(norm),
                    "pairs_dropped": float(dropped)})
        if i == 0:
            grad0 = {k: n / 0.1 for k, n in leaf_norms(m).items()}
    del m, v
    start = init_weights(config, layers, seed)
    if placed is not None:
        start = jax.device_put(start, placed)
    update = leaf_norms(jax.tree.map(jnp.subtract, weights, start))
    return {"steps": out, "grad0_leaf": grad0, "update_leaf": update}


def leaf_norms(tree) -> dict:
    """{leaf's path: its norm}, paths as "layers/wq"."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path):
            float(jnp.sqrt(jnp.sum(jnp.square(leaf)))) for path, leaf in flat}
