"""The Gated DeltaNet mixer (gated delta rule, arXiv 2412.06464): a
chunked form for a sequence and a one-step update for decode.

With Hk key heads and Hv value heads (value head ``h`` reads key head
``h // (Hv/Hk)``), key size dk, value size dv and a depthwise causal
convolution of kernel K over the ``2·Hk·dk + Hv·dv`` channels
``[q | k | v]``::

    [q | k | v | z] = u · W_qkvz      (grouped by key head, as published)
    [b | a] = u · W_ba                (Hv | Hv, grouped likewise)
    [q | k | v] ← silu(conv1d([q | k | v]))           (no bias)
    q, k ← l2norm(·);  q ← q / √dk                    (per head)
    β = sigmoid(b);  g = −exp(A_log) · softplus(a + dt_bias)
    S_t = e^{g_t} S_{t−1} + k_t ⊗ β_t (v_t − (e^{g_t} S_{t−1})ᵀ k_t)
    o_t = S_tᵀ q_t                                    (S ∈ R^{Hv×dk×dv})
    out = [rms(o_t) · w ⊙ silu(z_t)] · W_out          (norm over dv, gain w)

What a sequence carries between tokens is ``S`` (float32) and the last
K−1 inputs of the convolution. Where Mamba-2's update
(``ops/mamba2.py``) is a decay and an outer product, this one *reads the
state to form what it writes* (``v − Sᵀk``). The recurrence (g, β, the
norms of q and k, the state and every product that feeds it) runs in
float32 at full precision; the projections run in the compute dtype.

`chunked` is the sequence form: positions are cut into chunks of
``chunk`` (64, the modelling code's). With ``γ`` the running sum of g
inside a chunk and ``Γ_ij = e^{γ_i − γ_j}``, the rule's dependence of
each position's write on the earlier writes of its chunk is the
unit-lower-triangular system ::

    (I + tril(diag(β) K Kᵀ ⊙ Γ, −1)) · [W | U] = diag(β) [K ⊙ e^γ | V]

solved once a chunk (all chunks at once); a `lax.scan` over the chunks
then carries ``S``: ``V' = U − W S``, ``O = (Q ⊙ e^γ) S + tril(Q Kᵀ ⊙
Γ) V'``, ``S ← e^{γ_C} S + (K ⊙ e^{γ_C − γ})ᵀ V'``. `step` is one
position. The sequential recurrence above is what decides: the tests
hold both to it.

A decode step's position goes through `step_rows`, over the cache's
leaf of every layer's rows in place. Which code runs it is read from
where it is traced (`update_kernel`): on a TPU that holds the leaf whole,
the Pallas kernel of ``ops/gdn_update.py``, which brings a block of
(rows x heads) into fast memory once, takes both products and the
update from that copy and writes it back once (`step`'s one-read form,
without the second pass the compiler needs for it); everywhere else
(the CPU of the tests, a mesh that shards rows or heads) `step` on the
rows' slice and ``common.put_layer``. `step` is the kernel's oracle
in ``tests/test_gdn_update.py`` and what ``benchmark/kernels/
gdn_update.py`` counts the floor from. Every sequence (a prefill, a
training batch) is `chunked` on either.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from polyaxon_tpu.models.common import _w, project, put_layer, rms_norm
from polyaxon_tpu.ops.gdn_update import gdn_update
from polyaxon_tpu.parallel import compat

HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def conv_dim(cfg) -> int:
    """Channels of the convolution: every key head's q and k and every
    value head's v."""
    return (2 * cfg.gdn_key_heads * cfg.gdn_key_dim
            + cfg.gdn_value_heads * cfg.gdn_value_dim)


def split_projections(cfg, qkvz: jax.Array, ba: jax.Array):
    """``u · W_qkvz`` [..., 2·Hk·dk + 2·Hv·dv] and ``u · W_ba`` [...,
    2·Hv], each laid out key head by key head as the published
    checkpoint has them (a key head's q, k, then its value heads' v and
    z; its value heads' b, then a) → (the convolution's input ``[q | k |
    v]`` flat [..., conv_dim], z [..., Hv, dv], b and a [..., Hv])."""
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    R = Hv // Hk
    lead = qkvz.shape[:-1]
    by_head = qkvz.reshape(*lead, Hk, 2 * dk + 2 * R * dv)
    q = by_head[..., :dk].reshape(*lead, Hk * dk)
    k = by_head[..., dk:2 * dk].reshape(*lead, Hk * dk)
    v = by_head[..., 2 * dk:2 * dk + R * dv].reshape(*lead, Hv * dv)
    z = by_head[..., 2 * dk + R * dv:].reshape(*lead, Hv, dv)
    ba = ba.reshape(*lead, Hk, 2 * R)
    return (jnp.concatenate([q, k, v], axis=-1), z,
            ba[..., :R].reshape(*lead, Hv), ba[..., R:].reshape(*lead, Hv))


def _heads(cfg, qkv: jax.Array):
    """The convolution's output [..., conv_dim] → q, k [..., Hv, dk]
    (l2-normalised, q scaled, each key head repeated for its value
    heads) and v [..., Hv, dv], float32."""
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    qkv = qkv.astype(jnp.float32)
    lead = qkv.shape[:-1]
    q = qkv[..., :Hk * dk].reshape(*lead, Hk, dk)
    k = qkv[..., Hk * dk:2 * Hk * dk].reshape(*lead, Hk, dk)
    v = qkv[..., 2 * Hk * dk:].reshape(*lead, Hv, dv)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    q = jnp.repeat(unit(q) * dk ** -0.5, Hv // Hk, axis=-2)
    return q, jnp.repeat(unit(k), Hv // Hk, axis=-2), v


def chunked(q, k, v, g, beta, chunk: int, state0=None):
    """The rule over a sequence, chunk by chunk. ``q``/``k`` [B, S, H,
    dk] (as `_heads` gives them), ``v`` [B, S, H, dv], ``g`` [B, S, H]
    (the log-decay, ≤ 0) and ``beta`` [B, S, H] (a padded position has
    both 0, which leaves the state as it was), ``state0`` [B, H, dk,
    dv] or None (zeros); all float32. Returns (o [B, S, H, dv], the
    state after the last position [B, H, dk, dv])."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    pad = -S % C
    if pad:
        widen = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = widen(q), widen(k), widen(v), widen(g), widen(beta)
    nc = (S + pad) // C
    # [B, chunks, H, C, ...]
    by_chunk = lambda t: jnp.moveaxis(t.reshape(B, nc, C, *t.shape[2:]), 2, 3)
    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    g, beta = by_chunk(g), by_chunk(beta)           # [B, nc, H, C]
    cum = jnp.cumsum(g, axis=-1)                    # γ: the decay up to and with i
    seg = cum[..., :, None] - cum[..., None, :]     # γ_i − γ_j
    causal = jnp.tril(jnp.ones((C, C), bool))
    gamma = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    k_beta = k * beta[..., None]
    # How each position's write depends on the chunk's earlier writes.
    within = jnp.einsum("bzhik,bzhjk->bzhij", k_beta, k, precision=HI) * gamma
    system = jnp.eye(C, dtype=jnp.float32) + jnp.where(
        jnp.tril(jnp.ones((C, C), bool), -1), within, 0.0)
    rhs = jnp.concatenate(
        [k_beta * jnp.exp(cum)[..., None], v * beta[..., None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    w, u = solved[..., :dk], solved[..., dk:]
    local = jnp.einsum("bzhik,bzhjk->bzhij", q, k, precision=HI) * gamma
    q_in = q * jnp.exp(cum)[..., None]              # reads the entering state
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]   # decayed to the end
    keep = jnp.exp(cum[..., -1])                    # [B, nc, H]
    if state0 is None:
        state0 = jnp.zeros((B, H, dk, dv), jnp.float32)

    def carry(state, inputs):
        q_i, k_i, u_i, w_i, local_i, keep_i = inputs
        fresh = u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, state, precision=HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_i, state, precision=HI)
             + jnp.einsum("bhij,bhjv->bhiv", local_i, fresh, precision=HI))
        state = (keep_i[..., None, None] * state
                 + jnp.einsum("bhck,bhcv->bhkv", k_i, fresh, precision=HI))
        return state, o

    chunks_first = lambda t: jnp.moveaxis(t, 1, 0)
    final, o = jax.lax.scan(carry, state0, tuple(map(
        chunks_first, (q_in, k_out, u, w, local, keep))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)   # [B, nc, C, H, dv]
    return o.reshape(B, nc * C, H, dv)[:, :S], final


def step(q, k, v, g, beta, state):
    """One position: ``q``/``k`` [B, H, dk], ``v`` [B, H, dv], ``g``/
    ``beta`` [B, H], ``state`` [B, H, dk, dv], float32 → (o [B, H, dv],
    new state).

    Both reads of the state are taken from the state *before* its
    write, in one pass over it: ``Sᵀk`` for the write, and ``Sᵀq`` for
    the output, which by the rule is ``o = S_tᵀq = α·S_{t−1}ᵀq + δ·(k·q)``
    with ``δ = β(v − α·S_{t−1}ᵀk)`` what is written. So the state is
    read once for the two products and once more for its update, where
    reading the new state back for ``o`` would be a third pass. (Compiled
    for a TPU those are two operations, 0.365 + 0.815 ms a layer at 128
    rows of 32 heads of 128 x 128: the write needs the whole reduction
    first. ``ops/gdn_update.py`` is this function with the state held in
    fast memory between the two, 0.82 ms: `step_rows`.)"""
    alpha = jnp.exp(g)[..., None]                   # [B, H, 1]
    kq = jnp.stack([k, q], axis=-1)                 # [B, H, dk, 2]
    read = jnp.einsum("bhkv,bhkj->bhjv", state, kq, precision=HI)
    delta = beta[..., None] * (v - alpha * read[:, :, 0])
    o = alpha * read[:, :, 1] + delta * jnp.sum(k * q, -1, keepdims=True)
    new = alpha[..., None] * state + k[..., :, None] * delta[..., None, :]
    return o, new


def _gated_out(cfg, layer: dict, o: jax.Array, z: jax.Array) -> jax.Array:
    """``o`` [..., Hv, dv] float32 and the gate ``z`` [..., Hv, dv] → the
    mixer's output [..., D]: RMS norm over each head's dv (gain ``w``,
    one vector for every head), times silu(z), W_out."""
    dt = cfg.dtype
    lead = o.shape[:-2]
    normed = rms_norm(o, layer["out_norm"], cfg.norm_eps)
    gated = normed * jax.nn.silu(z.astype(jnp.float32))
    return gated.reshape(*lead, -1).astype(dt) @ _w(layer["w_out"], dt)


def _recurrence_inputs(cfg, layer: dict, u: jax.Array, conv_tail: jax.Array,
                       real_len=None):
    """``u`` [B, S, D] behind ``conv_tail`` → what the rule takes: (q, k
    [B, S, Hv, dk], v [B, S, Hv, dv], g, beta [B, S, Hv], float32; the
    gate z; the new tail). Positions at or past ``real_len`` have g and
    beta 0."""
    dt_ = cfg.dtype
    S, K = u.shape[1], cfg.conv_kernel
    qkv, z, b, a = split_projections(
        cfg, project(layer, "w_qkvz", u, dt_), u @ _w(layer["w_ba"], dt_))
    seq = jnp.concatenate([conv_tail.astype(dt_), qkv], axis=1)
    taps = layer["conv_w"].astype(jnp.float32)      # [conv_dim, K]
    conv = sum(taps[:, j] * seq[:, j:j + S].astype(jnp.float32)
               for j in range(K))
    q, k, v = _heads(cfg, jax.nn.silu(conv))
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
    if real_len is None:
        tail = seq[:, S:]
    else:
        real = (jnp.arange(S) < real_len)[None, :, None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
        tail = jax.lax.dynamic_slice_in_dim(seq, real_len, K - 1, axis=1)
    return q, k, v, g, beta, z, tail


def mixer(cfg, layer: dict, u: jax.Array, conv_tail: jax.Array,
          state: jax.Array, real_len=None):
    """The mixer over ``u`` [B, S, D] (already normalised) behind what
    the sequence carries: ``conv_tail`` [B, K−1, conv_dim] (the
    convolution's inputs of the K−1 positions before, zeros at the
    start) and ``state`` [B, Hv, dk, dv] float32 (zeros there).
    Positions at or past ``real_len`` (traced; None: all real) are
    padding: they leave the state alone, and the tail returned is that
    of the last real position. Returns (out [B, S, D], new tail, new
    state)."""
    S = u.shape[1]
    with jax.named_scope("gated_delta"):
        q, k, v, g, beta, z, tail = _recurrence_inputs(
            cfg, layer, u, conv_tail, real_len)
        if S == 1:
            o, state = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            state)
            o = o[:, None]
        else:
            o, state = chunked(q, k, v, g, beta, cfg.chunk_size, state)
        return _gated_out(cfg, layer, o, z), tail, state


def update_kernel() -> bool:
    """Whether `step_rows` is the Pallas kernel (``ops/gdn_update.py``):
    on a TPU, decided from the backend as ``models/moe.py
    _grouped_kernel`` decides its own, where the call is handed the leaf
    whole. Elsewhere (the CPU of every test; a mesh that shards rows or
    heads, where the partitioner can split `step` and cannot split a
    kernel) it is `step` and ``common.put_layer``."""
    return jax.default_backend() == "tpu" and compat.unsharded()


def step_rows(stack: jax.Array, i: int, q, k, v, g, beta, started):
    """`step` for the first B rows of layer ``i`` of the decode cache's
    leaf ``stack`` [L, rows ≥ B, Hv, dk, dv], in place: a row that has
    not ``started`` ([B] bool) starts from zeros whatever the leaf
    holds. Returns (o [B, Hv, dv], the leaf). On a TPU one kernel that
    reads each row's state once and writes it once (`update_kernel`);
    elsewhere the rows' slice through `step` and back."""
    if update_kernel():
        return gdn_update(stack, i, q, k, v, g, beta, started)
    B = q.shape[0]
    state = jnp.where(started[:, None, None, None], stack[i, :B], 0.0)
    o, state = step(q, k, v, g, beta, state)
    return o, put_layer(stack, state, i)


def decode_mixer(cfg, layer: dict, u: jax.Array, conv_tail: jax.Array,
                 stack: jax.Array, i: int, started: jax.Array):
    """`mixer` for one position a row over the decode cache's leaf:
    ``u`` [B, 1, D], ``conv_tail`` [B, K−1, conv_dim] (zeros for a row
    that has not started), ``stack`` [L, rows ≥ B, Hv, dk, dv] whose
    layer ``i`` holds the rows' states, updated in place (`step_rows`).
    Returns (out [B, 1, D], new tail, the leaf)."""
    with jax.named_scope("gated_delta"):
        q, k, v, g, beta, z, tail = _recurrence_inputs(cfg, layer, u,
                                                       conv_tail)
        o, stack = step_rows(stack, i, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             beta[:, 0], started)
        return _gated_out(cfg, layer, o[:, None], z), tail, stack
