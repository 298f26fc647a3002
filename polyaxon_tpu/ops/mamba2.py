"""The Mamba-2 mixer (state-space duality, arXiv 2405.21060): a chunked
scan for a sequence and a one-step update for decode.

With H heads of P channels, G groups of B and C (head ``h`` reads group
``h // (H/G)``), state size N and a depthwise causal convolution of
kernel K over the ``d_inner + 2·G·N`` channels ``xBC``::

    [z | xBC | dt] = u · W_in                    (d_inner | conv_dim | H)
    xBC ← silu(conv1d(xBC) + b_conv);  [x | B | C] = xBC
    Δ = softplus(dt + dt_bias);  A = −exp(A_log)
    S_t = exp(Δ_t A) · S_{t−1} + Δ_t · x_t ⊗ B_t      (S ∈ R^{H×P×N})
    y_t = S_t C_t + D · x_t
    out = rms_group(y ⊙ silu(z)) · W_out         (gate before the norm,
                                                  normalised within each
                                                  of the G groups)

What a sequence carries between tokens is ``S`` (float32) and the last
K−1 inputs ``xBC`` of the convolution. The recurrence (Δ, the decays,
the state and every product that feeds it) runs in float32 at full
precision; the two projections run in the compute dtype.

`ssd_scan` is the sequence form: the positions are cut into chunks of
``chunk`` (the published ``chunk_size``); inside a chunk the outputs are
a masked quadratic product (``C Bᵀ`` weighted by the decays between the
two positions), each chunk leaves one state, and a `lax.scan` over the
chunks carries the state from one to the next. `ssd_step` is one
position: an elementwise update of the state a row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from polyaxon_tpu.models.common import _w, rms_norm

HI = jax.lax.Precision.HIGHEST


def split_projection(cfg, zxbcdt: jax.Array):
    """``u · W_in`` [..., d_inner + conv_dim + H] → (z, xBC, dt)."""
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv_dim],
            zxbcdt[..., d_inner + conv_dim:])


def _split_xbc(cfg, xbc: jax.Array):
    """The convolution's output [..., conv_dim] → x [..., H, P], B and
    C [..., G, N], float32."""
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    xbc = xbc.astype(jnp.float32)
    lead = xbc.shape[:-1]
    x = xbc[..., :H * P].reshape(*lead, H, P)
    b = xbc[..., H * P:H * P + G * N].reshape(*lead, G, N)
    c = xbc[..., H * P + G * N:].reshape(*lead, G, N)
    return x, b, c


def _gated_out(cfg, layer: dict, y: jax.Array, z: jax.Array) -> jax.Array:
    """``y`` [..., H, P] float32 and the gate ``z`` [..., d_inner] → the
    mixer's output [..., D]: gate, RMS norm within each group, W_out."""
    dt = cfg.dtype
    lead = y.shape[:-2]
    G = cfg.ssm_groups
    gated = y.reshape(*lead, -1) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(*lead, G, -1)
    normed = rms_norm(grouped, layer["gate_norm"].reshape(G, -1),
                      cfg.norm_eps)
    return normed.reshape(*lead, -1).astype(dt) @ _w(layer["w_out"], dt)


def ssd_scan(x, dt, a, b, c, chunk: int, state0=None):
    """The recurrence over a sequence, chunk by chunk. ``x`` [B, S, H,
    P], ``dt`` [B, S, H] (Δ, already softplus'd; 0 at a padded
    position, which then leaves the state as it was), ``a`` [H]
    (negative), ``b``/``c`` [B, S, G, N], ``state0`` [B, H, P, N] or
    None (zeros); all float32. Returns (y [B, S, H, P] without the
    ``D·x`` term, the state after the last position [B, H, P, N])."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    R = H // G
    Q = chunk
    pad = -S % Q
    if pad:
        widen = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc = (S + pad) // Q
    # [B, chunks, Q, ...]; heads as (group, head in group).
    x = x.reshape(B, nc, Q, G, R, P)
    dt = dt.reshape(B, nc, Q, G, R)
    b = b.reshape(B, nc, Q, G, N)
    c = c.reshape(B, nc, Q, G, N)
    da = dt * a.reshape(G, R)                       # log-decay a position
    cum = jnp.cumsum(da, axis=2)                    # ... up to and with l
    xdt = x * dt[..., None]
    # Inside a chunk: y_l = Σ_{s≤l} (C_l·B_s) exp(cum_l − cum_s) Δ_s x_s.
    # Each product is written out pair by pair, so that none is ever
    # formed over positions, heads, channels and state at once.
    cb = jnp.einsum("bzlgn,bzsgn->bzgls", c, b, precision=HI)
    by_head = jnp.moveaxis(cum, 2, -1)              # [B, nc, G, R, Q]
    seg = by_head[..., :, None] - by_head[..., None, :]     # [.., l, s]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    y_diag = jnp.einsum("bzgrls,bzsgrp->bzlgrp", cb[:, :, :, None] * decay,
                        xdt, precision=HI)
    # What a chunk adds to the state by its end.
    to_end = jnp.exp(cum[:, :, -1:] - cum)          # [B, nc, Q, G, R]
    added = jnp.einsum("bzsgn,bzsgrp->bzgrpn", b, xdt * to_end[..., None],
                       precision=HI)
    chunk_decay = jnp.exp(cum[:, :, -1])            # [B, nc, G, R]
    if state0 is None:
        state0 = jnp.zeros((B, H, P, N), jnp.float32)

    def carry(state, inputs):
        keep, add = inputs
        return keep[..., None, None] * state + add, state

    final, before = jax.lax.scan(
        carry, state0.reshape(B, G, R, P, N),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(added, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)             # state entering chunk z
    y_off = jnp.einsum("bzlgn,bzgrpn->bzlgrp", c, before,
                       precision=HI) * jnp.exp(cum)[..., None]
    y = (y_diag + y_off).reshape(B, nc * Q, H, P)[:, :S]
    return y, final.reshape(B, H, P, N)


def ssd_step(x, dt, a, b, c, state):
    """One position: ``x`` [B, H, P], ``dt`` [B, H], ``b``/``c`` [B, G,
    N], ``state`` [B, H, P, N], float32 → (y [B, H, P], new state)."""
    B, H, P = x.shape
    G, N = b.shape[-2:]
    R = H // G
    state = state.reshape(B, G, R, P, N)
    dt = dt.reshape(B, G, R)
    keep = jnp.exp(dt * a.reshape(G, R))
    xdt = (x * dt.reshape(B, H, 1)).reshape(B, G, R, P)
    new = (keep[..., None, None] * state
           + xdt[..., None] * b[:, :, None, None, :])
    y = jnp.sum(new * c[:, :, None, None, :], axis=-1)
    return y.reshape(B, H, P), new.reshape(B, H, P, N)


def mixer(cfg, layer: dict, u: jax.Array, conv_tail: jax.Array,
          state: jax.Array, real_len=None):
    """The mixer over ``u`` [B, S, D] (already normalised) behind what
    the sequence carries: ``conv_tail`` [B, K−1, conv_dim] (the
    convolution's inputs of the K−1 positions before, zeros at the
    start) and ``state`` [B, H, P, N] float32 (zeros there). Positions
    at or past ``real_len`` (traced; None: all real) are padding: they
    leave the state alone, and the tail returned is that of the last
    real position. Returns (out [B, S, D], new tail, new state)."""
    dt_ = cfg.dtype
    S, K = u.shape[1], cfg.conv_kernel
    z, xbc, dt = split_projection(cfg, u @ _w(layer["w_in"], dt_))
    seq = jnp.concatenate([conv_tail.astype(dt_), xbc], axis=1)
    w = layer["conv_w"].astype(jnp.float32)          # [conv_dim, K]
    conv = sum(w[:, j] * seq[:, j:j + S].astype(jnp.float32)
               for j in range(K)) + layer["conv_b"].astype(jnp.float32)
    x, b, c = _split_xbc(cfg, jax.nn.silu(conv))
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + layer["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(layer["A_log"].astype(jnp.float32))
    if real_len is None:
        tail = seq[:, S:]
    else:
        delta = jnp.where((jnp.arange(S) < real_len)[None, :, None],
                          delta, 0.0)
        tail = jax.lax.dynamic_slice_in_dim(seq, real_len, K - 1, axis=1)
    if S == 1:
        y, state = ssd_step(x[:, 0], delta[:, 0], a, b[:, 0], c[:, 0], state)
        y = y[:, None]
    else:
        y, state = ssd_scan(x, delta, a, b, c, cfg.chunk_size, state)
    y = y + layer["D"].astype(jnp.float32)[:, None] * x
    return _gated_out(cfg, layer, y, z), tail, state
