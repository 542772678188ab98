"""State-space mixer (Mamba-2, Dao & Gu 2024): the chunked SSD scan, the
causal depthwise convolution in front of it and the gated group RMS norm
behind it.

The recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t
h_t`` is computed in the chunked ("state-space dual") form: inside a chunk
of ``chunk`` positions as masked matrix products (the quadratic form, on
the MXU), between chunks as a ``lax.scan`` over one state per chunk. Every
decay product is float32; the matrix products take the activations' dtype
with float32 accumulation. Plain ``jax.numpy``/``lax``: the backward pass is
autodiff of this form, under whatever remat the caller wraps the layer in.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_depthwise_conv(x, w, b):
    """x [B, S, C], w [K, C], b [C]: out_t = sum_k w_k x_{t-(K-1)+k} + b."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * w[i] for i in range(k)) + b


def ssd_chunked(x, dt, a, b, c, chunk):
    """x [B,S,H,P], dt [B,S,H] (after softplus), a [H] (negative), b and c
    [B,S,G,N] with H a multiple of G -> y [B,S,H,P] in x's dtype. A
    sequence that is not a multiple of ``chunk`` is padded with dt = 0
    (decay 1, no input), which leaves the positions before untouched."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = -s % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    dtype = x.dtype
    dt = dt.astype(F32)
    r = h // g
    # chunked views; the decay tensors keep the chunk's positions as their
    # minor axes ([..., L] and [..., L, L]), heads as (G, R) before them
    xc = (x.astype(F32) * dt[..., None]).astype(dtype).reshape(
        bsz, nc, chunk, g, r, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    la = (dt * a.astype(F32)).reshape(bsz, nc, chunk, g, r)
    cum = jnp.cumsum(la.transpose(0, 1, 3, 4, 2), axis=-1)   # [B, nc, G, R, L]
    total = cum[..., -1]                                     # [B, nc, G, R]

    # inside a chunk: y_l = sum_{s<=l} exp(cum_l - cum_s) (C_l . B_s) x_s
    cb = jnp.einsum("bzlgn,bzsgn->bzgls", cc, bc, preferred_element_type=F32)
    seg = cum[..., :, None] - cum[..., None, :]              # [B, nc, G, R, L, L]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    weights = (decay * cb[:, :, :, None]).astype(dtype)
    y = jnp.einsum("bzgrls,bzsgrp->bzlgrp", weights, xc,
                   preferred_element_type=F32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(total[..., None] - cum).transpose(0, 1, 4, 2, 3)
    x_end = (xc.astype(F32) * to_end[..., None]).astype(dtype)
    states = jnp.einsum("bzlgn,bzlgrp->bzgrpn", bc, x_end,
                        preferred_element_type=F32)

    # between chunks: the state each chunk starts from
    def carry(state, inp):
        own, log_decay = inp
        return state * jnp.exp(log_decay)[..., None, None] + own, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1).astype(dtype)    # [B, nc, G, R, P, N]
    from_start = jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y + jnp.einsum("bzlgn,bzgrpn->bzlgrp", cc, entering,
                       preferred_element_type=F32) * from_start
    y = y.reshape(bsz, nc * chunk, h, p)[:, :s]
    return y.astype(dtype)


def gated_group_rms_norm(y, z, gain, groups, eps):
    """RMSNorm over each of ``groups`` slices of the last axis of
    ``y silu(z)``, in float32."""
    gated = (y.astype(F32) * jax.nn.silu(z.astype(F32)))
    shaped = gated.reshape(gated.shape[:-1] + (groups, -1))
    shaped = shaped * jax.lax.rsqrt(
        jnp.mean(jnp.square(shaped), -1, keepdims=True) + eps)
    return (shaped.reshape(gated.shape) * gain.astype(F32)).astype(y.dtype)


def mamba2_mixer(p, x, *, heads, head_dim, groups, state, chunk, eps):
    """One Mamba-2 mixer over normalized ``x`` [B, S, E]. ``p``: in_proj
    [E, d_inner + (d_inner + 2 G N) + heads], conv_w [K, d_inner + 2 G N],
    conv_b, dt_bias/A_log/D [heads], gate_norm [d_inner], out_proj
    [d_inner, E]; ``d_inner = heads * head_dim``."""
    bsz, s, _ = x.shape
    di, gn = heads * head_dim, groups * state
    with jax.named_scope("mamba_mixer"):
        proj = x @ p["in_proj"]
        z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * gn], axis=-1)
        xbc = jax.nn.silu(causal_depthwise_conv(xbc, p["conv_w"], p["conv_b"]))
        xs, b, c = jnp.split(xbc, [di, di + gn], axis=-1)
        xs = xs.reshape(bsz, s, heads, head_dim)
        delta = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
        with jax.named_scope("mamba_ssd"):
            y = ssd_chunked(
                xs, delta, -jnp.exp(p["A_log"].astype(F32)),
                b.reshape(bsz, s, groups, state),
                c.reshape(bsz, s, groups, state), chunk)
        y = y + (p["D"].astype(F32)[:, None] * xs.astype(F32)).astype(y.dtype)
        y = gated_group_rms_norm(
            y.reshape(bsz, s, di), z, p["gate_norm"], groups, eps)
        return y @ p["out_proj"]
