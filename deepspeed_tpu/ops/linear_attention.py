"""Linear attention with a delta rule and a gate (Gated DeltaNet, Yang,
Kautz & Hatamizadeh 2024): the chunked recurrence and the mixer around it.

Per value head a state ``S`` [d_k, d_v] starts at zero and every position
decays it, corrects it and reads it::

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t

Unlike the state-space scan of ops/ssm.py the update READS the state, so a
chunk of ``C`` positions is not a plain masked product. With ``c`` the
running sum of ``g`` inside the chunk and ``S0`` the state the chunk starts
from, the corrections of a chunk solve a unit lower-triangular system::

    (I + A) U = beta (V - exp(c) K S0)
    A[t, s] = beta_t exp(c_t - c_s) (k_t . k_s)  for s < t, else 0

so ``U = T (beta V) - T (beta exp(c) K) S0`` with ``T = (I + A)^-1``, and then
``O = exp(c) Q S0 + tril(exp(c_t - c_s) Q K^T) U`` and ``S_C = exp(c_C) S0 +
(exp(c_C - c) K)^T U``. Everything but the ``S0`` terms is computed for all
chunks of a segment at once (masked matrix products on the MXU); a
``lax.scan`` hands one state from each chunk to the next, and segments of
``SEGMENT_CHUNKS`` chunks follow one another (``scan_segments``): the forward
pass keeps the state each segment started from, the backward pass computes
one segment again at a time, so the arrays of one segment are all that is
alive. ``A`` is strictly lower triangular, so
``A^C = 0`` and ``T = (I - A)(I + A^2)(I + A^4) ... (I + A^(C/2))``: log2(C)
squarings and as many products, all ``C x C``, in float32 at full precision,
with a custom backward ``dA = -T^T dT T^T`` in place of autodiff through the
chain. Every decay is float32 and is formed as ``exp`` of a DIFFERENCE that is
never positive, so a strong decay underflows to 0 and nothing overflows; the
other products take the activations' dtype with float32 accumulation. The
backward pass is autodiff of this form under the caller's remat.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..utils.logging import logger
from .ssm import causal_depthwise_conv

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


# Chunks per segment. All the chunks of a segment are computed at once, and
# what that takes (a dozen arrays of [rows, positions, value heads, 128] and
# of [.., chunk, chunk] in float32) grows with the segment, not with the
# sequence: segments run one after the other, each handing its last state
# on, and the backward pass computes one again at a time. Alone on a "TPU v5
# lite" at [2, 16384] positions, 32 value heads of 128, chunk 64 (my chip
# runs, PR 30), forward + backward in ms: 64 chunks a segment 129.8, 32
# 108.0, 16 89.9 (with jax.checkpoint around a segment); 8 79.5, 4 69.8 (with
# the hand-written backward below). One float32 state [value heads, 128, 128]
# a row is kept a segment: 4 MB at the cell's shape, 64 of them a layer.
SEGMENT_CHUNKS = 4


def gdn_chunking(seq, chunk, segment_chunks=SEGMENT_CHUNKS):
    """How ``gated_delta_rule_chunked`` cuts a sequence: the chunk (a power
    of two: the inverse is a product of log2(chunk) factors), the chunks, the
    segments they are computed in, the padding, and how the triangular
    inverse is computed. Logged once a shape."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"gdn chunk {chunk} is not a power of two")
    chunks = -(-seq // chunk)
    segments = -(-chunks // segment_chunks)
    chunks = segments * -(-chunks // segments)
    padded = chunks * chunk - seq
    inverse = (f"product of {max(int(math.log2(chunk)), 1)} factors "
               f"(I - A)(I + A^2)...(I + A^{max(chunk // 2, 1)}), "
               "float32 at highest precision")
    _log_chunking(seq, chunk, chunks, segments, padded, inverse)
    return {"chunk": chunk, "chunks": chunks, "segments": segments,
            "padded": padded, "inverse": inverse}


@functools.lru_cache(maxsize=None)
def _log_chunking(seq, chunk, chunks, segments, padded, inverse):
    logger.debug(
        "gdn_chunking seq=%d chunk=%d chunks=%d segments=%d padded=%d "
        "inverse=%r", seq, chunk, chunks, segments, padded, inverse)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=F32)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., C, C], C a
    power of two, float32."""
    eye = jnp.eye(a.shape[-1], dtype=F32)
    inv, power = eye - a, a
    for _ in range(int(math.log2(a.shape[-1])) - 1):
        power = _mm(power, power)
        inv = _mm(inv, eye + power)
    return inv


def _inverse_fwd(a):
    inv = unit_lower_inverse(a)
    return inv, inv


def _inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-_mm(_mm(t, g), t),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def scan_segments(body, state, consts, arrays, seq, chunk, segment_chunks):
    """``body(state, consts, *arrays of one segment) -> (out, state)`` over
    the segments of ``gdn_chunking``, one after the other; ``arrays`` are
    [B, S, ...] and are padded with zeros to whole segments (g = 0 and beta
    = 0 there: no decay, no correction, so the positions before are
    untouched); ``consts`` is a tuple of float arrays that every segment
    reads (``body`` may close over nothing that carries a gradient).
    Returns ``out`` [B, S, ...]. The backward pass (``_run_segments``) keeps
    the state each segment started from and computes one segment again at a
    time."""
    plan = gdn_chunking(seq, chunk, segment_chunks)
    if plan["padded"]:
        arrays = tuple(
            jnp.pad(t, ((0, 0), (0, plan["padded"])) + ((0, 0),) * (t.ndim - 2))
            for t in arrays)
    ns, bsz = plan["segments"], arrays[0].shape[0]
    segments = tuple(
        jnp.moveaxis(t.reshape((bsz, ns, -1) + t.shape[2:]), 1, 0)
        for t in arrays)
    out = jnp.moveaxis(_run_segments(body, state, consts, segments), 0, 1)
    return out.reshape((bsz, -1) + out.shape[3:])[:, :seq]


def _in_segment_scope(body):
    """``body`` under the device scope ``gdn_segment``. A trace renames the
    FIRST scope entered inside a differentiated function (the backward pass
    reads ``jvp(gdn_segment)``) and leaves those inside it as they are: with
    this one around it, a scope that ``body`` enters keeps its name in the
    backward pass too, where the profile's readers look for it."""
    def scoped(*args):
        with jax.named_scope("gdn_segment"):
            return body(*args)
    return scoped


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _run_segments(body, state, consts, segments):
    return _run_segments_fwd(body, state, consts, segments)[0]


def _run_segments_fwd(body, state, consts, segments):
    def step(state, segment):
        out, after = _in_segment_scope(body)(state, consts, *segment)
        return after, (out, state)

    _, (out, entering) = jax.lax.scan(step, state, segments)
    # named, so that a remat policy can keep what the backward pass needs
    # of this scan (its output and one state a segment) and not run it again
    out, entering = (
        checkpoint_name(t, "gdn_segments") for t in (out, entering))
    return out, (entering, consts, segments)


def _run_segments_bwd(body, residuals, g):
    entering, consts, segments = residuals

    def step(carry, inp):
        d_state, d_consts = carry
        state, segment, g_out = inp
        _, vjp = jax.vjp(_in_segment_scope(body), state, consts, *segment)
        d_state, d_c, *d_segment = vjp((g_out, d_state))
        return (d_state, jax.tree_util.tree_map(jnp.add, d_consts, d_c)), \
            tuple(d_segment)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, (entering[0], consts))
    (d_state, d_consts), d_segments = jax.lax.scan(
        step, zeros, (entering, segments, g), reverse=True)
    return d_state, d_consts, d_segments


_run_segments.defvjp(_run_segments_fwd, _run_segments_bwd)


def gated_delta_rule_chunked(q, k, v, g, beta, chunk,
                             segment_chunks=SEGMENT_CHUNKS):
    """q and k [B,S,Hk,dk] (normalised and scaled by the caller), v
    [B,S,Hv,dv] with Hv a multiple of Hk (key head j serves value heads
    j Hv/Hk ...), g (log decay, <= 0) and beta [B,S,Hv] float32 -> o
    [B,S,Hv,dv] in v's dtype. A sequence that is no multiple of ``chunk``
    (or of the chunks of a segment) is padded."""
    bsz, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    return scan_segments(
        lambda state, _consts, *seg: _segment(state, *seg, chunk),
        jnp.zeros((bsz, hk, hv // hk, dk, dv), F32), (),
        (q, k, v, g.astype(F32), beta.astype(F32)), s, chunk, segment_chunks)


def _segment(state, q, k, v, g, beta, chunk):
    """One segment (a whole number of chunks) from ``state`` [B,Hk,R,dk,dv]
    float32 -> (o [B,L,Hv,dv] in v's dtype, the state after it)."""
    bsz, length, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, nc = hv // hk, length // chunk
    dtype = v.dtype
    # chunked views, heads as (Hk, R) and the chunk's positions minor
    qc = q.reshape(bsz, nc, chunk, hk, dk).transpose(0, 1, 3, 2, 4)
    kc = k.reshape(bsz, nc, chunk, hk, dk).transpose(0, 1, 3, 2, 4)
    vc = v.reshape(bsz, nc, chunk, hk, r, dv).transpose(0, 1, 3, 4, 2, 5)
    gc = g.astype(F32).reshape(bsz, nc, chunk, hk, r).transpose(0, 1, 3, 4, 2)
    bc = beta.astype(F32).reshape(bsz, nc, chunk, hk, r).transpose(
        0, 1, 3, 4, 2)                                   # [B, nc, Hk, R, C]
    cum = jnp.cumsum(gc, axis=-1)
    total = cum[..., -1]                                 # [B, nc, Hk, R]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = cum[..., :, None] - cum[..., None, :]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))     # [B, nc, Hk, R, C, C]
    from_start = jnp.exp(cum)[..., None]                 # [B, nc, Hk, R, C, 1]
    to_end = jnp.exp(total[..., None] - cum)[..., None]

    kk = jnp.einsum("bzhtd,bzhsd->bzhts", kc, kc, preferred_element_type=F32)
    qk = jnp.einsum("bzhtd,bzhsd->bzhts", qc, kc, preferred_element_type=F32)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, bc[..., None] * decay * kk[:, :, :, None], 0.0)
    inv = unit_lower_inverse(a).astype(dtype)            # [B, nc, Hk, R, C, C]
    kf = kc.astype(F32)[:, :, :, None]                   # [B, nc, Hk, 1, C, dk]
    w = jnp.matmul(inv, (bc[..., None] * from_start * kf).astype(dtype),
                   preferred_element_type=F32).astype(dtype)
    u0 = jnp.matmul(inv, (bc[..., None] * vc.astype(F32)).astype(dtype),
                    preferred_element_type=F32)
    q_in = (from_start * qc.astype(F32)[:, :, :, None]).astype(dtype)
    k_end = (to_end * kf).astype(dtype)
    attn = (decay * qk[:, :, :, None]).astype(dtype)

    # between chunks: one state in, the chunk's corrections and its reading
    # of that state out
    def carry(state, inp):
        w_c, u0_c, q_c, k_c, log_decay = inp
        s_in = state.astype(dtype)
        u = u0_c - jnp.matmul(w_c, s_in, preferred_element_type=F32)
        read = jnp.matmul(q_c, s_in, preferred_element_type=F32)
        state = state * jnp.exp(log_decay)[..., None, None] + jnp.matmul(
            jnp.swapaxes(k_c, -1, -2), u.astype(dtype),
            preferred_element_type=F32)
        return state, (u.astype(dtype), read)

    chunks_first = tuple(
        jnp.moveaxis(t, 1, 0) for t in (w, u0, q_in, k_end, total))
    state, (u, read) = jax.lax.scan(carry, state, chunks_first)
    u, read = jnp.moveaxis(u, 0, 1), jnp.moveaxis(read, 0, 1)
    o = read + jnp.matmul(attn, u, preferred_element_type=F32)
    o = o.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, length, hv, dv)
    return o.astype(dtype), state


def l2_normalise(x):
    xs = x.astype(F32)
    return xs * jax.lax.rsqrt(
        jnp.sum(jnp.square(xs), -1, keepdims=True) + L2_EPS)


def gated_head_rms_norm(o, z, gain, eps):
    """Per head ``o / rms(o) * gain * silu(z)`` over the last axis, float32."""
    os_ = o.astype(F32)
    normed = os_ * jax.lax.rsqrt(
        jnp.mean(jnp.square(os_), -1, keepdims=True) + eps)
    return (normed * gain.astype(F32) * jax.nn.silu(z.astype(F32))).astype(
        o.dtype)


def gated_deltanet_mixer(p, x, *, key_heads, value_heads, key_dim, value_dim,
                         chunk, eps):
    """One Gated DeltaNet mixer over normalized ``x`` [B, S, E]. ``p``:
    in_qkvz [E, 2 Hk dk + 2 Hv dv] (q | k | v | z, head by head in each),
    in_ba [E, 2 Hv] (b | a), conv_w [K, 2 Hk dk + Hv dv] (no bias), A_log and
    dt_bias [Hv], out_norm [dv], out_proj [Hv dv, E]. Everything between the
    convolution and the output projection (the L2 norms, the decays, the
    delta rule, the gated output norm: the float32 part) runs segment by
    segment (``scan_segments``)."""
    bsz, s, _ = x.shape
    qk, vz = key_heads * key_dim, value_heads * value_dim
    def segment(state, consts, mixed, z, b, a):
        a_neg, dt_bias, gain = consts
        length = mixed.shape[1]
        q, k, v = jnp.split(mixed, [qk, 2 * qk], axis=-1)
        q = (l2_normalise(q.reshape(bsz, length, key_heads, key_dim))
             * key_dim ** -0.5).astype(x.dtype)
        k = l2_normalise(
            k.reshape(bsz, length, key_heads, key_dim)).astype(x.dtype)
        beta = jax.nn.sigmoid(b.astype(F32))
        g = a_neg * jax.nn.softplus(a.astype(F32) + dt_bias)
        with jax.named_scope("gdn_delta_rule"):
            o, state = _segment(
                state, q, k, v.reshape(bsz, length, value_heads, value_dim),
                g, beta, chunk)
        o = gated_head_rms_norm(
            o, z.reshape(bsz, length, value_heads, value_dim), gain, eps)
        return o.reshape(bsz, length, vz), state

    with jax.named_scope("gdn_mixer"):
        # two products over the two column blocks of the one leaf: the
        # convolution's input and the gate come out as arrays of their own
        # (slicing a [B, S, 12288] result would copy both)
        mixed = x @ p["in_qkvz"][:, :2 * qk + vz]
        z = x @ p["in_qkvz"][:, 2 * qk + vz:]
        b, a = jnp.split(x @ p["in_ba"], 2, axis=-1)
        mixed = jax.nn.silu(causal_depthwise_conv(mixed, p["conv_w"], 0))
        consts = (-jnp.exp(p["A_log"].astype(F32)), p["dt_bias"].astype(F32),
                  p["out_norm"].astype(F32))
        o = scan_segments(
            segment, jnp.zeros(
                (bsz, key_heads, value_heads // key_heads, key_dim, value_dim),
                F32), consts, (mixed, z, b, a), s, chunk, SEGMENT_CHUNKS)
        return o @ p["out_proj"]
