"""Linear attention with a delta rule and a gate (Gated DeltaNet, Yang,
Kautz & Hatamizadeh 2024): the chunked recurrence, as two Pallas kernels and
as one XLA form, and the mixer around it.

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
(exp(c_C - c) K)^T U``. ``A`` is strictly lower triangular, so ``A^C = 0`` and
``T = (I - A)(I + A^2)(I + A^4) ... (I + A^(C/2))``: log2(C) - 1 squarings and
as many products, in float32 at full precision, with the backward ``dA = -T^T
dT T^T`` written out. Every decay is float32 and is formed as ``exp`` of a
DIFFERENCE that is never positive, so a strong decay underflows to 0 and
nothing overflows; the other products take the activations' dtype with
float32 accumulation.

Two paths compute this, and ``gdn_path`` chooses between them from the shapes
it sees (and logs the choice once a shape):

- **the kernels** ``gdn_fwd`` and ``gdn_bwd``: a program per (row, key head)
  walks the sequence one segment a grid step, the key head's ``R`` value
  heads at once, with the state ``[R, d_k, d_v]`` float32 in VMEM. Chunks are
  taken ``128 / C`` at a time as one block of 128 positions whose ``[128,
  128]`` arrays (``K K^T``, ``Q K^T``, the decays, ``A``, ``T``) are block
  diagonal: every product fills the matrix unit's tile, and none of them
  leaves VMEM. q, k ``[B, S, Hk dk]`` and v, o ``[B, S, Hv dv]`` are read and
  written a head's 128-lane column block at a time, as the projections left
  them. The forward pass keeps its output, the state each segment started
  from and every chunk's ``T`` (remat name ``gdn_segments``); the backward
  pass takes the segments in reverse with ``dS`` in VMEM, computes a
  segment's W, U and chunk states again from what was kept, then walks its
  chunks backwards.
- **the XLA form** for every shape the kernels refuse: all chunks of a
  segment at once as batched products, a ``lax.scan`` over chunks inside
  and over segments outside, the backward pass one segment at a time.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from ..utils.logging import logger
from .gdn_glue import (L2_EPS, gdn_glue_path, gdn_inputs_fused,
                       gdn_output_fused)
from .ssm import causal_depthwise_conv

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128


# Chunks per segment of the XLA form. All the chunks of a segment are
# computed at once, and what that takes (a dozen arrays of [rows, positions,
# value heads, 128] and of [.., chunk, chunk] in float32) grows with the
# segment, not with the sequence. Alone on a "TPU v5 lite" at [2, 16384]
# positions, 32 value heads of 128, chunk 64 (my chip runs, PR 30), forward +
# backward in ms: 64 chunks a segment 129.8, 32 108.0, 16 89.9, 8 79.5, 4
# 69.8, 2 70.8, 1 79.6.
SEGMENT_CHUNKS = 4

# Chunks per grid step of the kernels, which is their segment: one float32
# state [value heads, d_k, d_v] a row is kept a segment for the backward
# pass, and the backward kernel holds a segment's chunk states, its W and U in
# VMEM (3 MB at the cell's shape). The length moves nothing on the chip: the
# rule alone at the shape above, forward + backward, 34.5 ms at 8 chunks and
# 34.3 at 16 (my chip runs, PR 31; 70.4 / 69.9 / 69.7 / 69.6 at 4 / 8 / 16 /
# 32 in the kernels' first version); 8 keeps 32 states a row (128 MB a layer).
KERNEL_SEGMENT_CHUNKS = 8
KERNEL_MIN_CHUNK = 8


def gdn_chunking(seq, chunk, segment_chunks=SEGMENT_CHUNKS, multiple=1):
    """How a sequence is cut: the chunk (a power of two: the inverse is a
    product of log2(chunk) factors), the chunks, the segments they are
    computed in (each a multiple of ``multiple`` chunks), the padding, and
    how the triangular inverse is computed. Logged once a shape."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"gdn chunk {chunk} is not a power of two")
    chunks = -(-seq // chunk)
    segments = -(-chunks // (multiple * -(-segment_chunks // multiple)))
    chunks = segments * multiple * -(-chunks // (segments * multiple))
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


def gdn_path(batch, seq, key_heads, value_heads, key_dim, value_dim, chunk,
             mesh=None, segment_chunks=None):
    """Which path computes the delta rule for these shapes, and how it cuts
    the sequence: ``{"path": "kernel" | "xla", "reason": why not the
    kernels, "block": chunks a 128-position block of the kernels,
    "chunks_a_step": chunks a grid step (0 for ``xla``), ...gdn_chunking's}``
    (one state a segment is kept a row and value head for the backward
    pass). Chosen from what the caller sees and nothing else; logged once a
    shape, with the chunks a segment and the states kept."""
    from .attention import _flash_route

    reason = None
    if key_dim % LANES or value_dim % LANES:
        reason = (f"head widths {key_dim} / {value_dim} do not fill "
                  f"{LANES}-lane blocks")
    elif chunk < KERNEL_MIN_CHUNK:
        reason = f"chunk {chunk} is under {KERNEL_MIN_CHUNK} rows"
    else:
        route = _flash_route(mesh, batch, key_heads)
        if route != "local":
            reason = ("a kernel is not partitioned over devices" if
                      route == "sharded" else route)
    if reason:
        plan = gdn_chunking(seq, chunk, segment_chunks or SEGMENT_CHUNKS)
        plan.update(path="xla", reason=reason, block=1, chunks_a_step=0)
    else:
        block = max(LANES // chunk, 1)
        plan = gdn_chunking(
            seq, chunk, segment_chunks or KERNEL_SEGMENT_CHUNKS, block)
        plan.update(path="kernel", reason=None, block=block,
                    chunks_a_step=plan["chunks"] // plan["segments"],
                    inverse=plan["inverse"] + f", in VMEM on blocks of "
                    f"{block} chunks, each kept for the backward kernel")
    _log_path(batch, seq, key_heads, value_heads, key_dim, value_dim, chunk,
              plan["path"], plan["reason"], plan["chunks_a_step"],
              plan["chunks"] // plan["segments"], plan["segments"],
              plan["inverse"])
    return plan


@functools.lru_cache(maxsize=None)
def _log_path(batch, seq, key_heads, value_heads, key_dim, value_dim, chunk,
              path, reason, chunks_a_step, chunks_a_segment, states, inverse):
    logger.debug(
        "gdn_path b=%d s=%d heads=%d/%d d=%d/%d chunk=%d path=%s%s "
        "chunks_a_step=%d chunks_a_segment=%d states=%d inverse=%r",
        batch, seq, key_heads, value_heads, key_dim, value_dim, chunk, path,
        f" reason={reason!r}" if reason else "", chunks_a_step,
        chunks_a_segment, states, inverse)


def gated_delta_rule_chunked(q, k, v, g, beta, chunk, segment_chunks=None,
                             initial_state=None, mesh=None):
    """q and k [B,S,Hk,dk] (normalised and scaled by the caller), v
    [B,S,Hv,dv] with Hv a multiple of Hk (key head j serves value heads
    j Hv/Hk ...), g (log decay, <= 0) and beta [B,S,Hv] float32 -> o
    [B,S,Hv,dv] in v's dtype. ``initial_state`` [B,Hk,Hv/Hk,dk,dv] float32
    is the state before the first position (zeros if None). A sequence that
    is no multiple of ``chunk`` (or of the chunks of a segment) is padded
    with g = 0 and beta = 0: no decay, no correction."""
    bsz, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    plan = gdn_path(bsz, s, hk, hv, dk, dv, chunk, mesh, segment_chunks)
    if initial_state is None:
        initial_state = jnp.zeros((bsz, hk, hv // hk, dk, dv), F32)
    arrays = (q, k, v, g.astype(F32), beta.astype(F32))
    if plan["padded"]:
        arrays = tuple(
            jnp.pad(t, ((0, 0), (0, plan["padded"])) + ((0, 0),) * (t.ndim - 2))
            for t in arrays)
    run = _kernel_rule if plan["path"] == "kernel" else _xla_rule
    return run(plan, initial_state.astype(F32), *arrays)[:, :s]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _bmm(a, b, precision=None):
    """[R, M, K] @ [R, K, N], or @ [K, N] for all R at once."""
    if b.ndim == 2:
        r, m, k = a.shape
        return jnp.dot(a.reshape(r * m, k), b, preferred_element_type=F32
                       ).reshape(r, m, b.shape[1])
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), precision=precision,
        preferred_element_type=F32)


_bmm32 = functools.partial(_bmm, precision=HIGHEST)


def _bmm_nt(a, b):
    """[R, M, K] @ [R, N, K]^T."""
    return jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))), preferred_element_type=F32)


def _dot_nt(a, b):
    """``a @ b.T``."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32)


def _t(x):
    return jnp.swapaxes(x, -1, -2)


class _Block:
    """What forward and backward share of one block of ``M`` positions
    (``M / chunk`` chunks side by side) for the ``R`` value heads of a key
    head at once (a leading axis of every array: ``R`` independent chains of
    products for the matrix unit to interleave): the masks of the
    block-diagonal ``[M, M]`` arrays and the decays. ``c`` (the running sum
    of the log decay inside each chunk) and ``beta`` arrive as rows [R, 1,
    M]; a column [R, M, 1] is the row's diagonal summed over the lanes,
    which is exact."""

    def __init__(self, m, chunk):
        row = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
        shift = int(math.log2(chunk))
        self.same = (row >> shift) == (col >> shift)
        self.eye = row == col
        self.lower = self.same & (col <= row)
        self.strict = self.same & (col < row)
        # the last position of the row's chunk
        self.ends = self.same & ((col & (chunk - 1)) == chunk - 1)
        self.chunk, self.per = chunk, m // chunk

    def column(self, row_vector):
        return jnp.sum(
            jnp.where(self.eye, row_vector, 0.0), axis=-1, keepdims=True)

    def row(self, column_vector):
        return jnp.sum(
            jnp.where(self.eye, column_vector, 0.0), axis=-2, keepdims=True)

    def decays(self, c_row, b_row):
        """(beta as a column, the decays inside each chunk [R, M, M], from
        the chunk's start to each position and from it to the chunk's end as
        columns, exp of each chunk's whole log decay as [R, 1, 1]s)."""
        c_col, b_col = self.column(c_row), self.column(b_row)
        decay = jnp.exp(jnp.where(self.lower, c_col - c_row, -jnp.inf))
        total = jnp.sum(
            jnp.where(self.ends, c_row, 0.0), axis=-1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, c_row.shape, 2)
        whole = [jnp.exp(jnp.sum(
            jnp.where(lane == (i + 1) * self.chunk - 1, c_row, 0.0), axis=-1,
            keepdims=True)) for i in range(self.per)]
        return b_col, decay, jnp.exp(c_col), jnp.exp(total - c_col), whole

    def rows(self, i):
        return slice(i * self.chunk, (i + 1) * self.chunk)

    def inverse(self, a):
        """``(I + A)^-1`` for ``A`` [R, M, M] float32 whose diagonal blocks
        of a chunk are strictly lower triangular and which is zero
        elsewhere, as ``pack`` lays it out. The left operand of every
        product is packed (``chunk`` rows for the matrix unit to take in
        place of ``M``), the right one block diagonal."""
        eye = jnp.where(self.eye, 1.0, 0.0)
        inv, power, packed = self.pack(eye - a), a, self.pack(a)
        for _ in range(int(math.log2(self.chunk)) - 1):
            packed = _bmm32(packed, power)
            power = self.unpack(packed)
            inv = _bmm32(inv, eye + power)
        return inv

    def pack(self, block):
        """The chunks of a block-diagonal [R, M, M] side by side, [R, chunk,
        M]: what is not zero of it."""
        return functools.reduce(
            jnp.add, [block[:, self.rows(i)] for i in range(self.per)])

    def unpack(self, packed):
        if self.per == 1:
            return packed
        return jnp.where(
            self.same, jnp.concatenate([packed] * self.per, axis=1), 0.0)

    def corrections(self, inv, kf, v, b_col, from_start, dtype):
        """(W, U0): a block's corrections before any state is read."""
        w = _bmm(inv.astype(dtype), (b_col * from_start * kf).astype(dtype))
        return w.astype(dtype), _bmm(
            inv.astype(dtype), (b_col * v).astype(dtype))


def _fwd_kernel(q_ref, k_ref, v_ref, c_ref, b_ref, s0_ref, o_ref, kept_ref,
                inv_ref, state, v_heads, o_heads, *, chunk, m, blocks, r):
    dtype, dv = v_ref.dtype, v_ref.shape[2] // r
    blk = _Block(m, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = s0_ref[0, 0]

    kept_ref[0, 0, 0] = state[...]
    for h in range(r):
        v_heads[h] = v_ref[0, :, h * dv:(h + 1) * dv]

    def block(p, _):
        rows = pl.ds(pl.multiple_of(p * m, m), m)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        qf, kf = q.astype(F32), k.astype(F32)
        kk, qk = _dot_nt(k, k), _dot_nt(q, k)
        v = v_heads[:, rows, :].astype(F32)
        b_col, decay, from_start, to_end, whole = blk.decays(
            c_ref[0, :, 0, pl.ds(p, 1), :], b_ref[0, :, 0, pl.ds(p, 1), :])
        packed = blk.inverse(jnp.where(blk.strict, b_col * decay * kk, 0.0))
        inv_ref[0, 0, 0, p] = packed
        w, u0 = blk.corrections(
            blk.unpack(packed), kf, v, b_col, from_start, dtype)
        q_in = (from_start * qf).astype(dtype)
        k_end_t = _t(to_end * kf).astype(dtype)               # [R, dk, M]
        s = state[...]
        us, reads = [], []
        for i in range(blk.per):
            rs, s_in = blk.rows(i), s.astype(dtype)
            u = (u0[:, rs] - _bmm(w[:, rs], s_in)).astype(dtype)
            reads.append(_bmm(q_in[:, rs], s_in))
            us.append(u)
            s = s * whole[i] + _bmm(k_end_t[:, :, rs], u)
        state[...] = s
        o = jnp.concatenate(reads, 1) + _bmm(
            (decay * qk).astype(dtype), jnp.concatenate(us, 1))
        o_heads[:, rows, :] = o.astype(dtype)
        return 0

    jax.lax.fori_loop(0, blocks, block, 0)
    for h in range(r):
        o_ref[0, :, h * dv:(h + 1) * dv] = o_heads[h]


def _bwd_kernel(q_ref, k_ref, v_ref, c_ref, b_ref, kept_ref, inv_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dc_ref, db_ref, ds0_ref,
                state, d_state, states, ws, us, v_heads, do_heads, dv_heads,
                *, chunk, m, blocks, r):
    dtype, dv = v_ref.dtype, v_ref.shape[2] // r
    blk = _Block(m, chunk)
    per = blk.per

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    state[...] = kept_ref[0, 0, 0]
    for h in range(r):
        lanes = slice(h * dv, (h + 1) * dv)
        v_heads[h] = v_ref[0, :, lanes]
        do_heads[h] = do_ref[0, :, lanes]

    def gates(p):
        return blk.decays(
            c_ref[0, :, 0, pl.ds(p, 1), :], b_ref[0, :, 0, pl.ds(p, 1), :])

    # the segment's chunk states again, with W and U of every block
    def forward(p, _):
        rows = pl.ds(pl.multiple_of(p * m, m), m)
        kf = k_ref[0, rows, :].astype(F32)
        b_col, _, from_start, to_end, whole = gates(p)
        w, u0 = blk.corrections(
            blk.unpack(inv_ref[0, 0, 0, p]), kf,
            v_heads[:, rows, :].astype(F32), b_col, from_start, dtype)
        ws[p] = w
        k_end_t = _t(to_end * kf).astype(dtype)
        s = state[...]
        for i in range(per):
            rs = blk.rows(i)
            states[p * per + i] = s
            u = (u0[:, rs] - _bmm(w[:, rs], s.astype(dtype))).astype(dtype)
            us[p, :, rs, :] = u
            s = s * whole[i] + _bmm(k_end_t[:, :, rs], u)
        state[...] = s
        return 0

    jax.lax.fori_loop(0, blocks, forward, 0)

    def backward(step, _):
        p = blocks - 1 - step
        rows = pl.ds(pl.multiple_of(p * m, m), m)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        qf, kf = q.astype(F32), k.astype(F32)
        kk, qk = _dot_nt(k, k), _dot_nt(q, k)
        lane = jax.lax.broadcasted_iota(jnp.int32, (r, 1, m), 2)
        v = v_heads[:, rows, :].astype(F32)
        do = do_heads[:, rows, :]
        b_col, decay, from_start, to_end, whole = gates(p)
        inv_t = _t(blk.unpack(inv_ref[0, 0, 0, p]))
        w, u = ws[p], us[p]
        k_end = (to_end * kf).astype(dtype)
        q_in_t = _t(from_start * qf).astype(dtype)            # [R, dk, M]
        w_t = _t(w.astype(F32)).astype(dtype)
        d_attn = _bmm_nt(do, u)                               # [R, M, M]
        du_out = _bmm(_t(decay * qk).astype(dtype), do)       # [R, M, dv]

        # the block's chunks backwards, dS from one to the one before;
        # d_total: what reaches a chunk's whole log decay, at its last
        # position, here through the state the chunk hands on
        ds = d_state[...]
        pieces = []
        d_total = jnp.zeros((r, 1, m), F32)
        for i in reversed(range(per)):
            rs = blk.rows(i)
            s = states[p * per + i]
            s_in, ds_in = s.astype(dtype), ds.astype(dtype)
            du = du_out[:, rs] + _bmm(k_end[:, rs], ds_in)
            du_in = du.astype(dtype)
            pieces.append((du, -_bmm_nt(du_in, s_in),
                           _bmm_nt(u[:, rs], ds_in), _bmm_nt(do[:, rs], s_in)))
            d_total = d_total + jnp.where(
                lane == (i + 1) * chunk - 1,
                jnp.sum(jnp.sum(ds * s, axis=-1, keepdims=True), axis=-2,
                        keepdims=True) * whole[i], 0.0)
            ds = (ds * whole[i] + _bmm(q_in_t[:, :, rs], do[:, rs])
                  - _bmm(w_t[:, :, rs], du_in))
        d_state[...] = ds
        du, dw, d_k_end, d_q_in = (
            jnp.concatenate(t[::-1], 1) for t in zip(*pieces))

        du_in, dw_in = du.astype(dtype), dw.astype(dtype)
        d_inv = (_bmm_nt(du_in, (b_col * v).astype(dtype))
                 + _bmm_nt(dw_in, (b_col * from_start * kf).astype(dtype)))
        d_v_beta = _bmm(inv_t.astype(dtype), du_in)
        d_k_beta = _bmm(inv_t.astype(dtype), dw_in)
        da = jnp.where(
            blk.strict, -_bmm32(_bmm32(inv_t, d_inv), inv_t), 0.0)
        d_decay = jnp.where(
            blk.lower, (da * b_col * kk + d_attn * qk) * decay, 0.0)
        d_kk = da * b_col * decay
        d_qk = jnp.where(blk.lower, d_attn * decay, 0.0)
        dq = _bmm(d_qk.astype(dtype), k) + from_start * d_q_in
        dk = (_bmm(_t(d_qk).astype(dtype), q)
              + _bmm((d_kk + _t(d_kk)).astype(dtype), k)
              + b_col * from_start * d_k_beta + to_end * d_k_end)
        dq_ref[0, rows, :] = jnp.sum(dq, axis=0).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = jnp.sum(dk, axis=0).astype(dk_ref.dtype)
        dv_heads[:, rows, :] = (b_col * d_v_beta).astype(dtype)

        along_k = jnp.sum(d_k_beta * kf, axis=-1, keepdims=True)
        db = (jnp.sum(da * decay * kk, axis=-1, keepdims=True)
              + from_start * along_k
              + jnp.sum(d_v_beta * v, axis=-1, keepdims=True))
        d_to_end = to_end * jnp.sum(d_k_end * kf, axis=-1, keepdims=True)
        dc = (jnp.sum(d_decay, axis=-1, keepdims=True)
              + from_start * (jnp.sum(d_q_in * qf, axis=-1, keepdims=True)
                              + b_col * along_k)
              - d_to_end)
        # to_end reads the chunk's whole log decay too
        d_total = d_total + jnp.where(
            (lane & (chunk - 1)) == chunk - 1,
            jnp.sum(jnp.where(blk.same, d_to_end, 0.0), axis=-2,
                    keepdims=True), 0.0)
        dc_ref[0, :, 0, pl.ds(p, 1), :] = (
            blk.row(dc) - jnp.sum(d_decay, axis=-2, keepdims=True) + d_total)
        db_ref[0, :, 0, pl.ds(p, 1), :] = blk.row(db)
        return 0

    jax.lax.fori_loop(0, blocks, backward, 0)
    for h in range(r):
        dv_ref[0, :, h * dv:(h + 1) * dv] = dv_heads[h]
    ds0_ref[0, 0] = d_state[...]


def _kernel_specs(cut, state_shape, at):
    """The static numbers both kernels share and the BlockSpecs of their
    operands: ``rows`` of q, k ``[B, S, Hk dk]``, ``wide`` of v, o ``[B, S,
    Hv dv]`` (a key head's 128-lane column block, and its value heads'),
    ``gates`` of c, beta ``[B, Hv, segments, blocks, M]``, ``first`` of the
    state ``[B, Hk, R, dk, dv]`` before the first position, ``kept`` of
    one a segment and ``inverses`` of every chunk's T ``[B, Hk, segments,
    blocks, R, chunk, M]``. ``cut``: (chunk, chunks a block, segments,
    chunks a grid step); ``at`` maps the grid's step to the segment."""
    chunk, per, _, chunks_a_step = cut
    _, _, r, dk, dv = state_shape
    m, blocks = per * chunk, chunks_a_step // per
    length = blocks * m
    specs = dict(
        rows=pl.BlockSpec((1, length, dk), lambda b, h, i: (b, at(i), h)),
        wide=pl.BlockSpec((1, length, r * dv), lambda b, h, i: (b, at(i), h)),
        gates=pl.BlockSpec(
            (1, r, 1, blocks, m), lambda b, h, i: (b, h, at(i), 0, 0)),
        first=pl.BlockSpec((1, 1, r, dk, dv), lambda b, h, i: (b, h, 0, 0, 0)),
        kept=pl.BlockSpec(
            (1, 1, 1, r, dk, dv), lambda b, h, i: (b, h, at(i), 0, 0, 0)),
        inverses=pl.BlockSpec(
            (1, 1, 1, blocks, r, chunk, m),
            lambda b, h, i: (b, h, at(i), 0, 0, 0, 0)))
    return dict(chunk=chunk, m=m, blocks=blocks, r=r), specs, length


def _kernel_call(kernel, name, static, grid, **kwargs):
    return pl.pallas_call(
        functools.partial(kernel, **static), grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=not device.on_tpu(), name=name, **kwargs)


def _forward_call(cut, state, q, k, v, c, beta):
    """``gdn_fwd``: q, k [B,S,Hk dk], v [B,S,Hv dv], c and beta as
    ``_gates`` lays them out, the state [B,Hk,R,dk,dv] before the first
    position -> (o [B,S,Hv dv], the state each segment started from
    [B,Hk,segments,R,dk,dv], every chunk's T float32)."""
    bsz, hk, r, dk, dv = state.shape
    static, s, length = _kernel_specs(cut, state.shape, lambda i: i)
    n_seg, blocks, m = cut[2], static["blocks"], static["m"]
    return _kernel_call(
        _fwd_kernel, "gdn_fwd", static, (bsz, hk, n_seg),
        in_specs=[s["rows"], s["rows"], s["wide"], s["gates"], s["gates"],
                  s["first"]],
        out_specs=[s["wide"], s["kept"], s["inverses"]],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bsz, hk, n_seg, r, dk, dv), F32),
            jax.ShapeDtypeStruct(
                (bsz, hk, n_seg, blocks, r, cut[0], m), F32)],
        scratch_shapes=[
            pltpu.VMEM((r, dk, dv), F32),                    # the state
            pltpu.VMEM((r, length, dv), v.dtype),            # v, by head
            pltpu.VMEM((r, length, dv), v.dtype)],           # o
    )(q, k, v, c, beta, state)


def _backward_call(cut, kept, inverses, q, k, v, c, beta, do):
    """``gdn_bwd``: the segments in reverse -> (dq, dk, dv, dc, dbeta, the
    gradient of the state before the first position)."""
    bsz, hk, n_seg, r, dk, dv = kept.shape
    static, s, length = _kernel_specs(
        cut, (bsz, hk, r, dk, dv), lambda i: n_seg - 1 - i)
    m, blocks, dtype = static["m"], static["blocks"], v.dtype
    return _kernel_call(
        _bwd_kernel, "gdn_bwd", static, (bsz, hk, n_seg),
        in_specs=[s["rows"], s["rows"], s["wide"], s["gates"], s["gates"],
                  s["kept"], s["inverses"], s["wide"]],
        out_specs=[s["rows"], s["rows"], s["wide"], s["gates"], s["gates"],
                   s["first"]],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, dtype),
            jax.ShapeDtypeStruct(c.shape, F32),
            jax.ShapeDtypeStruct(beta.shape, F32),
            jax.ShapeDtypeStruct((bsz, hk, r, dk, dv), F32)],
        scratch_shapes=[
            pltpu.VMEM((r, dk, dv), F32),                    # the state
            pltpu.VMEM((r, dk, dv), F32),                    # dS
            pltpu.VMEM((cut[3], r, dk, dv), F32),            # chunk states
            pltpu.VMEM((blocks, r, m, dk), dtype),           # W
            pltpu.VMEM((blocks, r, m, dv), dtype),           # U
            pltpu.VMEM((r, length, dv), dtype),              # v, by head
            pltpu.VMEM((r, length, dv), dtype),              # dO
            pltpu.VMEM((r, length, dv), dtype)],             # dv
    )(q, k, v, c, beta, kept, inverses, do)


def _gates(cut, t):
    """g's running sum or beta [B, S, Hv] float32 as the kernels read them:
    [B, Hv, segments, blocks a segment, M], a block's positions minor."""
    bsz, _, hv = t.shape
    return t.reshape(bsz, cut[2], -1, cut[0] * cut[1], hv).transpose(
        0, 4, 1, 2, 3)


def _kernel_rule(plan, state, q, k, v, g, beta):
    """The padded operands through the kernels: the heads folded into the
    lanes (no copy), the decays summed inside each chunk and laid out with
    beta as the kernels read them (two [B, S, Hv] float32 arrays: the only
    layout passes), then the two kernels as one differentiable function."""
    bsz, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    cut = (plan["chunk"], plan["block"], plan["segments"],
           plan["chunks_a_step"])
    c = jnp.cumsum(g.reshape(bsz, -1, plan["chunk"], hv), axis=2)
    o = _kernels(
        cut, state, q.reshape(bsz, s, hk * dk), k.reshape(bsz, s, hk * dk),
        v.reshape(bsz, s, hv * dv), _gates(cut, c.reshape(bsz, s, hv)),
        _gates(cut, beta))
    return o.reshape(bsz, s, hv, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernels(cut, state, q, k, v, c, beta):
    return _forward_call(cut, state, q, k, v, c, beta)[0]


def _kernels_fwd(cut, state, q, k, v, c, beta):
    # named, so that a remat policy can keep what the backward kernel needs
    # of the forward kernel (its output, one state a segment and every
    # chunk's T) and not run it again
    o, kept, inverses = (
        checkpoint_name(t, "gdn_segments")
        for t in _forward_call(cut, state, q, k, v, c, beta))
    return o, (kept, inverses, q, k, v, c, beta)


def _kernels_bwd(cut, residuals, do):
    dq, dk, dv, dc, dbeta, d_state = _backward_call(cut, *residuals, do)
    return d_state, dq, dk, dv, dc, dbeta


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# ---------------------------------------------------------------------------
# the XLA form
# ---------------------------------------------------------------------------
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


def _xla_rule(plan, state, *arrays):
    """The padded operands [B, S, ...] through ``_segment``, one segment
    after the other; the backward pass (``_run_segments``) keeps the state
    each segment started from and computes one segment again at a time."""
    ns, bsz = plan["segments"], arrays[0].shape[0]
    segments = tuple(
        jnp.moveaxis(t.reshape((bsz, ns, -1) + t.shape[2:]), 1, 0)
        for t in arrays)
    out = jnp.moveaxis(
        _run_segments(plan["chunk"], state, segments), 0, 1)
    return out.reshape((bsz, -1) + out.shape[3:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _run_segments(chunk, state, segments):
    return _run_segments_fwd(chunk, state, segments)[0]


def _run_segments_fwd(chunk, state, segments):
    def step(state, segment):
        out, after = _segment(state, *segment, chunk)
        return after, (out, state)

    _, (out, entering) = jax.lax.scan(step, state, segments)
    # named, so that a remat policy can keep what the backward pass needs
    # of this scan (its output and one state a segment) and not run it again
    out, entering = (
        checkpoint_name(t, "gdn_segments") for t in (out, entering))
    return out, (entering, segments)


def _run_segments_bwd(chunk, residuals, g):
    entering, segments = residuals

    def step(d_state, inp):
        state, segment, g_out = inp
        _, vjp = jax.vjp(
            lambda state, *segment: _segment(state, *segment, chunk), state,
            *segment)
        d_state, *d_segment = vjp((g_out, d_state))
        return d_state, tuple(d_segment)

    d_state, d_segments = jax.lax.scan(
        step, jnp.zeros_like(entering[0]), (entering, segments, g),
        reverse=True)
    return d_state, d_segments


_run_segments.defvjp(_run_segments_fwd, _run_segments_bwd)


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


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------
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


def gdn_inputs(mixed, conv_w, key_heads, key_dim):
    """``mixed`` [B, S, 2 Hk dk + Hv dv] (q | k | v) and ``conv_w`` [K, the
    same lanes] -> (q, k [B, S, Hk dk], v [B, S, Hv dv]): the causal depthwise
    convolution and SiLU over all of it, q and k L2-normalised a head and q
    times ``dk ** -0.5``. The XLA form of ``gdn_glue.gdn_inputs_fused``."""
    bsz, s, _ = mixed.shape
    qk = key_heads * key_dim
    mixed = jax.nn.silu(causal_depthwise_conv(mixed, conv_w, 0))
    q, k, v = jnp.split(mixed, [qk, 2 * qk], axis=-1)
    heads = (bsz, s, key_heads, key_dim)
    q = l2_normalise(q.reshape(heads)) * key_dim ** -0.5
    k = l2_normalise(k.reshape(heads))
    return (q.reshape(bsz, s, qk).astype(mixed.dtype),
            k.reshape(bsz, s, qk).astype(mixed.dtype), v)


def gated_deltanet_mixer(p, x, *, key_heads, value_heads, key_dim, value_dim,
                         chunk, eps, mesh=None):
    """One Gated DeltaNet mixer over normalized ``x`` [B, S, E]. ``p``:
    in_qkvz [E, 2 Hk dk + 2 Hv dv] (q | k | v | z, head by head in each),
    in_ba [E, 2 Hv] (b | a), conv_w [K, 2 Hk dk + Hv dv] (no bias), A_log and
    dt_bias [Hv], out_norm [dv], out_proj [Hv dv, E]. The float32 glue on
    either side of the delta rule (convolution, SiLU and the L2 norms before
    it, the gated output norm after it) is one Pallas pass a side wherever
    ``gdn_glue_path`` says ``fused``, else ``gdn_inputs`` and
    ``gated_head_rms_norm`` over the whole array; the decays and beta are
    [B, S, Hv] float32 and plain XLA either way."""
    bsz, s, _ = x.shape
    qk, vz = key_heads * key_dim, value_heads * value_dim
    a_neg, dt_bias = -jnp.exp(p["A_log"].astype(F32)), p["dt_bias"].astype(F32)
    fused = gdn_glue_path(
        bsz, s, key_heads, value_heads, key_dim, value_dim,
        p["conv_w"].shape[0], mesh)[0] == "fused"

    with jax.named_scope("gdn_mixer"):
        # two products over the two column blocks of the one leaf: the
        # convolution's input and the gate come out as arrays of their own
        # (slicing a [B, S, 12288] result would copy both)
        mixed = x @ p["in_qkvz"][:, :2 * qk + vz]
        z = x @ p["in_qkvz"][:, 2 * qk + vz:]
        b, a = jnp.split(x @ p["in_ba"], 2, axis=-1)
        g = a_neg * jax.nn.softplus(a.astype(F32) + dt_bias)
        beta = jax.nn.sigmoid(b.astype(F32))
        q, k, v = (gdn_inputs_fused if fused else gdn_inputs)(
            mixed, p["conv_w"], key_heads=key_heads, key_dim=key_dim)
        with jax.named_scope("gdn_delta_rule"):
            o = gated_delta_rule_chunked(
                q.reshape(bsz, s, key_heads, key_dim),
                k.reshape(bsz, s, key_heads, key_dim),
                v.reshape(bsz, s, value_heads, value_dim), g, beta, chunk,
                mesh=mesh)
        if fused:
            o = gdn_output_fused(o.reshape(bsz, s, vz), z, p["out_norm"], eps)
        else:
            o = gated_head_rms_norm(
                o, z.reshape(o.shape), p["out_norm"], eps).reshape(bsz, s, vz)
        return o @ p["out_proj"]
