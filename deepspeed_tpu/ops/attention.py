"""Attention: Pallas flash kernels + XLA reference path.

TPU-native replacement for the reference's attention pipeline inside the
fused BERT layer — StridedBatchGemm(QK^T) -> scale+mask+softmax kernel ->
dropout -> StridedBatchGemm(probs.V) (reference:
csrc/transformer/ds_transformer_cuda.cpp:217-231 and
csrc/transformer/softmax_kernels.cu). Instead of materializing the
[B,H,S,S] score matrix, the Pallas kernel streams KV blocks through VMEM
with an online softmax (flash attention), so there is **no sequence-length
cap** (the reference hard-limits seq <= 1024,
ds_transformer_cuda.cpp:133) and HBM traffic is O(S) instead of O(S^2).

Three entry points:
  - ``mha_reference``: plain XLA attention (always correct, differentiable
    through arbitrary additive masks; the numerics oracle and fallback).
  - ``flash_attention``: custom-vjp Pallas forward/backward. Masking is a
    compact per-key validity vector [B, Sk] (non-differentiable padding
    semantics) — NOT a full [B,H,Sq,Sk] additive bias, which would
    reintroduce the O(S^2) footprint the kernel exists to avoid.
  - ``attention``: dispatcher. Padding-style additive masks (broadcast over
    the query dim) are converted to validity vectors and sent to flash;
    learned/general additive biases (q-dependent) go to the XLA path so
    their gradients are exact.

Dropout inside the kernel uses the TPU PRNG seeded per (batch*head,
q granule, k granule) of 128 x 128 scores, so the backward pass regenerates
bit-identical masks without storing them (the reference stores an explicit
byte mask, dropout_kernels.cu; regeneration is the bandwidth-friendly TPU
design).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from ..utils.logging import logger, warn_once

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA reference implementation
# ---------------------------------------------------------------------------
def mha_reference(
    q, k, v, mask=None, causal=False, sm_scale=None, dropout_rate=0.0, dropout_rng=None
):
    """q,k,v: [B, H, S, D]; mask: additive, broadcastable to [B, H, Sq, Sk]."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        idx_q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        idx_k = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(idx_k <= idx_q + (sk - sq), s, NEG_INF)
    if mask is not None:
        s = s + mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    # tag for remat policies ("...+attn_probs"): saving the softmax output
    # lets per-layer remat backward skip re-running the QK^T einsum + mask +
    # softmax chain (softmax bwd needs only p itself)
    from jax.ad_checkpoint import checkpoint_name

    p = checkpoint_name(p, "attn_probs")
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash attention
# ---------------------------------------------------------------------------
# Default (largest) BlockSpec block: what one grid step copies into VMEM,
# 128 KB each of q, k, v at head_dim 64 in bf16. The arithmetic runs in
# sub-tiles inside a block (below), so a large block costs little VMEM, and
# a sequence that fits ONE block each way takes the kernels' static path.
# The three kernels alone at [8, 20, 1024, 64] bf16 causal on one
# "TPU v5 lite" chip, forward + dq + dkv in ms (device time from a
# profiler trace, PR 25, docs/TESTING.md): the one-level kernels before
# PR 25 at 512 x 512 blocks 1.22 + 0.94 + 1.10 (16.6 TFLOP/s in the
# forward), at 1024 x 1024 0.74 + 0.73 + 1.02; these kernels at 512 x 512
# blocks (a 2 x 2 grid, fori_loop walk) 0.76 + 0.89 + 1.33, at 1024 x 1024
# 0.43 + 0.48 + 0.60 (50 TFLOP/s in the forward). These are the ceiling
# pick_block starts from; block and sub-tiles follow from the shape.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# checkpoint_name tags remat-policy specs can name (consumed by
# ops/transformer.py:resolve_remat_policy). attn_probs/flash_* are emitted
# here; "zero3_gathered" tags the just-in-time all-gathered layer weights
# of the ZeRO-3 stack (models/stack.py) — naming it in a policy SAVES the
# gathered weights across backward (skipping the re-gather at n_layers x
# full-layer HBM cost; the default stage-3 policies deliberately exclude
# it so backward re-gathers instead). "moe_plan" tags the routing choice and
# the sorted row plan of the no-drop expert layer (ops/moe.py): a few MB of
# integers that a policy naming it keeps, so that backward does not run the
# top-k and the sort again.
CHECKPOINT_NAMES = ("attn_probs", "flash_out", "flash_lse", "zero3_gathered",
                    "moe_plan")


def pick_block(seq, maximum):
    """Largest block <= maximum that divides ``seq``, halving from the
    maximum (so a seq like 1536 uses 512-blocks rather than losing the
    flash path to the 1024 default). ``seq <= maximum`` returns ``seq``
    itself — a block equal to the full dim is always TPU-tileable. Returns
    0 when nothing >= 8 divides."""
    b = min(maximum, seq)
    while b >= 8:
        if seq % b == 0:
            return b
        b //= 2
    return seq if seq <= maximum else 0


# Two levels of tiling. The BlockSpec blocks above set what one grid step
# copies into VMEM; inside a step the kernels walk the block's score
# matrix in sub-tiles, and under ``causal`` the walk stops at the diagonal
# in sub-tile steps. A score sub-tile is computed TRANSPOSED, s_t = k q^T
# ([keys, queries]): queries lie on the lanes, so every per-query
# statistic (running max, sum, lse, delta) is one lane-dense row, and the
# reductions over keys run down the sublanes as plain elementwise
# max/add. With queries on the sublanes (before PR 25) the two lane
# reductions of the forward's softmax were 40% of that kernel.
#
# Where a grid has ONE block each way (every seq up to DEFAULT_BLOCK),
# every loop bound is known at trace time and the walk unrolls: the
# scheduler overlaps one sub-tile's matmuls with its neighbour's softmax.
# With more blocks the bounds depend on the grid position and the walk is
# a ``fori_loop``, whose every step costs ~0.3 us that nothing overlaps.
#
# Sub-tile sizes, measured alone at [8, 20, 1024, 64] bf16 causal on
# "TPU v5 lite" (docs/TESTING.md, PR 25; 128 / 256 / 512 square, ms):
# forward 0.83 / 0.67 / 0.43, dq 1.35 / 0.57 / 0.48, dkv 0.60 / 0.63 /
# 0.78. The query-major kernels (forward, dq) carry a chain from one key
# sub-tile to the next (the running max; the accumulator) and want few
# large steps even at 3/4 of the causal square; dkv's sub-tiles are
# independent, so it takes the 128-steps that visit 9/16 of it.
SUB_QUERY_MAJOR = 512
SUB_KEY_MAJOR = 128
# in-kernel dropout draws its bits in granules of this size (or the block,
# where 128 does not divide it), whatever sub-tile a kernel computes in
DROPOUT_TILE = 128


def pick_subtile(block, target):
    """Largest of ``target``, ``target/2``, ... 128 that divides ``block``;
    else the block itself (a block under the target, as seq 384's; an
    8-row block at an odd length)."""
    if block <= target:
        return block
    while target >= 128:
        if block % target == 0:
            return target
        target //= 2
    return block


def pick_subtiles(block_q, block_k, nq, nk, key_major):
    """(sub_q, sub_k) of the forward and dq kernels, or of dkv
    (``key_major``), for blocks on an ``nq x nk`` grid."""
    static = nq == nk == 1
    target = SUB_KEY_MAJOR if key_major and static else SUB_QUERY_MAJOR
    return pick_subtile(block_q, target), pick_subtile(block_k, target)


def _clip(x, lo, hi):
    if isinstance(x, int):
        return max(lo, min(hi, x))
    return jnp.clip(x, lo, hi)


def _key_range(q_first, sub_q, k_first, sub_k, nsk, diag_offset):
    """Key sub-tiles ``[0, n_full)`` of a K block that starts at key
    ``k_first`` lie wholly under the diagonal for the ``sub_q`` query rows
    from ``q_first`` (no causal mask needed); ``[n_full, hi)`` are crossed
    by it; from ``hi`` on every score is masked. Python ints or traced
    int32 alike (``//`` floors on both)."""
    first_row_limit = q_first + diag_offset - k_first
    n_full = (first_row_limit + 1) // sub_k
    hi = (first_row_limit + sub_q - 1 + sub_k) // sub_k
    return _clip(n_full, 0, nsk), _clip(hi, 0, nsk)


def _query_range(k_first, sub_k, q_first, sub_q, nsq, diag_offset):
    """The same seen from ``sub_k`` keys starting at ``k_first`` over a Q
    block that starts at row ``q_first``: query sub-tiles ``[lo, full)``
    are crossed by the diagonal, ``[full, nsq)`` lie wholly under it, and
    below ``lo`` every score is masked."""
    d = k_first - diag_offset - q_first
    lo = d // sub_q
    full = -((-(d + sub_k - 1)) // sub_q)
    return _clip(lo, 0, nsq), _clip(full, 0, nsq)


def flash_tiling(
    sq, sk, block_q, block_k, causal, key_major=False, sub_q=None, sub_k=None
):
    """Outer blocks, sub-tiles and the share of the ``sq x sk`` score
    square whose sub-tiles a kernel visits (the forward and dq, or dkv
    with ``key_major``), from the same bounds that set its loops."""
    picked = pick_subtiles(
        block_q, block_k, sq // block_q, sk // block_k, key_major
    )
    sub_q = picked[0] if sub_q is None else sub_q
    sub_k = picked[1] if sub_k is None else sub_k
    visited = sk // sub_k * (sq // sub_q)
    if causal:
        visited = 0
        for q_first in range(0, sq, sub_q):
            for k_first in range(0, sk, block_k):
                _, hi = _key_range(
                    q_first, sub_q, k_first, sub_k, block_k // sub_k, sk - sq
                )
                visited += hi
    return {
        "block_q": block_q, "block_k": block_k, "sub_q": sub_q,
        "sub_k": sub_k,
        "visited_share": visited * sub_q * sub_k / (sq * sk),
    }


@functools.lru_cache(maxsize=None)
def _log_tiling(sq, sk, d, dtype, block_q, block_k, causal, use_mask, dropout):
    t = flash_tiling(sq, sk, block_q, block_k, causal)
    kv = flash_tiling(sq, sk, block_q, block_k, causal, key_major=True)
    logger.debug(
        "flash_tiling sq=%d sk=%d d=%d %s causal=%s mask=%s dropout=%s "
        "block=%dx%d sub=%dx%d visited_share=%.4f "
        "dkv_sub=%dx%d dkv_visited_share=%.4f",
        sq, sk, d, dtype, causal, use_mask, dropout, block_q, block_k,
        t["sub_q"], t["sub_k"], t["visited_share"],
        kv["sub_q"], kv["sub_k"], kv["visited_share"],
    )


def _scale_is_exact(sm_scale):
    """A power of two (1/8 at head_dim 64) only shifts exponents: folding
    it into an operand changes no bit of the scores."""
    return math.frexp(sm_scale)[0] == 0.5


def _keep_mask(seed_ref, bh, q_first, k_first, shape, gran, rate):
    """Regenerable keep-mask of a ``[keys, queries]`` sub-tile of scores whose
    corner is (``k_first``, ``q_first``), drawn granule by granule: each
    ``gran = (gran_k, gran_q)`` granule is seeded by its global (batch*head,
    q granule, k granule) position, so the forward and both backward
    kernels draw the same bit for the same score element whatever sub-tile
    they compute in."""
    gran_k, gran_q = gran
    threshold = jnp.uint32(int(rate * (2**32)))
    rows = []
    for a in range(shape[0] // gran_k):
        row = []
        for b in range(shape[1] // gran_q):
            pltpu.prng_seed(
                seed_ref[0] + bh * 2_000_003
                + (q_first // gran_q + b) * 4_001 + (k_first // gran_k + a)
            )
            row.append(pltpu.prng_random_bits((gran_k, gran_q)) >= threshold)
        rows.append(row[0] if len(row) == 1 else jnp.concatenate(row, axis=1))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _scores_t(
    k, q, valid, q_first, k_first, *, sm_scale, fold_scale, diagonal,
    diag_offset,
):
    """One transposed score sub-tile ``s_t = k q^T`` ([keys, queries]) with
    causal and key-validity masking. ``diagonal``: the causal diagonal
    crosses this sub-tile; the ones wholly under it skip the
    iota/compare/select. ``valid``: the keys' validity column or None."""
    s_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if not fold_scale:
        s_t = s_t * sm_scale
    if diagonal:
        keys = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0) + k_first
        rows = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1) + q_first
        s_t = jnp.where(keys <= rows + diag_offset, s_t, NEG_INF)
    if valid is not None:
        s_t = jnp.where(valid > 0, s_t, NEG_INF)
    return s_t


def _exp_t(s_t, stat, guard):
    """``exp(s_t - stat)`` for a per-query row ``stat`` (the running
    maximum, or lse). ``guard``: a fully-masked row has stat == NEG_INF and
    exp(s - stat) = 1, so its entries are zeroed (l stays 0 -> output
    zeros). Without a key mask and with diag_offset >= 0 every row's first
    sub-tile holds a live key, stat is finite and exp already gives exact
    zeros."""
    p_t = jnp.exp(s_t - stat)
    if guard:
        p_t = jnp.where(s_t > NEG_INF / 2, p_t, 0.0)
    return p_t


def _rows(i, size):
    """The ``size`` rows of sub-tile ``i``."""
    if isinstance(i, int):
        return slice(i * size, (i + 1) * size)
    return pl.ds(pl.multiple_of(i * size, size), size)


def _span(lo, hi, step, carry):
    """``carry = step(i, carry)`` over the sub-tiles ``[lo, hi)``: unrolled
    where the bounds are known at trace time, else a ``fori_loop``."""
    if isinstance(lo, int) and isinstance(hi, int):
        for i in range(lo, hi):
            carry = step(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, step, carry)


def _when(cond):
    """``pl.when`` that also takes a condition known at trace time."""
    if isinstance(cond, bool):
        return (lambda f: f()) if cond else (lambda f: None)
    return pl.when(cond)


def _transposed_scratch(n_sub, d, sub, dtype, interpret):
    """Scratch for a K or V block transposed once a grid step, as
    ``[n_sub, D, sub]`` sub-tiles in the storage dtype. Off the chip
    (interpret mode) it is float32: XLA folds the transpose into the
    matmul that reads it, and its CPU backend has no bf16 matmul that
    contracts the left operand's first dimension. The kernels round the
    other operand to the storage dtype first either way, so the products
    are the same numbers."""
    return pltpu.VMEM((n_sub, d, sub), jnp.float32 if interpret else dtype)


def _dot_t(a_t, b, dtype):
    """``a_t @ b`` with ``a_t`` out of a transposed scratch and ``b``
    rounded to the storage ``dtype``; float32 accumulation."""
    return jnp.dot(
        a_t, b.astype(dtype).astype(a_t.dtype),
        preferred_element_type=jnp.float32,
    )


class _Tiles:
    """What the three kernels share: the grid position (a Python 0 on an
    axis of one block, so that every bound derived from it is static),
    sub-tile counts, and the masking flags."""

    def __init__(
        self, q_axis, *, sm_scale, causal, block_q, block_k, sub_q, sub_k,
        nq, nk, diag_offset, dropout_rate, use_mask,
    ):
        self.bh = pl.program_id(0)
        self.iq = pl.program_id(q_axis) if nq > 1 else 0
        self.ik = pl.program_id(3 - q_axis) if nk > 1 else 0
        self.causal, self.diag_offset = causal, diag_offset
        self.block_q, self.block_k = block_q, block_k
        self.sub_q, self.sub_k = sub_q, sub_k
        self.nsq, self.nsk = block_q // sub_q, block_k // sub_k
        self.nq, self.nk = nq, nk
        self.sm_scale = sm_scale
        self.fold_scale = _scale_is_exact(sm_scale)
        self.use_mask = use_mask
        self.dropout_rate = dropout_rate
        self.gran = (
            pick_subtile(block_k, DROPOUT_TILE), pick_subtile(block_q, DROPOUT_TILE)
        )
        self.scores = functools.partial(
            _scores_t, sm_scale=sm_scale, fold_scale=self.fold_scale,
            diag_offset=diag_offset,
        )
        self.exp = functools.partial(
            _exp_t, guard=use_mask or diag_offset < 0
        )
        # whole blocks above the diagonal are skipped
        self.run = True
        if causal:
            self.run = (
                self.ik * block_k
                <= self.iq * block_q + (block_q - 1) + diag_offset
            )

    def q_first(self, r):
        return self.iq * self.block_q + r * self.sub_q

    def k_first(self, c):
        return self.ik * self.block_k + c * self.sub_k

    def valid(self, kvm_ref, c):
        return kvm_ref[0, _rows(c, self.sub_k), :] if self.use_mask else None

    def keep(self, seed_ref, q_first, k_first, shape):
        return _keep_mask(
            seed_ref, self.bh, q_first, k_first, shape, self.gran,
            self.dropout_rate,
        )

    def over_keys(self, r, step, carry):
        """``step(c, carry, diagonal)`` over the key sub-tiles of this
        K block that query stripe ``r`` sees: the ones wholly under the
        diagonal, then the ones it crosses."""
        n_full = hi = self.nsk
        if self.causal:
            n_full, hi = _key_range(
                self.q_first(r), self.sub_q, self.k_first(0), self.sub_k,
                self.nsk, self.diag_offset,
            )
        carry = _span(0, n_full, functools.partial(step, diagonal=False), carry)
        return _span(n_full, hi, functools.partial(step, diagonal=True), carry)

    def over_queries(self, c, step, carry):
        """``step(r, carry, diagonal)`` over the query sub-tiles of this
        Q block that see key sub-tile ``c``: the ones the diagonal crosses,
        then the ones wholly under it."""
        lo = full = 0
        if self.causal:
            lo, full = _query_range(
                self.k_first(c), self.sub_k, self.q_first(0), self.sub_q,
                self.nsq, self.diag_offset,
            )
        carry = _span(lo, full, functools.partial(step, diagonal=True), carry)
        return _span(full, self.nsq, functools.partial(step, diagonal=False), carry)


def _fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr, vt_scr, **static,
):
    t = _Tiles(1, **static)

    @_when(t.ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @_when(t.run)
    def _body():
        # p v runs transposed (acc_t = v^T p_t): transpose the V block once
        for c in range(t.nsk):
            vt_scr[c] = v_ref[0, _rows(c, t.sub_k), :].T.astype(vt_scr.dtype)

        for r in range(t.nsq):
            # matmul operands stay in their storage dtype (MXU-native bf16
            # pairs, f32 accumulation); softmax bookkeeping is f32
            q = q_ref[0, _rows(r, t.sub_q), :]
            if t.fold_scale:
                q = q * t.sm_scale
            q_first = t.q_first(r)

            def k_step(c, carry, diagonal):
                m_prev, l_prev, acc = carry
                s_t = t.scores(
                    k_ref[0, _rows(c, t.sub_k), :], q, t.valid(kvm_ref, c),
                    q_first, t.k_first(c), diagonal=diagonal,
                )
                m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
                p_t = t.exp(s_t, m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_prev + jnp.sum(p_t, axis=0, keepdims=True)
                if t.dropout_rate > 0.0:
                    keep = t.keep(seed_ref, q_first, t.k_first(c), p_t.shape)
                    p_t = jnp.where(keep, p_t / (1.0 - t.dropout_rate), 0.0)
                pv = _dot_t(vt_scr[c], p_t, v_ref.dtype)
                return m_new, l_new, acc * alpha + pv

            m_scr[r], l_scr[r], acc_scr[r] = t.over_keys(
                r, k_step, (m_scr[r], l_scr[r], acc_scr[r])
            )

    @_when(t.ik == t.nk - 1)
    def _finalize():
        for r in range(t.nsq):
            l = l_scr[r]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
            o_ref[0, _rows(r, t.sub_q), :] = (
                (acc_scr[r] / l).T.astype(o_ref.dtype)
            )
            lse_ref[0, r] = m_scr[r] + jnp.log(l)


def _bwd_dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr, kt_scr, **static,
):
    t = _Tiles(1, **static)

    @_when(t.ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @_when(t.run)
    def _body():
        # dq_t += k^T ds_t: transpose the K block once
        for c in range(t.nsk):
            kt_scr[c] = k_ref[0, _rows(c, t.sub_k), :].T.astype(kt_scr.dtype)

        for r in range(t.nsq):
            q = q_ref[0, _rows(r, t.sub_q), :]
            if t.fold_scale:
                q = q * t.sm_scale
            do = do_ref[0, _rows(r, t.sub_q), :]
            lse, delta = lse_ref[0, r], delta_ref[0, r]  # [1, sub_q] rows
            q_first = t.q_first(r)

            def k_step(c, dq_t, diagonal):
                s_t = t.scores(
                    k_ref[0, _rows(c, t.sub_k), :], q, t.valid(kvm_ref, c),
                    q_first, t.k_first(c), diagonal=diagonal,
                )
                p_t = t.exp(s_t, lse)
                dp_t = jax.lax.dot_general(
                    v_ref[0, _rows(c, t.sub_k), :], do,
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )
                if t.dropout_rate > 0.0:
                    keep = t.keep(seed_ref, q_first, t.k_first(c), p_t.shape)
                    dp_t = jnp.where(keep, dp_t / (1.0 - t.dropout_rate), 0.0)
                ds_t = p_t * (dp_t - delta)
                return dq_t + _dot_t(kt_scr[c], ds_t, k_ref.dtype)

            dq_scr[r] = t.over_keys(r, k_step, dq_scr[r])

    @_when(t.ik == t.nk - 1)
    def _finalize():
        for r in range(t.nsq):
            dq_ref[0, _rows(r, t.sub_q), :] = (
                (dq_scr[r] * t.sm_scale).T.astype(dq_ref.dtype)
            )


def _bwd_dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, **static,
):
    t = _Tiles(2, **static)

    @_when(t.iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @_when(t.run)
    def _body():
        for c in range(t.nsk):
            keys = _rows(c, t.sub_k)
            k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            k_scaled = k * t.sm_scale if t.fold_scale else k
            valid = t.valid(kvm_ref, c)
            k_first = t.k_first(c)

            # the sub-tile is computed as k q^T, so that dv += p_t dO and
            # dk += ds_t q are plain matmuls; lse and delta enter as rows
            def q_step(r, carry, diagonal):
                dk, dv = carry
                q = q_ref[0, _rows(r, t.sub_q), :]
                do = do_ref[0, _rows(r, t.sub_q), :]
                q_first = t.q_first(r)
                s_t = t.scores(
                    k_scaled, q, valid, q_first, k_first, diagonal=diagonal
                )
                p_t = t.exp(s_t, lse_ref[0, r])
                dp_t = jax.lax.dot_general(
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                p_drop = p_t
                if t.dropout_rate > 0.0:
                    keep = t.keep(seed_ref, q_first, k_first, p_t.shape)
                    p_drop = jnp.where(keep, p_t / (1.0 - t.dropout_rate), 0.0)
                    dp_t = jnp.where(keep, dp_t / (1.0 - t.dropout_rate), 0.0)
                dv = dv + jnp.dot(
                    p_drop.astype(do.dtype), do, preferred_element_type=jnp.float32
                )
                ds_t = p_t * (dp_t - delta_ref[0, r])
                dk = dk + jnp.dot(
                    ds_t.astype(q.dtype), q, preferred_element_type=jnp.float32
                )
                return dk, dv

            dk_scr[keys, :], dv_scr[keys, :] = t.over_queries(
                c, q_step, (dk_scr[keys, :], dv_scr[keys, :])
            )

    @_when(t.iq == t.nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * t.sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _reshape_bh(x):
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


def _kvm_specs(use_mask, heads, block_k, order="q_inner_k"):
    """BlockSpec for the [B, Sk, 1] key-validity column (keys lie on the
    sublanes of a transposed score sub-tile); bh -> batch via // heads."""
    if not use_mask:
        if order == "q_inner_k":
            return pl.BlockSpec((1, 1, 1), lambda bh, iq, ik: (0, 0, 0))
        return pl.BlockSpec((1, 1, 1), lambda bh, ik, iq: (0, 0, 0))
    shape = (1, block_k, 1)
    if order == "q_inner_k":
        return pl.BlockSpec(shape, lambda bh, iq, ik: (bh // heads, ik, 0))
    return pl.BlockSpec(shape, lambda bh, ik, iq: (bh // heads, ik, 0))


def _kvm_column(kv_mask):
    """[B, Sk] validity -> [B, Sk, 1], or a dummy when there is no mask."""
    if kv_mask is None:
        return jnp.zeros((1, 1, 1), jnp.int32)
    return kv_mask.astype(jnp.int32)[:, :, None]


def _row_spec(block_q, sub_q, order="q_inner_k"):
    """BlockSpec for the per-query rows lse/delta, [B*H, Sq/sub_q, 1, sub_q]:
    one lane-dense row a query sub-tile."""
    shape = (1, block_q // sub_q, 1, sub_q)
    if order == "q_inner_k":
        return pl.BlockSpec(shape, lambda bh, iq, ik: (bh, iq, 0, 0))
    return pl.BlockSpec(shape, lambda bh, ik, iq: (bh, iq, 0, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k):
    out, _ = _flash_fwd_impl(
        q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k
    )
    return out


def _flash_fwd_impl(q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k):
    """Returns ``(out [B, H, Sq, D], lse [B*H, Sq] float32)``."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    sub_q, sub_k = pick_subtiles(block_q, block_k, nq, nk, key_major=False)
    use_mask = kv_mask is not None
    interpret = not device.on_tpu()
    _log_tiling(
        sq, sk, d, str(q.dtype), block_q, block_k, causal, use_mask,
        dropout_rate > 0.0,
    )

    q3, k3, v3 = _reshape_bh(q), _reshape_bh(k), _reshape_bh(v)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        sub_q=sub_q, sub_k=sub_k, nq=nq, nk=nk, diag_offset=sk - sq,
        dropout_rate=dropout_rate, use_mask=use_mask,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            _kvm_specs(use_mask, h, block_k),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            _row_spec(block_q, sub_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq // sub_q, 1, sub_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q // sub_q, 1, sub_q), jnp.float32),
            pltpu.VMEM((block_q // sub_q, 1, sub_q), jnp.float32),
            pltpu.VMEM((block_q // sub_q, d, sub_q), jnp.float32),
            _transposed_scratch(block_k // sub_k, d, sub_k, v.dtype, interpret),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(seed_arr, q3, k3, v3, _kvm_column(kv_mask))
    return out.reshape(b, h, sq, d), lse.reshape(b * h, sq)


def _flash_fwd(q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd_impl(
        q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k
    )
    # checkpoint_name tags let remat policies KEEP these residuals: under a
    # plain dots-saveable policy the pallas outputs are not dot_generals, so
    # per-layer remat would re-run the whole forward kernel in backward just
    # to regenerate them (policy "...+flash_out+flash_lse" in
    # ops/transformer.py saves them for a few MB per layer: lse is one
    # float32 a query row, [B*H, Sq]).
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, kv_mask, seed, out, lse)


def _flash_bwd(causal, sm_scale, dropout_rate, block_q, block_k, residuals, g):
    q, k, v, kv_mask, seed, out, lse = residuals
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    interpret = not device.on_tpu()
    use_mask = kv_mask is not None

    # delta_i = rowsum(dO * O): cheap elementwise reduction, leave to XLA
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(b * h, sq)

    q3, k3, v3 = _reshape_bh(q), _reshape_bh(k), _reshape_bh(v)
    do3 = _reshape_bh(g)
    kvm = _kvm_column(kv_mask)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    common = dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        nq=nq, nk=nk, diag_offset=sk - sq, dropout_rate=dropout_rate,
        use_mask=use_mask,
    )

    sub_q, sub_k = pick_subtiles(block_q, block_k, nq, nk, key_major=False)
    # lse and delta enter as one lane-dense row a query sub-tile
    rows = (b * h, sq // sub_q, 1, sub_q)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sub_q=sub_q, sub_k=sub_k, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            _kvm_specs(use_mask, h, block_k),
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            _row_spec(block_q, sub_q),
            _row_spec(block_q, sub_q),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q // sub_q, d, sub_q), jnp.float32),
            _transposed_scratch(block_k // sub_k, d, sub_k, k.dtype, interpret),
        ],
        interpret=interpret,
        name="flash_bwd_dq",
    )(seed_arr, q3, k3, v3, kvm, do3, lse.reshape(rows), delta.reshape(rows))

    sub_q, sub_k = pick_subtiles(block_q, block_k, nq, nk, key_major=True)
    rows = (b * h, sq // sub_q, 1, sub_q)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sub_q=sub_q, sub_k=sub_k, **common),
        grid=(b * h, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
            _kvm_specs(use_mask, h, block_k, order="k_inner_q"),
            pl.BlockSpec((1, block_q, d), lambda bh, ik, iq: (bh, iq, 0)),
            _row_spec(block_q, sub_q, order="k_inner_q"),
            _row_spec(block_q, sub_q, order="k_inner_q"),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(seed_arr, q3, k3, v3, kvm, do3, lse.reshape(rows), delta.reshape(rows))

    dq = dq.reshape(b, h, sq, d)
    dk = dk.reshape(b, h, sk, d)
    dv = dv.reshape(b, h, sk, d)
    # kv_mask is padding metadata (int), seed is RNG state: no gradients.
    dkvm = None if kv_mask is None else jnp.zeros_like(kv_mask)
    dseed = jnp.zeros_like(seed)
    return dq, dk, dv, dkvm, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


def additive_mask_to_kv_valid(mask):
    """Convert a padding-style additive mask (broadcast over the query dim,
    shape [B, 1, 1, Sk] or [B, Sk]-broadcastable) to a [B, Sk] validity
    vector. Returns None if the mask depends on the query position."""
    if mask is None:
        return None
    if mask.ndim == 2:
        return (mask > NEG_INF / 2).astype(jnp.int32)
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        return (mask[:, 0, 0, :] > NEG_INF / 2).astype(jnp.int32)
    return None


def flash_attention(
    q, k, v, mask=None, kv_mask=None, causal=False, sm_scale=None,
    dropout_rate=0.0, dropout_seed=0,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
):
    """Blockwise flash attention. q,k,v: [B, H, S, D].

    Masking: pass ``kv_mask`` [B, Sk] (nonzero = attend) or a padding-style
    additive ``mask`` (converted). Query-dependent additive biases are not
    supported here — use ``attention()`` / ``mha_reference`` for those.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    sq, sk = q.shape[2], k.shape[2]
    # shrink to the largest dividing block so e.g. seq 768 runs with
    # 256-blocks instead of failing the divisibility check on the default
    block_q = pick_block(sq, block_q)
    block_k = pick_block(sk, block_k)
    if block_q == 0 or block_k == 0:
        raise ValueError(
            f"flash_attention found no block size dividing sq={sq}/sk={sk}; "
            f"pad the sequence or use attention()/mha_reference"
        )
    if kv_mask is None and mask is not None:
        kv_mask = additive_mask_to_kv_valid(mask)
        if kv_mask is None:
            raise ValueError(
                "flash_attention only supports padding-style masks "
                "(broadcast over the query dim); use mha_reference for "
                "query-dependent additive biases"
            )
    seed = jnp.asarray(dropout_seed, jnp.int32)
    return _flash(
        q, k, v, kv_mask, seed, causal, float(sm_scale), float(dropout_rate),
        int(block_q), int(block_k),
    )


# Flash dispatch mode:
#   "auto"   — flash where _flash_route finds a way (one device, or
#              per-shard via shard_map over a data/model mesh); otherwise
#              the XLA path, logged once on a TPU
#   "always" — force flash (caller guarantees per-device operands, e.g.
#              inside shard_map)
#   "never"  — XLA reference path
FLASH_MODE = "auto"

# Below this sequence length the O(S^2) XLA attention is faster than the
# blockwise kernel: with S <= one block the kernel pays its launch/PRNG
# overhead without saving any memory traffic (measured on v5e: BERT-large
# seq128 trains ~9% faster via the XLA path). Flash exists to break the
# quadratic wall at long S — exactly where the reference's fused kernel
# gives up (seq cap 1024, ds_transformer_cuda.cpp:133).
FLASH_MIN_SEQ = 256


def flash_attention_sharded(
    q, k, v, mesh, kv_mask=None, causal=False, sm_scale=None,
    dropout_rate=0.0, dropout_seed=0,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
):
    """Flash attention under a data/model-parallel mesh via ``shard_map``.

    A bare ``pallas_call`` inside a GSPMD-jitted program is not partitioned
    (XLA would all-gather its operands); wrapping it in ``shard_map`` runs
    the kernel per-shard — the TPU analog of the reference's fused attention
    running independently on every data-parallel GPU
    (ds_transformer_cuda.cpp:217-231). Batch shards over ``data``, heads
    over ``model`` (Megatron-style head split); the sequence axis stays
    local — sequence sharding goes through parallel/sequence.py instead.
    """
    from jax.sharding import PartitionSpec as P

    from ..config.constants import DATA_AXIS, MODEL_AXIS

    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    block_q = pick_block(q.shape[2], block_q)
    block_k = pick_block(k.shape[2], block_k)
    if block_q == 0 or block_k == 0:
        raise ValueError(
            f"no block size divides sq={q.shape[2]}/sk={k.shape[2]}"
        )
    qspec = P(DATA_AXIS, MODEL_AXIS, None, None)
    use_mask = kv_mask is not None
    seed = jnp.asarray(dropout_seed, jnp.int32)

    def local(q, k, v, kvm, seed):
        if dropout_rate > 0.0:
            # decorrelate in-kernel dropout streams across shards (the
            # kernel seeds per LOCAL (bh, iq, ik) program id)
            di = jax.lax.axis_index(DATA_AXIS).astype(jnp.int32)
            mi = jax.lax.axis_index(MODEL_AXIS).astype(jnp.int32)
            seed = seed + di * jnp.int32(7_368_787) + mi * jnp.int32(15_485_863)
        return _flash(
            q, k, v, kvm if use_mask else None, seed, causal,
            float(sm_scale), float(dropout_rate), int(block_q), int(block_k),
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, P(DATA_AXIS, None) if use_mask else P(), P()),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v, kv_mask if use_mask else jnp.zeros((), jnp.int32), seed)


def _flash_route(mesh, q, k):
    """How flash can run for these operands: ``"sharded"`` (per-shard
    over the mesh's data/model axes via ``shard_map``), ``"local"`` (the
    operands live on one device), or a reason string when it cannot (the
    caller has already validated mask and block tiling via its can_flash
    gate). A bare ``pallas_call`` inside a GSPMD-jitted program over
    several devices is not partitioned — XLA would all-gather its
    operands — so anything else goes to the XLA path."""
    from ..config.constants import DATA_AXIS, MODEL_AXIS, SEQUENCE_AXIS

    if mesh is None:
        n = jax.device_count()
        if n == 1:
            return "local"
        return (
            f"{n} devices and no mesh plumbed into the model config "
            "(initialize()/init_inference() set config.mesh)"
        )
    if mesh.size == 1:
        return "local"
    shape = dict(mesh.shape)
    if DATA_AXIS not in shape or MODEL_AXIS not in shape:
        return f"mesh axes {tuple(shape)} lack {DATA_AXIS!r}/{MODEL_AXIS!r}"
    dp, mp = shape[DATA_AXIS], shape[MODEL_AXIS]
    if shape.get(SEQUENCE_AXIS, 1) > 1:
        return "sequence-parallel mesh (handled in parallel/sequence.py)"
    if dp * mp <= 1:
        return f"mesh {shape} shards over neither data nor model"
    b, h = q.shape[0], q.shape[1]
    if b % dp or h % mp:
        return (
            f"batch {b} / heads {h} do not divide the mesh's "
            f"data={dp} / model={mp} axes"
        )
    return "sharded"


def attention(
    q, k, v, mask=None, causal=False, sm_scale=None, dropout_rate=0.0,
    dropout_rng=None, use_flash=True, mesh=None,
):
    """Dispatcher: flash kernel when shapes tile cleanly and the mask is a
    padding mask; XLA reference otherwise (incl. learned additive biases,
    which need exact mask gradients). With ``mesh`` supplied and a
    data/model-parallel layout, flash runs per-shard via ``shard_map``;
    where several devices leave no way to run it (``_flash_route``), the
    O(S^2) path runs and, on a TPU, says why once. ``k``/``v`` may have
    fewer heads than ``q`` (grouped-query attention)."""
    if k.shape[1] != q.shape[1]:
        # grouped-query heads: each kv head serves q_heads / kv_heads query
        # heads. Repeated here, before the kernel; a grouped kernel layout
        # that reads each kv head once is not built.
        k, v = (jnp.repeat(t, q.shape[1] // k.shape[1], axis=1)
                for t in (k, v))
    sq, sk = q.shape[2], k.shape[2]
    bq = pick_block(sq, DEFAULT_BLOCK_Q)
    bk = pick_block(sk, DEFAULT_BLOCK_K)
    if dropout_rng is None:
        dropout_rate = 0.0  # matches the XLA path's no-rng => no-dropout
    kv_mask = additive_mask_to_kv_valid(mask)
    can_flash = (
        use_flash
        and bq > 0
        and bk > 0
        and (mask is None or kv_mask is not None)
    )
    # interpret-mode PRNG is not available off-TPU; route dropout to XLA there
    if dropout_rate > 0.0 and not device.on_tpu():
        can_flash = False
    if FLASH_MODE == "never":
        can_flash = False
    elif FLASH_MODE == "auto" and max(sq, sk) < FLASH_MIN_SEQ:
        can_flash = False

    if can_flash:
        route = _flash_route(mesh, q, k)
        if FLASH_MODE == "always" and route != "sharded":
            route = "local"  # caller guarantees per-device operands
        seed = jnp.asarray(0, jnp.int32)
        if dropout_rate > 0.0:
            seed = jax.random.randint(dropout_rng, (), 0, 2**31 - 1)
        if route == "sharded":
            return flash_attention_sharded(
                q, k, v, mesh, kv_mask=kv_mask, causal=causal,
                sm_scale=sm_scale, dropout_rate=dropout_rate,
                dropout_seed=seed, block_q=bq, block_k=bk,
            )
        if route == "local":
            return flash_attention(
                q, k, v, kv_mask=kv_mask, causal=causal, sm_scale=sm_scale,
                dropout_rate=dropout_rate, dropout_seed=seed,
                block_q=bq, block_k=bk,
            )
        if device.on_tpu():
            # a shape the kernel could have served is about to pay
            # O(S^2) HBM on the chip: say so, once per reason
            warn_once(
                f"flash-gave-way:{route}",
                "attention: flash kernel not used for q%s k%s — %s; "
                "running the O(S^2) XLA path",
                tuple(q.shape), tuple(k.shape), route,
            )
    return mha_reference(
        q, k, v, mask=mask, causal=causal, sm_scale=sm_scale,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng,
    )
