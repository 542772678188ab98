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
q-block, kv-block), so the backward pass regenerates bit-identical masks
without storing them (the reference stores an explicit byte mask,
dropout_kernels.cu; regeneration is the bandwidth-friendly TPU design).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from ..utils.logging import warn_once

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
# Default block sizes, measured on v5e (GPT-2-large, seq 1024, full train
# step): 128x128 -> 37 model TFLOPS, 256x256 -> 52, 512x512 -> 60,
# 1024x1024 -> 61. Bigger blocks amortize the online-softmax bookkeeping
# and launch overhead; 512 sits within 2% of the best while keeping VMEM
# (~1 MB f32 scores/program) and grid parallelism comfortable for long
# sequences. ops/autotune.py re-derives this choice empirically on new
# hardware (the role of the reference's GEMM autotuner, gemm_test.h).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

# checkpoint_name tags remat-policy specs can name (consumed by
# ops/transformer.py:resolve_remat_policy). attn_probs/flash_* are emitted
# here; "zero3_gathered" tags the just-in-time all-gathered layer weights
# of the ZeRO-3 stack (models/stack.py) — naming it in a policy SAVES the
# gathered weights across backward (skipping the re-gather at n_layers x
# full-layer HBM cost; the default stage-3 policies deliberately exclude
# it so backward re-gathers instead).
CHECKPOINT_NAMES = ("attn_probs", "flash_out", "flash_lse", "zero3_gathered")


def pick_block(seq, maximum):
    """Largest block <= maximum that divides ``seq``, halving from the
    default (so a seq like 768 uses 256-blocks rather than losing the
    flash path to the 512 default). ``seq <= maximum`` returns ``seq``
    itself — a block equal to the full dim is always TPU-tileable. Returns
    0 when nothing >= 8 divides."""
    b = min(maximum, seq)
    while b >= 8:
        if seq % b == 0:
            return b
        b //= 2
    return seq if seq <= maximum else 0


def _dropout_keep(shape, rate):
    """Regenerable keep-mask from the already-seeded per-core PRNG."""
    bits = pltpu.prng_random_bits(shape)
    threshold = jnp.uint32(int(rate * (2**32)))
    return bits >= threshold


def _masked_scores(
    s, kvm_ref, iq, ik, *, causal, block_q, block_k, diag_offset, use_mask
):
    """Apply causal (with sq!=sk diagonal offset) and key-validity masking."""
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(
            cols + ik * block_k <= rows + iq * block_q + diag_offset, s, NEG_INF
        )
    if use_mask:
        valid = kvm_ref[0, :1] > 0  # [1, BK]
        s = jnp.where(valid, s, NEG_INF)
    return s


def _fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q, block_k, nk,
    diag_offset, dropout_rate, use_mask,
):
    iq, ik = pl.program_id(1), pl.program_id(2)
    bh = pl.program_id(0)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = jnp.asarray(True)
    if causal:
        run = ik * block_k <= iq * block_q + (block_q - 1) + diag_offset

    @pl.when(run)
    def _body():
        # keep matmul operands in their storage dtype (bf16 in bf16
        # training): the MXU consumes bf16 pairs natively and accumulates
        # f32 via preferred_element_type — an explicit f32 upcast before
        # the dot forces the much slower f32 MXU path (measured: the bulk
        # of the round-3 flash MFU gap). Softmax bookkeeping stays f32.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [BQ, BK] f32
        s = _masked_scores(
            s, kvm_ref, iq, ik, causal=causal, block_q=block_q,
            block_k=block_k, diag_offset=diag_offset, use_mask=use_mask,
        )

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked rows: m_new == NEG_INF makes exp(s - m_new) = 1, so
        # explicitly zero masked entries (keeps l == 0 -> output zeros)
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)

        if dropout_rate > 0.0:
            pltpu.prng_seed(seed_ref[0] + bh * 2_000_003 + iq * 4_001 + ik)
            keep = _dropout_keep((block_q, block_k), dropout_rate)
            p_use = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        else:
            p_use = p

        pv = jax.lax.dot_general(
            p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # lse rides a 128-lane trailing dim (TPU blocks need the last two
        # dims (8,128)-tileable; m_scr columns are already broadcast-equal)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _bwd_dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr, *, sm_scale, causal, block_q, block_k, nk,
    diag_offset, dropout_rate, use_mask,
):
    iq, ik = pl.program_id(1), pl.program_id(2)
    bh = pl.program_id(0)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = jnp.asarray(True)
    if causal:
        run = ik * block_k <= iq * block_q + (block_q - 1) + diag_offset

    @pl.when(run)
    def _body():
        # operands stay in storage dtype for every dot (MXU-native bf16
        # with f32 accumulation); only softmax/ds arithmetic runs f32
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        s = _masked_scores(
            s, kvm_ref, iq, ik, causal=causal, block_q=block_q,
            block_k=block_k, diag_offset=diag_offset, use_mask=use_mask,
        )
        p = jnp.exp(s - lse_ref[0, :, :1])  # true softmax probs
        p = jnp.where(s > NEG_INF / 2, p, 0.0)  # fully-masked rows

        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            pltpu.prng_seed(seed_ref[0] + bh * 2_000_003 + iq * 4_001 + ik)
            keep = _dropout_keep((block_q, block_k), dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta_ref[0, :, :1])
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal, block_q, block_k, nq,
    diag_offset, dropout_rate, use_mask,
):
    ik, iq = pl.program_id(1), pl.program_id(2)
    bh = pl.program_id(0)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = jnp.asarray(True)
    if causal:
        run = ik * block_k <= iq * block_q + (block_q - 1) + diag_offset

    @pl.when(run)
    def _body():
        # storage-dtype matmul operands (MXU-native bf16, f32 accumulate)
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        s = _masked_scores(
            s, kvm_ref, iq, ik, causal=causal, block_q=block_q,
            block_k=block_k, diag_offset=diag_offset, use_mask=use_mask,
        )
        p = jnp.exp(s - lse_ref[0, :, :1])  # [BQ, BK]
        p = jnp.where(s > NEG_INF / 2, p, 0.0)  # fully-masked rows

        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            pltpu.prng_seed(seed_ref[0] + bh * 2_000_003 + iq * 4_001 + ik)
            keep = _dropout_keep((block_q, block_k), dropout_rate)
            p_drop = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        else:
            p_drop = p
        # dv += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, :, :1])
        # dk += dS^T q
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _reshape_bh(x):
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


NUM_LANES = 128
NUM_SUBLANES = 8


def _kvm_specs(use_mask, heads, block_k, order="q_inner_k"):
    """BlockSpec for the [B, 8, Sk] validity tensor (8 broadcast sublanes so
    the block is TPU-tileable); bh -> batch via // heads."""
    if not use_mask:
        if order == "q_inner_k":
            return pl.BlockSpec((1, 1, 1), lambda bh, iq, ik: (0, 0, 0))
        return pl.BlockSpec((1, 1, 1), lambda bh, ik, iq: (0, 0, 0))
    shape = (1, NUM_SUBLANES, block_k)
    if order == "q_inner_k":
        return pl.BlockSpec(shape, lambda bh, iq, ik: (bh // heads, 0, ik))
    return pl.BlockSpec(shape, lambda bh, ik, iq: (bh // heads, 0, ik))


def _broadcast_kvm(kv_mask):
    """[B, Sk] validity -> [B, 8, Sk] (sublane-broadcast for TPU tiling)."""
    b, sk = kv_mask.shape
    return jax.lax.broadcast_in_dim(
        kv_mask.astype(jnp.int32), (b, NUM_SUBLANES, sk), (0, 2)
    )


def _lse_spec(block_q, order="q_inner_k"):
    """BlockSpec for [B*H, Sq, 128] lse/delta (lane-broadcast trailing dim)."""
    if order == "q_inner_k":
        return pl.BlockSpec((1, block_q, NUM_LANES), lambda bh, iq, ik: (bh, iq, 0))
    return pl.BlockSpec((1, block_q, NUM_LANES), lambda bh, ik, iq: (bh, iq, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k):
    out, _ = _flash_fwd_impl(
        q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k
    )
    return out


def _flash_fwd_impl(q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    diag_offset = sk - sq
    interpret = not device.on_tpu()
    use_mask = kv_mask is not None

    q3, k3, v3 = _reshape_bh(q), _reshape_bh(k), _reshape_bh(v)
    kvm = (
        _broadcast_kvm(kv_mask)
        if use_mask
        else jnp.zeros((1, 1, 1), jnp.int32)
    )
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        nk=nk, diag_offset=diag_offset, dropout_rate=dropout_rate,
        use_mask=use_mask,
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
            _lse_spec(block_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, NUM_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(seed_arr, q3, k3, v3, kvm)
    return out.reshape(b, h, sq, d), lse


def _flash_fwd(q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd_impl(
        q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k
    )
    # the 128 lse lanes are broadcast-equal: save one, re-broadcast in bwd
    # (keeps the held-across-backward residual at [B*H, Sq], not 128x that)
    #
    # checkpoint_name tags let remat policies KEEP these residuals: under a
    # plain dots-saveable policy the pallas outputs are not dot_generals, so
    # per-layer remat would re-run the whole forward kernel in backward just
    # to regenerate them (policy "...+flash_out+flash_lse" in
    # ops/transformer.py saves them for a few MB per layer).
    out = checkpoint_name(out, "flash_out")
    lse0 = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (q, k, v, kv_mask, seed, out, lse0)


def _flash_bwd(causal, sm_scale, dropout_rate, block_q, block_k, residuals, g):
    q, k, v, kv_mask, seed, out, lse = residuals
    b, h, sq, d = q.shape
    lse = jax.lax.broadcast_in_dim(lse, (*lse.shape, NUM_LANES), (0, 1))
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    diag_offset = sk - sq
    interpret = not device.on_tpu()
    use_mask = kv_mask is not None

    # delta_i = rowsum(dO * O): cheap elementwise reduction, leave to XLA;
    # lane-broadcast like lse so the block is TPU-tileable
    delta = jax.lax.broadcast_in_dim(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(
            b * h, sq
        ),
        (b * h, sq, NUM_LANES),
        (0, 1),
    )

    q3, k3, v3 = _reshape_bh(q), _reshape_bh(k), _reshape_bh(v)
    do3 = _reshape_bh(g)
    kvm = (
        _broadcast_kvm(kv_mask)
        if use_mask
        else jnp.zeros((1, 1, 1), jnp.int32)
    )
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    common = dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        diag_offset=diag_offset, dropout_rate=dropout_rate, use_mask=use_mask,
    )

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            _kvm_specs(use_mask, h, block_k),
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            _lse_spec(block_q),
            _lse_spec(block_q),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(seed_arr, q3, k3, v3, kvm, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **common),
        grid=(b * h, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik, iq: (bh, ik, 0)),
            _kvm_specs(use_mask, h, block_k, order="k_inner_q"),
            pl.BlockSpec((1, block_q, d), lambda bh, ik, iq: (bh, iq, 0)),
            _lse_spec(block_q, order="k_inner_q"),
            _lse_spec(block_q, order="k_inner_q"),
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
    )(seed_arr, q3, k3, v3, kvm, do3, lse, delta)

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
    O(S^2) path runs and, on a TPU, says why once."""
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
