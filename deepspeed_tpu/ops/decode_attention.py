"""Pallas fused decode kernels: single-query flash over the paged KV
pool, and the SGMV-style gathered LoRA matmul.

The serving hot path (ROADMAP item 1). The XLA paged decode step
(ops/transformer.py:transformer_block_decode_paged) gathers every slot's
pages back into a contiguous ``[B, heads, max_len, hd]`` logical view and
runs the full-matrix einsum over it — one HBM round trip to materialize
the view, a second to read it, and full-length compute even for slots
three tokens into a 1024-token budget. The training side never had this
problem because its attention went through the Pallas flash kernel
(ops/attention.py) long ago; decode never did.

:func:`paged_flash_decode` is the decode twin of that kernel, shaped by
the PagedAttention lineage (vLLM — PAPERS.md) and FlashAttention's
online softmax:

  * grid ``(B, max_blocks)``: one program per (slot, logical page).
  * the per-slot **block table rides as a scalar-prefetch operand**, so
    each program's BlockSpec index_map resolves logical page ``j`` of
    slot ``b`` to its PHYSICAL page before the body runs — the pool
    pages stream HBM->VMEM directly through the indirection, and no
    ``[B, heads, max_len, hd]`` gathered temporary ever exists.
  * **only live pages run**: a program whose physical page is the NULL
    page (0 — dead slots, never-allocated table tails) or whose page
    starts beyond the slot's current position skips its body entirely.
    A fully-dead slot (zero-length block table) therefore does zero
    attention work and emits exact zeros — the early-out the unfused
    path can't express (it masks, but still pays the full einsum).
  * online softmax (running max / sum / weighted-V accumulate in VMEM
    scratch, f32) across the slot's pages; the K/V page blocks feed the
    MXU in their storage dtype with f32 accumulation, the same dtype
    discipline as ops/attention.py.

Numerics: the online softmax visits keys pagewise instead of in one
full-length softmax, so logits agree with the XLA path to float
tolerance, not bitwise — greedy PARITY (identical argmax trajectories)
is the pinned contract (tests/unit/test_paged_kv.py), with the XLA path
remaining the reference. Off-TPU both kernels run in Pallas interpret
mode, so CPU tier-1 exercises the real kernel logic.

:func:`lora_sgmv` is the Punica-style SGMV analog (PAPERS.md
"Adapters") for the batched multi-LoRA decode step: per-slot adapter ids
ride as scalar prefetch and each program reads ITS slot's A/B pool rows
directly — no ``[B, in, r]`` / ``[B, r, out]`` gathered weight stacks
materialized per projection per layer per step, which is exactly what
the XLA gather path pays on adapter-heavy mixed batches.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from .attention import NEG_INF


def _flash_decode_kernel(
    tables_ref, positions_ref,  # scalar prefetch
    q_ref, k_ref, v_ref, out_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_size, max_blocks,
):
    """One (slot, logical page) program of the single-query flash decode.

    ``q_ref`` [1, heads, hd] is slot ``b``'s query; ``k_ref``/``v_ref``
    [1, block_size, heads, hd] are the PHYSICAL page the index_map
    resolved through the block table. Scratch carries the online-softmax
    state (running max ``m``, normalizer ``l``, weighted-V accumulator)
    across the slot's pages; the final page writes ``acc / l``.

    One query row per head leaves the MXU nothing to do, and Mosaic
    refuses a ``dot_general`` batched over a middle axis (heads sits
    between tokens and hd in the page). So the two contractions are VPU
    multiplies with a lane reduction (q.k over hd) and a reduction over
    the leading token axis (p.v): no transposes, every intermediate
    keeps the page's ``[token, head, lane]`` layout, and the kernel
    stays bytes-bound on the page stream as decode attention should be.
    """
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = positions_ref[b]
    phys = tables_ref[b * max_blocks + j]
    # live-page early-out: the null page (dead slots, unallocated table
    # tails) and pages starting beyond the slot's position do no work —
    # the whole point of fusing the gather
    run = (phys != 0) & (j * block_size <= pos)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # [heads, hd]
        k = k_ref[0].astype(jnp.float32)  # [block_size, heads, hd]
        v = v_ref[0].astype(jnp.float32)
        # scores per (token, head): contract hd on the lanes
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * sm_scale
        # validity within the page: token index j*bs + t <= pos
        tok = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        )
        s = jnp.where(tok <= pos, s, NEG_INF)  # [block_size, heads, 1]

        m_prev = m_scr[...]  # [heads, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=0)
        # p broadcasts along the lanes; the token axis reduces away
        acc_scr[...] = acc_scr[...] * alpha + jnp.sum(p * v, axis=0)
        m_scr[...] = m_new

    @pl.when(j == max_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # dead slots -> exact zeros
        out_ref[0] = (acc_scr[...] / l).astype(out_ref.dtype)


def paged_flash_decode(q, k_pool, v_pool, block_tables, positions,
                       sm_scale=None, interpret=None):
    """Fused single-query attention over the paged KV pool.

    ``q`` [B, heads, hd] (this step's queries, one per slot);
    ``k_pool``/``v_pool`` [num_blocks, block_size, heads, hd] (one
    layer's page pool, physical page 0 = the null page); ``block_tables``
    [B, max_blocks] int32; ``positions`` [B] int32 (each slot's current
    token index — keys at indices <= position attend, everything beyond
    is masked exactly as the XLA path masks it). Returns the attention
    context [B, heads, hd].

    The caller must have already scattered this step's k/v into the pool
    (the kernel reads the token at ``positions`` from its page like any
    other cached key). Off-TPU the kernel runs in interpret mode.
    """
    if interpret is None:
        interpret = not device.on_tpu()
    b, heads, hd = q.shape
    block_size = k_pool.shape[1]
    max_blocks = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)

    tables_flat = block_tables.reshape(-1).astype(jnp.int32)
    positions = positions.astype(jnp.int32)

    kernel = functools.partial(
        _flash_decode_kernel,
        sm_scale=float(sm_scale), block_size=int(block_size),
        max_blocks=int(max_blocks),
    )

    def page_spec():
        # logical page j of slot b -> the physical page the prefetched
        # block table names; this index_map IS the gather
        return pl.BlockSpec(
            (1, block_size, heads, hd),
            lambda b, j, tables, pos: (tables[b * max_blocks + j], 0, 0, 0),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, heads, hd), lambda b, j, tables, pos: (b, 0, 0)
            ),
            page_spec(),
            page_spec(),
        ],
        out_specs=pl.BlockSpec(
            (1, heads, hd), lambda b, j, tables, pos: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, hd), v_pool.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(tables_flat, positions, q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# SGMV: segmented gathered matrix-vector for the multi-LoRA decode step
# ---------------------------------------------------------------------------
def _sgmv_kernel(ids_ref, x_ref, a_ref, b_ref, out_ref):
    """One slot's LoRA delta: ``x @ A[id] @ B[id]`` with the pool rows
    resolved by the BlockSpec index_map from the prefetched ids — the
    per-slot weight gather never materializes."""
    x = x_ref[0]  # [1, in]
    t = jax.lax.dot_general(
        x, a_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [1, r]
    out_ref[0] = jax.lax.dot_general(
        t.astype(b_ref.dtype), b_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)  # [1, out]


def lora_sgmv(x, a_pool, b_pool, ids, interpret=None):
    """Per-slot gathered LoRA delta for the decode step (Punica's SGMV
    shape, PAPERS.md "Adapters").

    ``x`` [B, in] (one token per slot), ``a_pool`` [n_adapters+1, in, r]
    / ``b_pool`` [n_adapters+1, r, out] (row 0 = the all-zeros identity),
    ``ids`` [B] int32. Returns the UNSCALED delta ``x @ A[id] @ B[id]``
    [B, out] in f32 — the caller applies the (alpha/r) scale and adds it
    to the base projection, mirroring the XLA path's arithmetic order.

    Each grid program's A/B BlockSpecs index the pool by the
    scalar-prefetched id, so a batch mixing any adapters reads exactly
    B (in*r + r*out) weights from HBM instead of materializing gathered
    [B, in, r]/[B, r, out] stacks first; id 0 reads the identity rows
    and contributes an exact-zero delta. Ids are data, not shapes — the
    one compiled program serves every adapter mix (the block-table
    indirection trick again).
    """
    if interpret is None:
        interpret = not device.on_tpu()
    b, din = x.shape
    r = a_pool.shape[2]
    dout = b_pool.shape[2]
    ids = ids.astype(jnp.int32)

    # x/out ride as [B, 1, d] so each slot's (1, 1, d) block has its last
    # two dims equal to the array's — a (1, d) block over [B, d] breaks
    # the TPU's (8, 128) block rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, din), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((1, din, r), lambda i, ids: (ids[i], 0, 0)),
            pl.BlockSpec((1, r, dout), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dout), lambda i, ids: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _sgmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, dout), jnp.float32),
        interpret=interpret,
        name="lora_sgmv",
    )(ids, x[:, None, :], a_pool, b_pool)
    return out[:, 0, :]
