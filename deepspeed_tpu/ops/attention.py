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

Entry points:
  - ``mha_reference``: plain XLA attention (always correct, differentiable
    through arbitrary additive masks; the numerics oracle and fallback).
  - ``flash_attention``: custom-vjp Pallas forward/backward over q, k, v
    [B, H, S, D]. Masking is a compact per-key validity vector [B, Sk]
    (non-differentiable padding semantics) — NOT a full [B,H,Sq,Sk]
    additive bias, which would reintroduce the O(S^2) footprint the kernel
    exists to avoid. Three STRUCTURAL forms are the kernels' own, made from
    ``iota`` on the sub-tiles their edges cross and skipped elsewhere:
    ``causal``, ``block_diffusion=B`` and, beside ``causal``, ``window=W``.
  - ``flash_attention_packed``: the same kernels over the fused qkv
    projection's own result [B, S, 3*H*D]; the context comes back
    [B, S, H*D] (``_Operands`` below).
  - ``flash_attention_latent``: the same kernels over a latent mixer's
    operands where its projections wrote them (q in its unrotated and
    rotated parts, the k | v up-projection's result, the one shared rotated
    key part), the score as the sum of two products; ``latent_layout``
    chooses it, ``latent_refusal`` says why not.
  - ``attention`` / ``attention_packed``: dispatchers for the two operand
    forms. Padding-style additive masks (broadcast over the query dim) are
    converted to validity vectors and sent to flash; learned/general
    additive biases (q-dependent) go to the XLA path so their gradients
    are exact. ``attention_layout`` chooses, and logs, which form the
    kernels get.

Dropout inside the kernel uses the TPU PRNG seeded per (batch*head,
q granule, k granule) of 128 x 128 scores, so the backward pass regenerates
bit-identical masks without storing them (the reference stores an explicit
byte mask, dropout_kernels.cu; regeneration is the bandwidth-friendly TPU
design).
"""

import collections
import dataclasses
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
    q, k, v, mask=None, causal=False, sm_scale=None, dropout_rate=0.0,
    dropout_rng=None, window=0,
):
    """q,k,v: [B, H, S, D]; mask: additive, broadcastable to [B, H, Sq, Sk].
    ``window=W`` beside ``causal``: a query sees its last W keys only."""
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
        if window:
            s = jnp.where(idx_k > idx_q + (sk - sq) - window, s, NEG_INF)
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
# The three kernels of before PR 33 alone at [8, 20, 1024, 64] bf16 causal
# on one "TPU v5 lite" chip, forward + dq + dkv in ms (device time from a
# profiler trace, PR 25, docs/TESTING.md): the one-level kernels before
# PR 25 at 512 x 512 blocks 1.22 + 0.94 + 1.10 (16.6 TFLOP/s in the
# forward), at 1024 x 1024 0.74 + 0.73 + 1.02; these kernels at 512 x 512
# blocks (a 2 x 2 grid, then a fori_loop walk) 0.76 + 0.89 + 1.33, at
# 1024 x 1024 0.43 + 0.48 + 0.60 (50 TFLOP/s in the forward). These are the
# ceiling pick_block starts from; block and sub-tiles follow from the shape.
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
# top-k and the sort again. "gdn_segments" tags what the backward pass of the
# segmented delta rule needs of its forward scan (ops/linear_attention.py:
# the mixer's output before the out-projection and one state a segment): a
# policy naming it keeps them, and per-layer remat then does not run the scan
# over chunks a second time only to have them.
CHECKPOINT_NAMES = ("attn_probs", "flash_out", "flash_lse", "zero3_gathered",
                    "moe_plan", "gdn_segments")


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
# every loop bound is known at trace time and the walk unrolls into one
# straight-line body that the scheduler packs as a whole (how well, below).
# With more blocks the bounds depend on the grid position, but only through
# the few CLASSES of step a grid has (under ``causal`` a step lies ON the
# diagonal or UNDER it): a kernel lowers one body a class, each with its
# bounds as ints, and the walk unrolls there too (PR 46; ``_walk_plan``
# below). Only a shape with more classes than ``MAX_WALK_BODIES`` walks by
# ``fori_loop``, whose every step costs ~0.3 us that nothing overlaps.
#
# Two kernels run in training: ``flash_fwd`` and ONE backward, key-major
# like the old dkv kernel, that computes each score sub-tile once and puts
# dq, dk and dv out of it (PR 33; it runs as ``flash_bwd_dkv``, the name
# the benchmark's ``flash_ms.train`` sums). Before, ``flash_bwd_dq`` and
# ``flash_bwd_dkv`` each computed the same ``k q^T``, ``exp``, ``v dO^T``
# and ``ds``: seven products and two ``exp`` passes a sub-tile where five
# and one do. The pair is kept for the shapes whose dq does not fit VMEM
# (``backward_plan``).
#
# The forward carries a CHAIN a query stripe of a head (the running max, the
# sum, the accumulator) from one key sub-tile to the next; a grid step holds
# ``heads_a_block x block_q / sub_q`` of them and they do not depend on each
# other. Until PR 50 a step lowered them one after another, in 512-square
# sub-tiles, and the compiler's schedule of such a body (``tools/
# flash_schedule.py``, no chip) ALTERNATES: stretches where all four of the
# matrix unit's slots are full, then 400-600 bundles a sub-tile of max / exp /
# sum with the unit idle, the ``exp`` pass at one vector a bundle through the
# one ``exp`` slot and the one store slot (every one of a sub-tile's 256
# score registers is spilled as it is popped and again as it is exponentiated).
# 40% of a body's bundles held no product, and on the chip the kernel ran at
# 43-51% of its roofline where the backward, whose sub-tiles are independent,
# fills 98% of those slots and runs at 70-76%. Since PR 50 a step walks
# KEY-MAJOR (``_fwd_kernel``): the key sub-tile outermost, every chain that
# sees it advancing by one link, each link's score product emitted
# ``FWD_SCORES_AHEAD`` links before its softmax; each chain meets its
# sub-tiles in the order it always did, so the bits are the same. In
# 256-square sub-tiles the schedule then keeps the unit's slots 75-95% full
# through the whole body. A step of one chain, and a walk by ``fori_loop``,
# keep the old order (``_forward_order``).
#
# Sub-tile sizes, measured alone on "TPU v5 lite" (docs/TESTING.md; ms a
# call, bf16). The FORWARD (PR 50; query x key sub-tile, old order -> new): at
# [2, 32, 16384, 128] under the block-diffusion mask 512 square 25.88 -> 23.09,
# 256 x 512 26.45 -> 22.61, 512 x 256 27.46 -> 21.97, 256 square 23.80 ->
# 20.97, 128 square 20.34 -> 20.63; at packed [1, 8192, 3*16*128] causal 2.773
# -> 2.450, 2.823 -> 2.375, -, 2.570 -> 2.247, 2.186 -> 2.223; at packed
# [8, 1024, 3*20*64] causal with the bias (one block, two heads a block: four
# chains at 512) 0.428 -> 0.358, 0.638 -> 0.349, -, 0.695 -> 0.325, 0.817 ->
# 0.458: the old order wanted few large steps wherever two heads share a block
# (PR 25 read 0.83 / 0.67 / 0.43 at 128 / 256 / 512 square there) and the new
# one wants 256 (128 square reads level at 128 lanes and costs four times the
# unrolled links to compile). In the new order at every shape of the table
# (256 square | 512 square | 512 q x 128 k | 256 q x 128 k): GPT-2's 0.322 |
# 0.358 | 0.327 | 0.365; [2, 16, 16384, 256] causal 29.35 | 29.77 | 27.46 |
# 30.38; the LATENT layout at q/k 128 + 64 on v 128 11.31 | 12.35 | 11.82 |
# 11.27; a band of 512 keys 4.76 | 5.71 | 5.61 | 4.49; [2, 48, 8192, 128]
# causal 13.30 | 14.60 | 12.73 | 12.92; and BERT's packed [8, 512, 3*16*64]
# with a key mask, ONE block of 512 rows, 0.150 | 0.150 | 0.125 | 0.150. So
# the forward takes 256 square under blocks of 1,024 (level with the best or
# within 7% of it at every shape, and the band needs no rule of its own), and
# a block of up to 512 rows stays ONE stripe a head over key sub-tiles of 128
# where a block's two heads are its chains (where it holds one head, a step
# has one chain and the parent's 512-steps).
# The BACKWARD at [8, 20, 1024, 64] causal, one
# block each way, 128 / 256 / 512 square (PR 25): dq 1.35 / 0.57 / 0.48, dkv
# 0.60 / 0.63 / 0.78; the fused backward (PR 33, packed operands with the
# bias) 0.92 / 0.74 / 0.87, 256 q x 128 k 0.89, 128 q x 256 k 0.98, against
# the pair's 0.49 + 0.59. dq carries a chain as the forward does and keeps its
# large steps (no cell runs the pair); dkv's sub-tiles are independent, so it
# takes the 128-steps that visit 9/16 of the causal square; the fused kernel
# adds every sub-tile's product into dq's accumulator in VMEM, which
# 128-steps do four times as often as 256-steps (5/8 of the square). BERT's
# [8, 16, 512, 64] with a key mask reads the same: 0.36 / 0.26 / 0.28
# against the pair's 0.16 + 0.24. On a grid of several blocks the fused
# kernel under a ``fori_loop`` walk had nothing to choose ([1, 16, 8192,
# 128]: 5.60 at 512 square, 5.55 to 6.06 elsewhere; PR 33); with one static
# body a class of step (PR 46) the same shape reads 4.21 at 512 square, 4.21
# at 512 q x 256 k and 256 q x 512 k, 4.10 at 256 square, and 256 square wins
# at every cell's shape: 61.28 -> 60.46 at [2, 16, 16384, 256], 24.33 -> 23.74
# at q/k 192 on v 128, 39.37 -> 37.65 under the block-diffusion mask, 10.00 ->
# 7.96 under a band of 512 keys (three quarter-size sub-tiles a stripe where
# two whole ones were visited). So the fused kernel takes 256 on every grid;
# the pair's dkv (no cell runs it on several blocks) keeps 512 there, not
# measured since.
SUB_FORWARD = 256
SUB_FORWARD_SHORT = 128
SUB_QUERY_MAJOR = 512
SUB_KEY_MAJOR = 128
SUB_FUSED = 256
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


def pick_subtiles(block_q, block_k, nq, nk, key_major, fused=False,
                  forward=False, heads_a_block=1):
    """(sub_q, sub_k) of the ``forward``, of dq (neither flag), or of the
    key-major kernels (``key_major``: dkv of the pair, or the ``fused``
    backward), for blocks on an ``nq x nk`` grid: the fused kernel's 256 on
    every grid, the pair's dkv 128 on one block; the forward's 256 where a Q
    block is longer than 512 rows, else the block as ONE stripe, a head, over
    key sub-tiles of 128 where the ``heads_a_block`` are several chains and
    of 512 where a step has the one."""
    target = SUB_QUERY_MAJOR
    if forward and block_q > SUB_QUERY_MAJOR:
        target = SUB_FORWARD
    elif forward and heads_a_block > 1:
        return block_q, pick_subtile(block_k, SUB_FORWARD_SHORT)
    elif key_major and fused:
        target = SUB_FUSED
    elif key_major and nq == nk == 1:
        target = SUB_KEY_MAJOR
    return pick_subtile(block_q, target), pick_subtile(block_k, target)


def _clip(x, lo, hi):
    if all(isinstance(a, int) for a in (x, lo, hi)):
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


# The band (``window=W`` beside ``causal``): query ``i`` sees key ``j`` iff
# ``i - W < j <= i``: a sliding window of ``W`` keys that ends at the query's
# own position, the fourth structural form. Two diagonals ``W`` apart bound it,
# so a walk has a lower bound as well as an upper one, and the sub-tiles that
# EITHER diagonal crosses build the mask from ``iota``; ``W`` need not divide
# or be divided by a block or a sub-tile. ``S W - W (W - 1) / 2`` of the ``S^2``
# pairs are allowed. A grid's INNER axis holds only the blocks the band touches
# (``_band_blocks``): at W 512 under 1,024-blocks a query block needs its own
# key block and the one before, 2 steps a query block at any length. ``W >= S``
# is ``causal`` and lowers to its program.


def _band_key_range(q_first, sub_q, k_first, sub_k, nsk, diag_offset, window):
    """``(lo, a, b, hi)`` under the band: of the ``nsk`` key sub-tiles from
    key ``k_first``, ``[lo, a)`` are crossed by the band's lower edge for the
    ``sub_q`` query rows from ``q_first``, ``[a, b)`` are wholly allowed,
    ``[b, hi)`` are crossed by the causal diagonal (or, where the band is
    narrower than a sub-tile, by both), the rest are empty."""
    n_full, hi = _key_range(q_first, sub_q, k_first, sub_k, nsk, diag_offset)
    # the first key the first row sees, the first that the last row sees
    first = q_first + diag_offset - k_first - window + 1
    lo = _clip(first // sub_k, 0, hi)
    a = _clip(-((-(first + sub_q - 1)) // sub_k), lo, hi)
    return lo, a, _clip(n_full, a, hi), hi


def _band_query_range(k_first, sub_k, q_first, sub_q, nsq, diag_offset, window):
    """The same seen from ``sub_k`` keys starting at ``k_first`` over a Q block
    that starts at row ``q_first``: ``(lo, a, b, hi)``, query sub-tiles ``[lo,
    a)`` crossed by the causal diagonal, ``[a, b)`` wholly allowed, ``[b, hi)``
    crossed by the band's lower edge."""
    lo, full = _query_range(k_first, sub_k, q_first, sub_q, nsq, diag_offset)
    # one past the last row that sees the first key; the last key's
    past = k_first - diag_offset - q_first + window
    hi = _clip((past + sub_k - 2) // sub_q + 1, lo, nsq)
    a = _clip(full, lo, hi)
    return lo, a, _clip(past // sub_q, a, hi), hi


def _band_blocks(outer, block_q, block_k, nq, nk, diag_offset, window,
                 key_major):
    """``(first, last)`` of the blocks of the INNER axis that the band
    touches for block ``outer`` of the outer one: the key blocks of a query
    block (a query-major grid), or with ``key_major`` the query blocks of a
    key block. Python ints or traced int32 alike."""
    if key_major:
        first = (outer * block_k - diag_offset) // block_q
        last = (outer * block_k + block_k - 2 - diag_offset + window) // block_q
        return _clip(first, 0, nq - 1), _clip(last, 0, nq - 1)
    first = (outer * block_q + diag_offset - window + 1) // block_k
    last = (outer * block_q + block_q - 1 + diag_offset) // block_k
    return _clip(first, 0, nk - 1), _clip(last, 0, nk - 1)


def _band_inner_steps(block_q, block_k, nq, nk, diag_offset, window, key_major):
    """Steps of a grid's inner axis under the band: the most blocks that any
    block of the outer axis needs."""
    spans = (
        _band_blocks(o, block_q, block_k, nq, nk, diag_offset, window, key_major)
        for o in range(nk if key_major else nq)
    )
    return max(last - first + 1 for first, last in spans)


def band_mask(sq, sk, window):
    """The band as a dense additive ``[sq, sk]`` float32 array (0 allowed,
    ``NEG_INF`` not): what the kernels never build. For tests."""
    rows = jnp.arange(sq)[:, None] + (sk - sq)
    keys = jnp.arange(sk)[None, :]
    allowed = (keys <= rows) & (keys > rows - window)
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


# The block-diffusion mask (``block_diffusion=B``): a row of ``2 L``
# positions is ``[noisy ; clean]``, both halves numbered ``0..L-1`` in blocks
# of ``B``. A noisy query sees the noisy keys of its OWN block (both ways
# inside it) and the clean keys of strictly EARLIER blocks; a clean query sees
# the clean keys up to the end of its own block and no noisy key. Neither a
# diagonal nor a property of the key alone: a third, structural form beside
# ``causal`` and the key-validity column. Blocks and sub-tiles divide ``L``,
# so each lies in one half and the case is a scalar of the grid position;
# the bounds below skip what the mask empties and only the sub-tiles that
# the staircase or the block diagonal crosses build it from ``iota``. ``L^2 +
# L B`` of the ``4 L^2`` pairs are allowed: a quarter of the square, half of
# what a causal walk over ``2 L`` visits.


def _sel(cond, a, b):
    """``a if cond else b`` for a condition known at trace time or not."""
    if isinstance(cond, bool):
        return a if cond else b
    return jnp.where(cond, a, b)


def _bd_key_range(q_first, sub_q, k_first, sub_k, nsk, half, block):
    """``(lo, n_full, hi)`` under the block-diffusion mask: of the ``nsk``
    key sub-tiles from key ``k_first`` (one half's), ``[lo, n_full)`` are
    wholly allowed for the ``sub_q`` query rows from ``q_first`` (one
    half's), ``[n_full, hi)`` are crossed, the rest are empty. Python ints
    or traced int32 alike."""
    q_clean, k_clean = q_first >= half, k_first >= half
    q = q_first - _sel(q_clean, half, 0)
    k = k_first - _sel(k_clean, half, 0)
    first = q // block * block                 # the first row's block starts
    last = (q + sub_q - 1) // block * block    # the last row's
    # clean keys: a row sees those before its block (noisy row) or before
    # its block's end (clean row)
    ext = _sel(q_clean, block, 0)
    full_c = (first + ext - k) // sub_k
    hi_c = -((k - last - ext) // sub_k)
    # noisy keys: a noisy row sees its own block, a clean row none
    lo_n = _sel(q_clean, 0, (first - k) // sub_k)
    hi_n = _sel(q_clean, 0, -((k - last - block) // sub_k))
    lo = _clip(_sel(k_clean, 0, lo_n), 0, nsk)
    n_full = _clip(_sel(k_clean, full_c, lo_n), 0, nsk)
    return lo, n_full, _clip(_sel(k_clean, hi_c, hi_n), 0, nsk)


def _bd_query_range(k_first, sub_k, q_first, sub_q, nsq, half, block):
    """The same seen from ``sub_k`` keys from ``k_first``, over the ``nsq``
    query sub-tiles of a Q block that starts at row ``q_first``: ``(lo,
    full, hi)``, ``[lo, full)`` crossed, ``[full, hi)`` wholly allowed."""
    q_clean, k_clean = q_first >= half, k_first >= half
    q = q_first - _sel(q_clean, half, 0)
    k = k_first - _sel(k_clean, half, 0)
    # clean keys: row i sees key j iff j < i's block start + ext
    ext = _sel(q_clean, block, 0)
    some = ((k - ext) // block + 1) * block           # first row seeing key k
    every = ((k + sub_k - 1 - ext) // block + 1) * block   # ... the last key
    lo_c, full_c = (some - q) // sub_q, -((q - every) // sub_q)
    # noisy keys: the noisy rows of the keys' own blocks, all crossed
    lo_n = _sel(q_clean, 0, (k // block * block - q) // sub_q)
    hi_n = _sel(
        q_clean, 0,
        -((q - (k + sub_k - 1) // block * block - block) // sub_q),
    )
    lo = _clip(_sel(k_clean, lo_c, lo_n), 0, nsq)
    full = _clip(_sel(k_clean, full_c, hi_n), 0, nsq)
    return lo, full, _clip(_sel(k_clean, nsq, hi_n), 0, nsq)


def _bd_key_block(iq, ik, block_q, block_k, half, block):
    """The key block that step ``(iq, ik)`` of a query-major grid holds
    under the block-diffusion mask: ``ik`` where the step runs, else the
    nearest block, in the walk's order, that a step of this Q block needs.
    A skipped step then asks for the block its neighbour holds and the
    pipeline copies nothing for it."""
    nqh, nkh = half // block_q, half // block_k
    q_clean = iq >= nqh
    q = (iq - _sel(q_clean, nqh, 0)) * block_q
    last = (q + block_q - 1) // block * block + _sel(q_clean, block, 0) - 1
    own = _clip(ik, q // block_k, (q + block_q - 1) // block_k)
    clean = nkh + _clip(ik - nkh, 0, _clip(last, 0, half) // block_k)
    return _sel(ik < nkh, _sel(q_clean, nkh, own), clean)


def _bd_query_block(ik, iq, block_q, block_k, half, block):
    """The same for step ``(ik, iq)`` of a key-major grid: the Q block
    (of q, dO, lse and delta) it holds."""
    nqh, nkh = half // block_q, half // block_k
    k = (ik - _sel(ik >= nkh, nkh, 0)) * block_k
    own = _clip(iq, k // block_q, (k + block_k - 1) // block_q)
    # a clean key: noisy rows from the block after its own, clean rows from
    # its own block's
    start = k // block * block
    after = (start + block) // block_q
    noisy = _sel(after < nqh, _clip(iq, after, nqh - 1), nqh + start // block_q)
    clean = nqh + _clip(iq - nqh, start // block_q, nqh - 1)
    return _sel(ik >= nkh, _sel(iq < nqh, noisy, clean), own)


def block_diffusion_mask(seq, block):
    """The mask as a dense additive ``[seq, seq]`` float32 array (0 allowed,
    ``NEG_INF`` not): what the kernels never build. For the XLA path at
    lengths under the kernels' and for tests."""
    half = seq // 2
    at = jnp.arange(seq)
    clean, blk = at >= half, (at % half) // block
    qc, kc, qb, kb = clean[:, None], clean[None, :], blk[:, None], blk[None, :]
    allowed = jnp.where(
        kc, jnp.where(qc, kb <= qb, kb < qb), ~qc & (kb == qb))
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


# what ``_key_spans`` / ``_query_spans`` need of a call's mask
_MaskForm = collections.namedtuple(
    "_MaskForm", "causal diag_offset block_diffusion half window")


def _key_spans(q_first, sub_q, k_first, sub_k, nsk, mask):
    """The walk of ``sub_q`` query rows from ``q_first`` over the ``nsk`` key
    sub-tiles of a K block from key ``k_first`` under ``mask`` (``_MaskForm``),
    as runs ``(lo, hi, diagonal)`` in the walk's order: sub-tiles ``[lo, hi)``,
    crossed by an edge of the mask (``diagonal``: they build it from ``iota``)
    or wholly allowed. Python ints or traced int32 alike."""
    causal, diag_offset, block_diffusion, half, window = mask
    if block_diffusion:
        lo, n_full, hi = _bd_key_range(
            q_first, sub_q, k_first, sub_k, nsk, half, block_diffusion)
        return (lo, n_full, False), (n_full, hi, True)
    if window:
        lo, a, b, hi = _band_key_range(
            q_first, sub_q, k_first, sub_k, nsk, diag_offset, window)
        return (lo, a, True), (a, b, False), (b, hi, True)
    if causal:
        n_full, hi = _key_range(q_first, sub_q, k_first, sub_k, nsk, diag_offset)
        return (0, n_full, False), (n_full, hi, True)
    return ((0, nsk, False),)


def _query_spans(k_first, sub_k, q_first, sub_q, nsq, mask):
    """The same seen from ``sub_k`` keys from ``k_first`` over the ``nsq``
    query sub-tiles of a Q block that starts at row ``q_first``."""
    causal, diag_offset, block_diffusion, half, window = mask
    if block_diffusion:
        lo, full, hi = _bd_query_range(
            k_first, sub_k, q_first, sub_q, nsq, half, block_diffusion)
        return (lo, full, True), (full, hi, False)
    if window:
        lo, a, b, hi = _band_query_range(
            k_first, sub_k, q_first, sub_q, nsq, diag_offset, window)
        return (lo, a, True), (a, b, False), (b, hi, True)
    if causal:
        lo, full = _query_range(k_first, sub_k, q_first, sub_q, nsq, diag_offset)
        return (lo, full, True), (full, nsq, False)
    return ((0, nsq, False),)


def _visited_share(
    sq, sk, block_k, sub_q, sub_k, causal, block_diffusion=0, window=0
):
    """Share of the ``sq x sk`` score square that lies in sub-tiles a
    kernel visits, from the bounds that set its loops."""
    mask = _MaskForm(causal, sk - sq, block_diffusion, sq // 2, window)
    visited = sum(
        max(hi - lo, 0)
        for q_first in range(0, sq, sub_q)
        for k_first in range(0, sk, block_k)
        for lo, hi, _ in _key_spans(
            q_first, sub_q, k_first, sub_k, block_k // sub_k, mask)
    )
    return visited * sub_q * sub_k / (sq * sk)


# A grid of several blocks. A step's place beside the mask's edges follows from
# its grid position, and on the cells' grids only a few places differ: under
# plain ``causal`` with square blocks a step lies ABOVE the diagonal (nothing
# to do), ON it (the bounds of the one-block case) or UNDER it (every sub-tile
# whole, no mask). ``_walk_plan`` finds these CLASSES of step at trace time, by
# running the same span arithmetic the kernels would run, with Python ints, over
# every step of the grid: steps whose spans are equal are one class. A kernel
# then lowers one body a class, each under its own ``pl.when`` with its spans as
# ints (so that the walk unrolls as it does on one block, and only the crossed
# sub-tiles build a mask), and reads the running step's class from a table that
# rides behind the dropout seed in SMEM. Shapes with more classes than
# ``MAX_WALK_BODIES`` keep ONE body whose bounds are traced and whose walk is a
# ``fori_loop``: ~0.3 us a step that nothing overlaps, and a loop body that the
# scheduler cannot interleave with its neighbour's. The ceiling is what
# lowering costs: a body more is 0.35 to 1 s of Mosaic's compile a kernel (a
# forward + backward pair compiled for a described v5e, PR 46: two bodies 1.9
# to 4.0 s where the one loop body takes 1.2 to 1.9, four bodies 3.7), paid at
# every cold start, and no cell's grid has more than three classes.
#
# The other half of the same fact: the operands of a grid's INNER axis (K and V
# of a query-major grid; q, dO, lse and delta of a key-major one) hold, through
# the steps that do nothing, the block their neighbour needs (``_needed_blocks``),
# and the pipeline moves a block only when its index changes.
MAX_WALK_BODIES = 4


def _needed_blocks(
    key_major, block_q, block_k, nq, nk, causal, diag_offset, block_diffusion,
    window,
):
    """``(g -> block, steps)`` for the operands a grid's INNER axis walks
    (the keys' of a query-major grid ``(group, iq, ik)``, the queries' of a
    key-major one ``(group, ik, iq)``) and how many steps that axis has; the
    map is None where every step holds its own block. A step that does
    nothing holds the nearest block, in the walk's order, that a step of its
    row needs. Under the band the axis has only as many steps as a block of
    the outer axis needs blocks (``_band_blocks``). Python ints or traced
    int32 alike."""
    steps = nq if key_major else nk
    if window or (causal and steps > 1):
        # plain causal: the band whose lower edge lies before the first key
        band = (block_q, block_k, nq, nk, diag_offset,
                window or nq * block_q + nk * block_k)

        def needed(g):
            # the band's inner axis counts its steps from ``first``
            first, last = _band_blocks(g[1], *band, key_major)
            return _clip(g[2] + (first if window else 0), first, last)

        return needed, _band_inner_steps(*band, key_major) if window else steps
    if not block_diffusion:
        return None, steps
    pick = _bd_query_block if key_major else _bd_key_block
    return lambda g: pick(
        g[1], g[2], block_q, block_k, nq * block_q // 2, block_diffusion
    ), steps


def _walk_plan(*walk):
    """How a kernel walks an ``nq x nk`` grid (``walk``: ``key_major``, the
    sub-tiles, then ``_grid_form``): ``walk`` (``static``: one body a class
    of step; ``loop``: more classes than ``MAX_WALK_BODIES``), ``bodies``
    (each class's spans, a tuple of ``_key_spans`` a query sub-tile or of
    ``_query_spans`` a key sub-tile, empty runs left out; none under
    ``loop``), ``table`` (the class of every step, row by row of the outer
    axis: 0 does nothing, ``i + 1`` runs ``bodies[i]``; None where one body
    serves every step) and ``steps``: how many of the grid's steps ``run``,
    are ``skipped``, and ``fetched`` a block of an inner-axis operand (a step
    whose block differs from the step before in its row)."""
    bodies, table, steps = _walk_classes(*walk)
    static = len(bodies) <= MAX_WALK_BODIES
    one_body = not static or (len(bodies) == 1 and not steps["skipped"])
    return {
        "walk": "static" if static else "loop",
        "bodies": bodies if static else (),
        "table": None if one_body else table,
        "steps": steps,
    }


@functools.lru_cache(maxsize=None)
def _walk_classes(
    key_major, sub_q, sub_k, block_q, block_k, nq, nk, causal, diag_offset,
    block_diffusion, window,
):
    """``(bodies, table, steps)`` of ``_walk_plan``, before its ceiling: the
    span arithmetic of the kernels, run with Python ints over every step."""
    mask = _MaskForm(causal, diag_offset, block_diffusion, nq * block_q // 2, window)
    needed, steps = _needed_blocks(
        key_major, block_q, block_k, nq, nk, causal, diag_offset,
        block_diffusion, window)
    band = (block_q, block_k, nq, nk, diag_offset, window)
    nsq, nsk = block_q // sub_q, block_k // sub_k
    bodies, table, fetched = {}, [], 0
    for outer in range(nk if key_major else nq):
        at = _band_blocks(outer, *band, key_major)[0] if window else 0
        held = None
        for step in range(steps):
            iq, ik = (at + step, outer) if key_major else (outer, at + step)
            spans = ()
            if iq < nq and ik < nk:
                q_first, k_first = iq * block_q, ik * block_k
                walks = (
                    _query_spans(k_first + c * sub_k, sub_k, q_first, sub_q, nsq, mask)
                    for c in range(nsk)
                ) if key_major else (
                    _key_spans(q_first + r * sub_q, sub_q, k_first, sub_k, nsk, mask)
                    for r in range(nsq)
                )
                spans = tuple(
                    tuple(run for run in walk if run[1] > run[0]) for walk in walks)
            table.append(bodies.setdefault(spans, len(bodies) + 1) if any(spans) else 0)
            block = needed((0, outer, step)) if needed else step
            fetched += block != held
            held = block
    run = sum(c > 0 for c in table)
    return tuple(bodies), tuple(table), {
        "run": run, "skipped": len(table) - run, "fetched": fetched}


# What the fused backward may hold in VMEM for dq: the float32 accumulator
# over a group's whole query length and the two buffers of its output block.
# Everything else the kernel holds is what ``flash_bwd_dkv`` of the pair
# holds, which fits Mosaic's default limit; the call asks for the sum. A v5e
# core has 128 MiB (``gdn_bwd`` runs with a limit of 64).
FUSED_DQ_VMEM_BUDGET = 48 * 2**20
PAIR_VMEM_BYTES = 16 * 2**20


def backward_plan(
    sq, sk, block_q, block_k, causal, lanes=128, itemsize=2, budget=None,
    block_diffusion=0, window=0,
):
    """Which backward a call gets, from its shape, its dtype and the VMEM
    budget alone: ``fused`` (one key-major kernel writes dq, dk and dv from
    each score sub-tile) where dq's accumulator and output for ``sq`` rows of
    ``lanes`` lanes fit ``budget``, else the ``pair`` (``flash_bwd_dq`` and
    ``flash_bwd_dkv``), with the reason. Also the sub-tiles the key-major
    walk computes in, the share of the score square it visits and how it
    walks the grid (``_walk_report``)."""
    if budget is None:
        budget = FUSED_DQ_VMEM_BUDGET
    nq, nk = sq // block_q, sk // block_k
    # a block narrower than the 128 lanes of a vector register takes them all
    dq_bytes = sq * -(-lanes // 128) * 128 * (4 + 2 * itemsize)
    fused = dq_bytes <= budget
    sub_q, sub_k = pick_subtiles(block_q, block_k, nq, nk, True, fused)
    return {
        "backward": "fused" if fused else "pair",
        "sub_q": sub_q, "sub_k": sub_k,
        "visited_share": _visited_share(
            sq, sk, block_k, sub_q, sub_k, causal, block_diffusion, window
        ),
        "dq_vmem_bytes": dq_bytes if fused else 0,
        "reason": None if fused else (
            f"dq over {sq} rows of {lanes} lanes takes {dq_bytes} bytes of "
            f"VMEM, budget {budget}"
        ),
        **_walk_report(
            True, sub_q, sub_k, block_q, block_k, nq, nk, causal, sk - sq,
            block_diffusion, window),
    }


def _forward_order(walk, nsq, heads_a_block):
    """How ``flash_fwd`` walks ONE grid step: ``chains``, the softmax chains
    (a head's query stripe of ``sub_q`` rows each, ``nsq`` a head) that the
    step advances side by side, and ``order``: ``key_major`` (the key sub-tile
    outermost, every chain that sees it advancing by one link) where a step
    has several chains and its spans are ints (``walk`` ``static``), else
    ``query_major`` (one chain after another: nothing to put side by side, or
    a ``fori_loop`` a stripe)."""
    chains = nsq * heads_a_block if walk == "static" else 1
    return {"order": "key_major" if chains > 1 else "query_major",
            "chains": chains}


def _walk_report(*walk):
    """``walk`` (``static`` | ``loop``), ``bodies`` (classes of step lowered)
    and ``steps`` (``run``, ``skipped``, ``fetched``) of ``_walk_plan``."""
    plan = _walk_plan(*walk)
    return {"walk": plan["walk"], "bodies": len(plan["bodies"]) or 1,
            "steps": dict(plan["steps"])}


def flash_tiling(
    sq, sk, block_q, block_k, causal, key_major=False, sub_q=None, sub_k=None,
    block_diffusion=0, window=0, heads_a_block=1, **plan,
):
    """Outer blocks, sub-tiles and the share of the ``sq x sk`` score
    square whose sub-tiles a kernel visits (the forward, or dkv with
    ``key_major``), from the same bounds that set its loops; how the
    kernel walks the grid (``_walk_report``) and, the forward, a grid step
    (``_forward_order``, for programs of ``heads_a_block`` heads); and under
    ``backward`` what ``backward_plan`` chooses for the call (``plan``: its
    ``lanes``, ``itemsize`` and ``budget``)."""
    picked = pick_subtiles(
        block_q, block_k, sq // block_q, sk // block_k, key_major,
        forward=not key_major, heads_a_block=heads_a_block,
    )
    sub_q = picked[0] if sub_q is None else sub_q
    sub_k = picked[1] if sub_k is None else sub_k
    walk = _walk_report(
        key_major, sub_q, sub_k, block_q, block_k, sq // block_q,
        sk // block_k, causal, sk - sq, block_diffusion, window)
    if not key_major:
        walk.update(_forward_order(
            walk["walk"], block_q // sub_q, heads_a_block))
    return {
        "block_q": block_q, "block_k": block_k, "sub_q": sub_q,
        "sub_k": sub_k,
        "visited_share": _visited_share(
            sq, sk, block_k, sub_q, sub_k, causal, block_diffusion, window
        ),
        **walk,
        "backward": backward_plan(
            sq, sk, block_q, block_k, causal, block_diffusion=block_diffusion,
            window=window, **plan,
        ),
    }


@functools.lru_cache(maxsize=None)
def _log_tiling(
    sq, sk, d, lanes, dtype, block_q, block_k, causal, use_mask, dropout,
    block_diffusion=0, window=0, heads_a_block=1,
):
    t = flash_tiling(
        sq, sk, block_q, block_k, causal, lanes=lanes,
        itemsize=jnp.dtype(dtype).itemsize, block_diffusion=block_diffusion,
        window=window, heads_a_block=heads_a_block,
    )
    b = t["backward"]

    def walk(w):
        return "%s bodies=%d steps=%d/%d/%d" % (
            w["walk"], w["bodies"], *w["steps"].values())

    logger.debug(
        "flash_tiling sq=%d sk=%d d=%d %s causal=%s mask=%s dropout=%s "
        "block=%dx%d sub=%dx%d visited_share=%.4f walk=%s order=%s chains=%d "
        "backward=%s bwd_sub=%dx%d bwd_visited_share=%.4f bwd_walk=%s "
        "dq_vmem_bytes=%d%s%s%s",
        sq, sk, d, dtype, causal, use_mask, dropout, block_q, block_k,
        t["sub_q"], t["sub_k"], t["visited_share"], walk(t), t["order"],
        t["chains"],
        b["backward"], b["sub_q"], b["sub_k"], b["visited_share"], walk(b),
        b["dq_vmem_bytes"], f" reason={b['reason']!r}" if b["reason"] else "",
        f" block_diffusion={block_diffusion}" if block_diffusion else "",
        f" window={window}" if window else "",
    )


def _scale_is_exact(sm_scale):
    """A power of two (1/8 at head_dim 64) only shifts exponents: folding
    it into an operand changes no bit of the scores."""
    return math.frexp(sm_scale)[0] == 0.5


def _keep_mask(seed_ref, bh, q_first, k_first, shape, gran, rate):
    """Regenerable keep-mask of a ``[keys, queries]`` sub-tile of scores whose
    corner is (``k_first``, ``q_first``), drawn granule by granule: each
    ``gran = (gran_k, gran_q)`` granule is seeded by its global (batch*head,
    q granule, k granule) position, so the forward and every backward
    kernel draw the same bit for the same score element whatever sub-tile
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


def _block_diffusion_allowed(shape, q_first, k_first, half, block):
    """Which scores of a ``[keys, queries]`` sub-tile the block-diffusion
    mask allows, from ``iota``; the sub-tile's rows lie in one half and so
    do its keys, so which case applies is two scalars. ``start`` is each
    query's block start: a key is seen from ``start - below`` (its own
    block's first noisy key; every clean key) up to ``start + above`` (its
    block's end; a noisy query stops before its block among the clean
    keys). A clean query never meets a noisy key here: no loop visits it."""
    q_clean, k_clean = q_first >= half, k_first >= half
    keys = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + (
        k_first - _sel(k_clean, half, 0))
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + (
        q_first - _sel(q_clean, half, 0))
    start = rows & -block   # a power of two
    above = _sel(k_clean & ~q_clean, 0, block)
    below = _sel(k_clean, half, 0)
    return (keys < start + above) & (keys >= start - below)


def _parts(x):
    """A q or k operand (a ref, a value, its lanes) as its parts: the LATENT
    layout's two, (unrotated, rotated), or the one of every other layout."""
    return x if isinstance(x, tuple) else (x,)


def _map(f, x, *more):
    """``f`` over the parts of ``x`` (and of ``more`` beside them), in
    ``x``'s form."""
    out = tuple(f(*each) for each in zip(_parts(x), *map(_parts, more)))
    return out if isinstance(x, tuple) else out[0]


def _scores_t(
    k, q, valid, q_first, k_first, *, sm_scale, fold_scale, diagonal,
    diag_offset, block_diffusion=0, half=0, window=0,
):
    """One transposed score sub-tile ``s_t = k q^T`` ([keys, queries]) with
    causal, block-diffusion and key-validity masking. ``diagonal``: the
    causal diagonal (the block-diffusion mask's staircase or block diagonal)
    crosses this sub-tile; the ones wholly under it skip the
    iota/compare/select. ``valid``: the keys' validity column or None. ``k``
    and ``q`` in the LATENT layout are their two parts (``_parts``): the score
    is the sum of the parts' products, in float32."""
    s_t = None
    for k_part, q_part in zip(_parts(k), _parts(q)):
        product = jax.lax.dot_general(
            k_part, q_part, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        s_t = product if s_t is None else s_t + product
    if not fold_scale:
        s_t = s_t * sm_scale
    if diagonal and block_diffusion:
        s_t = jnp.where(
            _block_diffusion_allowed(
                s_t.shape, q_first, k_first, half, block_diffusion),
            s_t, NEG_INF,
        )
    elif diagonal:
        keys = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0) + k_first
        rows = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1) + q_first
        allowed = keys <= rows + diag_offset
        if window:
            allowed &= keys > rows + (diag_offset - window)
        s_t = jnp.where(allowed, s_t, NEG_INF)
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


# Where one head of a program's block lies in everything the kernels index by
# lane or, once transposed into scratch, by row. SPLIT and PACKED have ONE
# answer to all of it (the head's lanes of the block); in the LATENT layout
# (``_Operands`` below) q and k are two parts each, (unrotated, rotated), in
# refs of their own, k's unrotated part and v share a ref, and each scratch
# holds the parts behind one another. ``q``: in q's refs and in dq's; ``k``:
# in k's refs; ``v``: in v's ref and in dv's; ``o``: in the arrays of v's
# width (the context, dO) and the rows of ``acc_scr`` and ``vt_scr``; ``kt``
# and ``dq``: the rows of k's parts in ``kt_scr`` and of dq's in ``dq_scr``;
# ``dk``: the parts' lanes in the head's ``dk_scr``; ``dk_out``: in dk's refs.
_HeadLanes = collections.namedtuple("_HeadLanes", "q k v o kt dq dk dk_out")


class _Tiles:
    """What the kernels share: the grid position (a Python 0 on an
    axis of one block, so that every bound derived from it is static),
    sub-tile counts, the heads of one program's block, the masking flags,
    and the bodies a kernel lowers: one a class of step (``_walk_plan``)."""

    def __init__(
        self, q_axis, scalars_ref, *, sm_scale, causal, block_q, block_k,
        sub_q, sub_k, nq, nk, diag_offset, dropout_rate, use_mask, head_dim,
        heads_a_block, use_bias, block_diffusion=0, window=0, rope_dim=0,
        v_dim=0,
    ):
        self.group = pl.program_id(0)
        # LATENT: the last ``rope_dim`` of a head's ``head_dim`` q and k
        # lanes are its rotated part, and v has ``v_dim``
        self.rope_dim, self.v_dim = rope_dim, v_dim
        self.nope_rows = heads_a_block * (head_dim - rope_dim)
        self.block_diffusion, self.half = block_diffusion, nq * block_q // 2
        self.window, self.key_major = window, q_axis == 2
        self.use_bias = use_bias
        if window:
            # the inner axis walks the blocks the band touches, from the
            # first that this block of the outer axis needs
            self.band = (block_q, block_k, nq, nk, diag_offset, window)
            self.steps = _band_inner_steps(*self.band, self.key_major)
            self.step = pl.program_id(2) if self.steps > 1 else 0
            outer = (
                pl.program_id(1) if (nk if self.key_major else nq) > 1 else 0
            )
            at = _band_blocks(outer, *self.band, self.key_major)[0] + self.step
            self.iq, self.ik = (at, outer) if self.key_major else (outer, at)
        else:
            self.iq = pl.program_id(q_axis) if nq > 1 else 0
            self.ik = pl.program_id(3 - q_axis) if nk > 1 else 0
        self.causal, self.diag_offset = causal, diag_offset
        self.block_q, self.block_k = block_q, block_k
        self.sub_q, self.sub_k = sub_q, sub_k
        self.nsq, self.nsk = block_q // sub_q, block_k // sub_k
        self.nq, self.nk = nq, nk
        self.head_dim, self.heads_a_block = head_dim, heads_a_block
        self.sm_scale = sm_scale
        self.fold_scale = _scale_is_exact(sm_scale)
        self.use_mask = use_mask
        self.dropout_rate = dropout_rate
        self.gran = (
            pick_subtile(block_k, DROPOUT_TILE), pick_subtile(block_q, DROPOUT_TILE)
        )
        self.mask = _MaskForm(
            causal, diag_offset, block_diffusion, self.half, window)
        self.scores = functools.partial(
            _scores_t, sm_scale=sm_scale, fold_scale=self.fold_scale,
            diag_offset=diag_offset, block_diffusion=block_diffusion,
            half=self.half, window=window,
        )
        self.guard = use_mask or diag_offset < 0
        # ``classes``: (the condition a body runs under, its spans) a body
        plan = _walk_plan(
            self.key_major, sub_q, sub_k, block_q, block_k, nq, nk, causal,
            diag_offset, block_diffusion, window)
        self.chains = _forward_order(
            plan["walk"], self.nsq, heads_a_block)["chains"]
        if plan["walk"] == "loop":
            # one body, its bounds from the grid position as it runs
            self.run = self._runs()
            self.classes = [(self.run, None)]
        elif plan["table"] is None:
            self.run = True
            self.classes = [(True, plan["bodies"][0])]
        else:
            inner = len(plan["table"]) // (nk if self.key_major else nq)
            this = scalars_ref[
                1 + pl.program_id(1) * inner + pl.program_id(2)]
            self.run = this > 0
            self.classes = [
                (this == i + 1, spans) for i, spans in enumerate(plan["bodies"])]

    def each_body(self, body):
        """Lower ``body(spans)`` once a class of step, each under the
        condition that the running step is of that class."""
        for run, spans in self.classes:
            _when(run)(functools.partial(body, spans))

    def _runs(self):
        """Whether the mask leaves this step anything to do (whole blocks
        above the diagonal, past the band, outside the block-diffusion mask
        are skipped), from the grid position as the step runs."""
        if self.block_diffusion:
            lo, _, hi = _bd_key_range(
                self.iq * self.block_q, self.block_q, self.ik * self.block_k,
                self.block_k, 1, self.half, self.block_diffusion,
            )
            return hi > lo
        last_row = self.iq * self.block_q + (self.block_q - 1) + self.diag_offset
        if self.window:
            # a step past the last block that the band touches does nothing
            return (
                (self.ik * self.block_k <= last_row)
                & (self.ik * self.block_k + (self.block_k - 1)
                   > self.iq * self.block_q + self.diag_offset - self.window)
                & (self.iq < self.nq)
            )
        return self.ik * self.block_k <= last_row if self.causal else True

    # where a walk of the inner axis starts and ends: the first and last
    # block of it, or under the band the first and last step
    @property
    def first_step(self):
        if self.window:
            return self.step == 0
        return (self.iq if self.key_major else self.ik) == 0

    @property
    def last_step(self):
        if self.window:
            return self.step == self.steps - 1
        if self.key_major:
            return self.iq == self.nq - 1
        return self.ik == self.nk - 1

    def dq_edge(self, last):
        """The fused backward's dq rows of a Q block are zeroed in the first
        key block's step that holds them and cast out in the last one's:
        whether this step is that one."""
        if self.window:
            return self.run & (
                self.ik == _band_blocks(self.iq, *self.band, False)[last])
        return self.ik == (self.nk - 1 if last else 0)

    def exp(self, s_t, stat, diagonal=False):
        """``_exp_t`` with this call's guard. Under the block-diffusion mask
        a crossed sub-tile may hold no key of a row that has met none yet (a
        noisy row's own block lies in ONE of the noisy key sub-tiles that its
        stripe crosses): the forward, whose ``stat`` is the running maximum,
        says which sub-tiles are crossed."""
        return _exp_t(
            s_t, stat,
            self.guard
            or (bool(self.block_diffusion or self.window) and diagonal),
        )

    def heads(self):
        """``(hh, lanes)`` of each head a program serves: its place in the
        block and the static lanes of the block that hold it
        (``_HeadLanes``; all of them where a block is one head). The same
        slice picks the head's rows out of a block TRANSPOSED into
        scratch."""
        if self.rope_dim:
            return [(hh, self._latent_lanes(hh))
                    for hh in range(self.heads_a_block)]
        def everywhere(lanes):
            # (a head's ``dk_scr`` is its own: all of it)
            return _HeadLanes(
                **dict.fromkeys(_HeadLanes._fields, lanes)
            )._replace(dk=slice(None))

        if self.heads_a_block == 1:
            return [(0, everywhere(slice(None)))]
        d = self.head_dim
        return [
            (hh, everywhere(slice(hh * d, (hh + 1) * d)))
            for hh in range(self.heads_a_block)
        ]

    def _latent_lanes(self, hh):
        rope, v = self.rope_dim, self.v_dim
        nope, all_nope = self.head_dim - rope, self.nope_rows

        def at(first, width):
            return slice(first, first + width)

        q = (at(hh * nope, nope), at(hh * rope, rope))
        k_nope = at(hh * (nope + v), nope)
        return _HeadLanes(
            q=q, k=(k_nope, slice(None)), v=at(hh * (nope + v) + nope, v),
            o=at(hh * v, v), kt=(q[0], at(all_nope, rope)),
            dq=(q[0], at(all_nope + hh * rope, rope)),
            dk=(at(0, nope), at(nope, rope)), dk_out=(k_nope, q[1]))

    @property
    def dq_parts(self):
        """The rows of ``dq_scr`` that go out to each of dq's refs."""
        if not self.rope_dim:
            return slice(None)
        return (slice(0, self.nope_rows),
                slice(self.nope_rows, self.heads_a_block * self.head_dim))

    def load(self, ref, bias_ref, rows, lanes=slice(None)):
        """``rows`` x ``lanes`` of a q, k or v block, with the projection's
        bias added where the operand arrives without it: the same bf16 sum
        XLA would have written out. The parts of a LATENT q or k (refs and
        lanes in pairs) as a tuple."""
        if isinstance(ref, tuple):
            return tuple(r[0, rows, at] for r, at in zip(ref, lanes))
        x = ref[0, rows, lanes]
        return x + bias_ref[:, lanes] if self.use_bias else x

    def transpose(self, scr, c, ref, bias_ref, v=False):
        """Key sub-tile ``c`` of a K block (``v``: of a V block) transposed
        into ``scr[c]``, every head of it: in one transpose where the block
        is the heads' lanes side by side, else (LATENT) a head's lanes at a
        time, and the rotated key part, which every head shares, once."""
        rows = _rows(c, self.sub_k)
        if not self.rope_dim:
            scr[c] = self.load(ref, bias_ref, rows).T.astype(scr.dtype)
            return
        for hh, lanes in self.heads():
            if v:
                scr[c, lanes.o, :] = ref[0, rows, lanes.v].T.astype(scr.dtype)
                continue
            parts = list(zip(ref, lanes.k, lanes.kt))
            for part, at, to in parts if hh == 0 else parts[:1]:
                scr[c, to, :] = part[0, rows, at].T.astype(scr.dtype)

    def q_first(self, r):
        return self.iq * self.block_q + r * self.sub_q

    def k_first(self, c):
        return self.ik * self.block_k + c * self.sub_k

    def valid(self, kvm_ref, c):
        return kvm_ref[0, _rows(c, self.sub_k), :] if self.use_mask else None

    def keep(self, seed_ref, hh, q_first, k_first, shape):
        """Dropout bits of head ``hh`` of this program's block, seeded by
        its GLOBAL batch*head index: the same bits whichever layout the
        operands arrive in."""
        return _keep_mask(
            seed_ref, self.group * self.heads_a_block + hh, q_first, k_first,
            shape, self.gran, self.dropout_rate,
        )

    def over_keys(self, spans, r, step, carry):
        """``step(c, carry, diagonal)`` over the key sub-tiles of this
        K block that query stripe ``r`` sees, in the order of ``_key_spans``:
        a class's ``spans`` (Python ints: the walk unrolls), or None for the
        ones that follow from the grid position as the step runs."""
        if spans is None:
            return self._over(_key_spans(
                self.q_first(r), self.sub_q, self.k_first(0), self.sub_k,
                self.nsk, self.mask), step, carry)
        return self._over(spans[r], step, carry)

    def over_queries(self, spans, c, step, carry):
        """``step(r, carry, diagonal)`` over the query sub-tiles of this
        Q block that see key sub-tile ``c``, in the order of
        ``_query_spans``."""
        if spans is None:
            return self._over(_query_spans(
                self.k_first(c), self.sub_k, self.q_first(0), self.sub_q,
                self.nsq, self.mask), step, carry)
        return self._over(spans[c], step, carry)

    @staticmethod
    def by_key(spans):
        """A class's ``spans`` (``_key_spans`` a query stripe) seen from the
        keys: ``(c, ((r, diagonal), ...))`` for each key sub-tile that some
        stripe sees, ascending: the order each stripe's own walk has."""
        seen = {}
        for r, runs in enumerate(spans):
            for lo, hi, diagonal in runs:
                for c in range(lo, hi):
                    seen.setdefault(c, []).append((r, diagonal))
        return sorted(seen.items())

    @staticmethod
    def _over(spans, step, carry):
        for lo, hi, diagonal in spans:
            carry = _span(lo, hi, functools.partial(step, diagonal=diagonal), carry)
        return carry


# In ``flash_fwd``'s key-major order a link's score product is emitted this
# many links before its softmax, so that in the order of emission every softmax
# pass lies between products that do not wait on it (measured alone on "TPU v5
# lite", docs/TESTING.md, PR 50: at [2, 32, 16384, 128] under the block-diffusion
# mask and 256-square sub-tiles, the chains' links one after another 23.84 ms,
# the products one link ahead 22.17, two ahead 20.97).
FWD_SCORES_AHEAD = 2


def _fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, bq_ref, bk_ref, bv_ref, kvm_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr, vt_scr, **static,
):
    t = _Tiles(1, seed_ref, **static)
    lanes_of = dict(t.heads())

    @_when(t.first_step)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # A CHAIN is one head's query stripe ``(hh, r)``: its running maximum, sum
    # and accumulator (``m_scr[hh, r]``, ``l_scr[hh, r]``, ``acc_scr[r,
    # lanes.o]`` between grid steps, values inside one) pass from one key
    # sub-tile to the next, one LINK each.
    def load_q(hh, r):
        """The stripe's rows of q, scaled, and its first row's position."""
        # matmul operands stay in their storage dtype (MXU-native bf16
        # pairs, f32 accumulation); softmax bookkeeping is f32
        q = t.load(q_ref, bq_ref, _rows(r, t.sub_q), lanes_of[hh].q)
        if t.fold_scale:
            q = _map(lambda part: part * t.sm_scale, q)
        return q, t.q_first(r)

    def scores(hh, q, q_first, c, diagonal):
        return t.scores(
            t.load(k_ref, bk_ref, _rows(c, t.sub_k), lanes_of[hh].k), q,
            t.valid(kvm_ref, c), q_first, t.k_first(c), diagonal=diagonal,
        )

    def link(hh, q_first, c, s_t, carry, diagonal):
        """Key sub-tile ``c``'s scores into the chain's carry."""
        m_prev, l_prev, acc = carry
        m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
        p_t = t.exp(s_t, m_new, diagonal)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p_t, axis=0, keepdims=True)
        if t.dropout_rate > 0.0:
            keep = t.keep(seed_ref, hh, q_first, t.k_first(c), p_t.shape)
            p_t = jnp.where(keep, p_t / (1.0 - t.dropout_rate), 0.0)
        pv = _dot_t(vt_scr[c, lanes_of[hh].o, :], p_t, v_ref.dtype)
        return m_new, l_new, acc * alpha + pv

    def held(hh, r):
        return m_scr[hh, r], l_scr[hh, r], acc_scr[r, lanes_of[hh].o, :]

    def hold(hh, r, carry):
        m_scr[hh, r], l_scr[hh, r], acc_scr[r, lanes_of[hh].o, :] = carry

    def query_major(spans):
        # one chain after another, each a walk over its key sub-tiles (a
        # ``fori_loop`` where ``spans`` is None)
        for hh in lanes_of:
            for r in range(t.nsq):
                q, q_first = load_q(hh, r)

                def k_step(c, carry, diagonal):
                    return link(
                        hh, q_first, c, scores(hh, q, q_first, c, diagonal),
                        carry, diagonal)

                hold(hh, r, t.over_keys(spans, r, k_step, held(hh, r)))

    def key_major(spans):
        # the key sub-tile outermost: every chain that sees it advances by
        # one link, side by side, and each chain meets its sub-tiles in the
        # order ``query_major`` gives it (the same bits). The unrolled body
        # then holds independent products and softmax passes next to each
        # other, and the scheduler fills the matrix unit under one chain's
        # max / exp / sum with another's products.
        links = [(hh, r, c, diagonal) for c, seen in t.by_key(spans)
                 for hh in lanes_of for r, diagonal in seen]
        last = {(hh, r): i for i, (hh, r, _, _) in enumerate(links)}
        qs, carries, scored = {}, {}, []
        for i, (hh, r, c, diagonal) in enumerate(links):
            for hh_, r_, c_, diagonal_ in links[
                    len(scored):i + 1 + FWD_SCORES_AHEAD]:
                if (hh_, r_) not in qs:
                    qs[hh_, r_] = load_q(hh_, r_)
                    carries[hh_, r_] = held(hh_, r_)
                scored.append(scores(hh_, *qs[hh_, r_], c_, diagonal_))
            carries[hh, r] = link(
                hh, qs[hh, r][1], c, scored[i], carries[hh, r], diagonal)
            scored[i] = None
            if last[hh, r] == i:
                hold(hh, r, carries.pop((hh, r)))

    @t.each_body
    def _body(spans):
        # p v runs transposed (acc_t = v^T p_t): transpose the V block
        # once, every head of it together (a head's v^T is the rows
        # ``lanes`` of the result)
        for c in range(t.nsk):
            t.transpose(vt_scr, c, v_ref, bv_ref, v=True)
        (key_major if t.chains > 1 else query_major)(spans)

    @_when(t.last_step)
    def _finalize():
        for r in range(t.nsq):
            for hh, lanes in lanes_of.items():
                l = l_scr[hh, r]
                l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
                acc_scr[r, lanes.o, :] = acc_scr[r, lanes.o, :] / l
                lse_ref[hh, r] = m_scr[hh, r] + jnp.log(l)
            # every head of the block in one transpose: whole-lane stores
            o_ref[0, _rows(r, t.sub_q), :] = acc_scr[r].T.astype(o_ref.dtype)


def _bwd_dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, bq_ref, bk_ref, bv_ref, kvm_ref, do_ref,
    lse_ref, delta_ref,
    dq_ref, dq_scr, kt_scr, **static,
):
    t = _Tiles(1, seed_ref, **static)

    @_when(t.first_step)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @t.each_body
    def _body(spans):
        # dq_t += k^T ds_t: transpose the K block once, all its heads
        for c in range(t.nsk):
            t.transpose(kt_scr, c, k_ref, bk_ref)

        for hh, head in t.heads():
            # one answer to all of ``_HeadLanes``: the LATENT layout takes
            # the fused backward only
            lanes = head.q
            for r in range(t.nsq):
                q = t.load(q_ref, bq_ref, _rows(r, t.sub_q), lanes)
                if t.fold_scale:
                    q = q * t.sm_scale
                do = do_ref[0, _rows(r, t.sub_q), lanes]
                lse, delta = lse_ref[hh, r], delta_ref[hh, r]  # [1, sub_q] rows
                q_first = t.q_first(r)

                def k_step(c, dq_t, diagonal):
                    s_t = t.scores(
                        t.load(k_ref, bk_ref, _rows(c, t.sub_k), lanes), q,
                        t.valid(kvm_ref, c), q_first, t.k_first(c),
                        diagonal=diagonal,
                    )
                    p_t = t.exp(s_t, lse)
                    dp_t = jax.lax.dot_general(
                        t.load(v_ref, bv_ref, _rows(c, t.sub_k), lanes), do,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    if t.dropout_rate > 0.0:
                        keep = t.keep(
                            seed_ref, hh, q_first, t.k_first(c), p_t.shape
                        )
                        dp_t = jnp.where(keep, dp_t / (1.0 - t.dropout_rate), 0.0)
                    ds_t = p_t * (dp_t - delta)
                    return dq_t + _dot_t(kt_scr[c, lanes, :], ds_t, k_ref.dtype)

                dq_scr[r, lanes, :] = t.over_keys(
                    spans, r, k_step, dq_scr[r, lanes, :])

    @_when(t.last_step)
    def _finalize():
        for r in range(t.nsq):
            dq_ref[0, _rows(r, t.sub_q), :] = (
                (dq_scr[r] * t.sm_scale).T.astype(dq_ref.dtype)
            )


def _bwd_dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, bq_ref, bk_ref, bv_ref, kvm_ref, do_ref,
    lse_ref, delta_ref, *results, fused, **static,
):
    """The key-major backward. ``fused``: the one backward kernel, which
    also puts out dq from the ``ds_t`` it holds, one more product a
    sub-tile. dq sums over KEYS, the outer axis of this grid, so ``dq_scr``
    and the ``dq_ref`` block cover the group's whole query length: a Q
    block's rows are zeroed in the first key block's steps, summed
    transposed (``dq_t += k^T ds_t``, as ``_bwd_dq_kernel`` does) through
    every key block, and cast out in the last one's. Without ``fused`` it
    is the pair's dkv kernel and dq is ``_bwd_dq_kernel``'s."""
    t = _Tiles(2, seed_ref, **static)
    if fused and t.rope_dim:
        # LATENT: dq in q's two layouts, dk's unrotated part and dv in their
        # halves of ONE block (the k | v product's cotangent), dk's rotated
        # part a head (the caller sums it over the heads)
        *dq_ref, dkv_ref, dkr_ref, dq_scr, dk_scr, dv_scr, kt_scr = results
        dq_ref, dk_ref, dv_ref = tuple(dq_ref), (dkv_ref, dkr_ref), dkv_ref
    elif fused:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, kt_scr = results
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = results

    def dq_rows(r):
        """Sub-tile ``r`` of this Q block among the whole sequence's."""
        return t.iq * t.nsq + r

    @_when(fused and t.dq_edge(0))
    def _init_dq():
        for r in range(t.nsq):
            dq_scr[dq_rows(r)] = jnp.zeros(dq_scr.shape[1:], dq_scr.dtype)

    @_when(t.first_step)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        # the K block stays for every Q block of this row of the grid:
        # transposed once for all of them, all its heads together
        for c in range(t.nsk if fused else 0):
            t.transpose(kt_scr, c, k_ref, bk_ref)

    @t.each_body
    def _body(spans):
        for hh, lanes in t.heads():
            for c in range(t.nsk):
                keys = _rows(c, t.sub_k)
                k = t.load(k_ref, bk_ref, keys, lanes.k)
                v = t.load(v_ref, bv_ref, keys, lanes.v)
                k_scaled = k
                if t.fold_scale:
                    k_scaled = _map(lambda part: part * t.sm_scale, k)
                valid = t.valid(kvm_ref, c)
                k_first = t.k_first(c)

                # the sub-tile is computed as k q^T, so that dv += p_t dO
                # and dk += ds_t q are plain matmuls; lse and delta enter
                # as rows
                def q_step(r, carry, diagonal):
                    dk, dv = carry
                    q = t.load(q_ref, bq_ref, _rows(r, t.sub_q), lanes.q)
                    do = do_ref[0, _rows(r, t.sub_q), lanes.o]
                    q_first = t.q_first(r)
                    s_t = t.scores(
                        k_scaled, q, valid, q_first, k_first, diagonal=diagonal
                    )
                    p_t = t.exp(s_t, lse_ref[hh, r])
                    dp_t = jax.lax.dot_general(
                        v, do, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    p_drop = p_t
                    if t.dropout_rate > 0.0:
                        keep = t.keep(seed_ref, hh, q_first, k_first, p_t.shape)
                        p_drop = jnp.where(keep, p_t / (1.0 - t.dropout_rate), 0.0)
                        dp_t = jnp.where(keep, dp_t / (1.0 - t.dropout_rate), 0.0)
                    dv = dv + jnp.dot(
                        p_drop.astype(do.dtype), do,
                        preferred_element_type=jnp.float32,
                    )
                    ds_t = (p_t * (dp_t - delta_ref[hh, r])).astype(do.dtype)
                    dk = _map(
                        lambda dk, q: dk + jnp.dot(
                            ds_t, q, preferred_element_type=jnp.float32),
                        dk, q)
                    if fused:
                        at = dq_rows(r)
                        for rows, k_rows in zip(
                                _parts(lanes.dq), _parts(lanes.kt)):
                            dq_scr[at, rows, :] = dq_scr[at, rows, :] + _dot_t(
                                kt_scr[c, k_rows, :], ds_t, do.dtype
                            )
                    return dk, dv

                dk, dv = t.over_queries(
                    spans, c, q_step,
                    (_map(lambda at: dk_scr[hh, keys, at], lanes.dk),
                     dv_scr[hh, keys, :])
                )
                for at, part in zip(_parts(lanes.dk), _parts(dk)):
                    dk_scr[hh, keys, at] = part
                dv_scr[hh, keys, :] = dv

    @_when(t.last_step)
    def _finalize():
        for hh, lanes in t.heads():
            for ref, at, part in zip(
                    _parts(dk_ref), _parts(lanes.dk_out), _parts(lanes.dk)):
                ref[0, :, at] = (
                    dk_scr[hh, :, part] * t.sm_scale).astype(ref.dtype)
            dv_ref[0, :, lanes.v] = dv_scr[hh].astype(dv_ref.dtype)

    @_when(fused and t.dq_edge(1))
    def _finalize_dq():
        for r in range(t.nsq):
            at = dq_rows(r)
            for ref, rows in zip(_parts(dq_ref), _parts(t.dq_parts)):
                ref[0, _rows(at, t.sub_q), :] = (
                    (dq_scr[at, rows, :] * t.sm_scale).T.astype(ref.dtype)
                )


def _reshape_bh(x):
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


# Three layouts of the kernels' operands, one set of kernel bodies.
#
# SPLIT: q, k, v, dO and every result are ``[B*H, S, D]`` arrays, one head a
# program (the public ``flash_attention``: sequence parallelism, the
# grouped-query mixer, separate projections).
#
# PACKED: the kernels read q, k and v straight out of the fused qkv
# projection's ``[B, S, 3*H*D]`` result (q | k | v along the last axis, a
# head's D lanes side by side in each) and write the context, dq, dk and dv
# as ``[B, S, H*D]``: what ``ctx @ attn_ow`` and the projection's backward
# read, so no split, no ``[B,S,H,D] -> [B,H,S,D]`` transpose and none back.
# A BlockSpec block is 128 lanes wide: one head of 128, or TWO heads of 64,
# each a static 64-lane half of the block, walked one after the other by the
# same kernel body. The lane-block index picks q, k or v and the pair.
#
# LATENT (PR 47): a latent mixer's operands where ITS projections wrote them.
# A head's q and k are ``nope`` unrotated lanes then ``rope`` rotated ones,
# and the rotated key part is ONE vector a position that every head shares.
# q arrives as two arrays, ``q_nope`` ``[B, S, H*nope]`` and the rotated
# ``q_r`` ``[B, S, H*rope]``; k's unrotated part and v as the k | v
# up-projection's own result ``kv`` ``[B, S, H*(nope + v)]`` (a head's
# ``k_nope`` then its ``v``, both whole 128-lane blocks of it); the rotated
# key part as ``k_r`` ``[B, S, rope]``, read once a key block. A score
# sub-tile is the SUM of two products, ``k_nope q_nope^T + k_r q_r^T``, in
# float32 before the softmax: the contraction over a head's ``nope + rope``
# lanes, in its two parts. A program serves the TWO heads that share a
# 128-lane block of ``q_r`` (rope 64), each a static half of it, walked one
# after the other as PACKED does at width 64. The context comes back ``[B,
# S, H*v]``, dq in q's two layouts, dk's unrotated part and dv in their
# halves of ONE ``[B, S, H*(nope + v)]`` array (the up-projection's
# cotangent), and dk's rotated part a head, ``[B, S, H*rope]``, for the
# caller to sum over the heads (the grid runs a batch row's head pairs
# outermost, so no program sees them all). No head-major array, broadcast,
# concatenation or transpose exists on either side of the kernels. The fused
# backward only (``latent_refusal``).
#
# lse and delta are ``[B*H, Sq/sub_q, 1, sub_q]`` rows in all three.
#
# In all, the fused backward's dq block is a group's WHOLE query length
# (``spec(None, ..)``): its grid runs key blocks outermost and dq sums over
# keys, so the block and its float32 accumulator stay in VMEM for all the
# steps of a group and go back to HBM once (no partial dq a key block
# through HBM). dk and dv are a key block each, as in the pair.
LANES = 128


@dataclasses.dataclass(frozen=True)
class _Operands:
    batch: int
    heads: int
    head_dim: int
    packed: bool
    # lanes of a head of v, of the context and of dO and dv: split operands
    # say them (they may differ from q's and k's ``head_dim``); 0: the same
    v_dim: int = 0
    # LATENT: the last ``rope_dim`` of a head's ``head_dim`` q and k lanes
    # are its rotated part; 0: another layout
    rope_dim: int = 0

    @property
    def v_width(self):
        return self.v_dim or self.head_dim

    @property
    def heads_a_block(self):
        if self.rope_dim:
            return LANES // self.rope_dim
        return LANES // self.head_dim if self.packed else 1

    @property
    def _latent_widths(self):
        """Lanes of a program's block of q_nope, q_r, kv and k_r."""
        hb, nope = self.heads_a_block, self.head_dim - self.rope_dim
        return (hb * nope, hb * self.rope_dim, hb * (nope + self.v_width),
                self.rope_dim)

    @property
    def groups_a_batch(self):
        """Programs (head groups) a batch row."""
        return self.heads // self.heads_a_block

    @property
    def groups(self):
        return self.batch * self.groups_a_batch

    def _row_axis(self, rows, key_major):
        """Where the grid (group, a, b) holds the block index of the
        ``rows`` ("q" or "k") axis: query-major grids are (group, iq, ik),
        dkv's key-major grid is (group, ik, iq)."""
        return 1 if (rows == "q") != key_major else 2

    def _at(self, rows, key_major, needed=None):
        """``g -> `` the block index of the ``rows`` axis at grid position
        ``g``; ``needed(g)`` replaces it where a mask form says which block
        a step needs (``_bd_key_block``)."""
        axis = self._row_axis(rows, key_major)
        return needed or (lambda g: g[axis])

    def spec(self, rows, block, key_major=False, part=0, needed=None,
             v=False):
        """BlockSpec of a group's ``block`` rows of q (``part`` 0), k (1)
        or v (2) in the projection's result, or of a ``[.., H*D]`` array
        (``part`` 0). ``rows`` None: ``block`` is ALL the group's rows,
        wherever the grid stands in them, so it stays in VMEM for every
        step of the group and is written back once. ``v``: an array of v's
        width (the context, dO, dv), as ``part`` 2 is. LATENT: those arrays
        only (``in_specs``, ``dq_results`` and ``dkv_results`` have q's and
        k's)."""
        at = self._at(rows, key_major, needed) if rows else (lambda g: 0)

        if self.rope_dim:
            return self._lanes_spec(block, self.v_lanes, at)
        if not self.packed:
            width = self.v_width if v or part == 2 else self.head_dim
            return pl.BlockSpec((1, block, width), lambda *g: (g[0], at(g), 0))
        per = self.groups_a_batch
        return pl.BlockSpec(
            (1, block, LANES),
            lambda *g: (g[0] // per, at(g), part * per + g[0] % per),
        )

    def _lanes_spec(self, block, width, at, shared=False):
        """BlockSpec of ``block`` rows of a group's ``width`` lanes of a
        ``[B, S, groups a batch row * width]`` array (``shared``: of a ``[B,
        S, width]`` array that every group reads)."""
        per = self.groups_a_batch
        return pl.BlockSpec(
            (1, block, width),
            lambda *g: (g[0] // per, at(g), 0 if shared else g[0] % per),
        )

    def in_specs(self, block_q, block_k, key_major=False, qs=None, ks=None,
                 use_bias=False):
        """BlockSpecs of the operands the kernels read q, k and v out of, in
        the kernels' order: q, k and v with the three biases, or (LATENT)
        q_nope, q_r, kv and k_r. ``qs`` / ``ks``: ``spec``'s ``needed`` of
        the queries' and of the keys' operands."""
        if not self.rope_dim:
            return [
                self.spec("q", block_q, key_major, part=0, needed=qs),
                self.spec("k", block_k, key_major, part=1, needed=ks),
                self.spec("k", block_k, key_major, part=2, needed=ks),
                *(self.bias_spec(use_bias, part) for part in range(3)),
            ]
        q_at = self._at("q", key_major, qs)
        k_at = self._at("k", key_major, ks)
        nope, rope, kv, shared = self._latent_widths
        return [
            self._lanes_spec(block_q, nope, q_at),
            self._lanes_spec(block_q, rope, q_at),
            self._lanes_spec(block_k, kv, k_at),
            self._lanes_spec(block_k, shared, k_at, shared=True),
        ]

    def dq_results(self, sq, dtype):
        """``(specs, shapes)`` of the fused backward's dq: a group's whole
        query length (``spec``'s ``rows`` None), in q's layout."""
        if not self.rope_dim:
            return [self.spec(None, sq)], [self.result(sq, dtype)]
        widths = self._latent_widths[:2]
        return (
            [self._lanes_spec(sq, w, lambda g: 0) for w in widths],
            [jax.ShapeDtypeStruct((self.batch, sq, self.groups_a_batch * w),
                                  dtype) for w in widths],
        )

    def dkv_results(self, block_k, sk, dtype):
        """``(specs, shapes)`` of dk and dv, a key block each: two arrays
        in k's and v's layouts, or (LATENT) dk's unrotated part and dv in
        kv's layout, then dk's rotated part a head."""
        if not self.rope_dim:
            return [
                self.spec("k", block_k, key_major=True),
                self.spec("k", block_k, key_major=True, v=True),
            ], [self.result(sk, dtype), self.result(sk, dtype, v=True)]
        _, rope, kv, _ = self._latent_widths
        widths, at = (kv, rope), self._at("k", True)
        return (
            [self._lanes_spec(block_k, w, at) for w in widths],
            [jax.ShapeDtypeStruct((self.batch, sk, self.groups_a_batch * w),
                                  dtype) for w in widths],
        )

    def bias_spec(self, use_bias, part):
        """BlockSpec of the group's lanes of the projection's bias
        [1, 3*H*D] (packed only), or of a dummy."""
        if not use_bias:
            return pl.BlockSpec((1, LANES), lambda *g: (0, 0))
        per = self.groups_a_batch
        return pl.BlockSpec(
            (1, LANES), lambda *g: (0, part * per + g[0] % per)
        )

    def row_spec(self, block_q, sub_q, key_major=False, needed=None):
        """BlockSpec of the per-query rows lse/delta of a group's heads:
        one lane-dense row a head a query sub-tile."""
        at = self._at("q", key_major, needed)
        return pl.BlockSpec(
            (self.heads_a_block, block_q // sub_q, 1, sub_q),
            lambda *g: (g[0], at(g), 0, 0),
        )

    def kvm_spec(self, use_mask, block_k, key_major=False, needed=None):
        """BlockSpec for the [B, Sk, 1] key-validity column (keys lie on
        the sublanes of a transposed score sub-tile)."""
        if not use_mask:
            return pl.BlockSpec((1, 1, 1), lambda *g: (0, 0, 0))
        at, per = self._at("k", key_major, needed), self.groups_a_batch
        return pl.BlockSpec(
            (1, block_k, 1), lambda *g: (g[0] // per, at(g), 0)
        )

    def result(self, seq, dtype, v=False):
        """Shape of the context or of one gradient (``v``: of v's width)."""
        if self.rope_dim:
            return jax.ShapeDtypeStruct(
                (self.batch, seq, self.heads * self.v_width), dtype)
        if self.packed:
            return jax.ShapeDtypeStruct(
                (self.batch, seq, self.heads * self.head_dim), dtype
            )
        return jax.ShapeDtypeStruct(
            (self.batch * self.heads, seq,
             self.v_width if v else self.head_dim), dtype
        )

    @property
    def block_lanes(self):
        return self.heads_a_block * self.head_dim

    @property
    def v_lanes(self):
        return self.heads_a_block * self.v_width

    @property
    def kt_lanes(self):
        """Rows of a K block transposed into scratch: every head's lanes,
        or (LATENT) their unrotated parts and the ONE rotated part."""
        if self.rope_dim:
            return self._latent_widths[0] + self.rope_dim
        return self.block_lanes


def _kvm_column(kv_mask):
    """[B, Sk] validity -> [B, Sk, 1], or a dummy when there is no mask."""
    if kv_mask is None:
        return jnp.zeros((1, 1, 1), jnp.int32)
    return kv_mask.astype(jnp.int32)[:, :, None]


def _static(
    ops, sq, sk, block_q, block_k, causal, sm_scale, dropout_rate, use_mask,
    use_bias, block_diffusion=0, window=0,
):
    """The keyword arguments of ``_Tiles`` that the kernels share."""
    return dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        nq=sq // block_q, nk=sk // block_k, diag_offset=sk - sq,
        dropout_rate=dropout_rate, use_mask=use_mask, use_bias=use_bias,
        head_dim=ops.head_dim, heads_a_block=ops.heads_a_block,
        block_diffusion=block_diffusion, window=window,
        rope_dim=ops.rope_dim, v_dim=ops.v_width if ops.rope_dim else 0,
    )


def _grid_form(common):
    """What ``_walk_plan`` and ``_needed_blocks`` need of ``_static``'s."""
    return tuple(common[k] for k in (
        "block_q", "block_k", "nq", "nk", "causal", "diag_offset",
        "block_diffusion", "window"))


def _scalars(seed, plan):
    """The kernels' SMEM operand: the dropout seed and, behind it, the class
    of every grid step where the walk has a table (``_walk_plan``)."""
    seed = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    if plan["table"] is None:
        return seed
    return jnp.concatenate([seed, jnp.asarray(plan["table"], jnp.int32)])


def _bias_row(bias, dtype):
    """The projection's bias [3*H*D] as a [1, 3*H*D] row, or a dummy."""
    if bias is None:
        return jnp.zeros((1, LANES), dtype)
    return bias.astype(dtype)[None, :]


def _forward_call(
    ops, q, k, v, bias, kv_mask, seed, sq, sk, causal, sm_scale, dropout_rate,
    block_q, block_k, block_diffusion=0, window=0,
):
    """``flash_fwd`` over all B x H heads. ``q``/``k``/``v``: three
    ``[B*H, S, D]`` arrays, or the packed projection three times, then
    with the ``bias`` [3*H*D] it still lacks (or None), or (LATENT) the
    pairs ``(q_nope, q_r)`` and ``(kv, k_r)`` and no ``v``. Returns the
    context in the operands' layout and lse ``[B*H, Sq]`` float32."""
    d, dtype = ops.head_dim, _parts(q)[0].dtype
    use_bias = bias is not None
    common = _static(
        ops, sq, sk, block_q, block_k, causal, sm_scale, dropout_rate,
        kv_mask is not None, use_bias, block_diffusion, window,
    )
    nq, nk = common["nq"], common["nk"]
    sub_q, sub_k = pick_subtiles(
        block_q, block_k, nq, nk, key_major=False, forward=True,
        heads_a_block=ops.heads_a_block)
    interpret = not device.on_tpu()
    _log_tiling(
        sq, sk, d, ops.block_lanes, str(dtype), block_q, block_k, causal,
        kv_mask is not None, dropout_rate > 0.0, block_diffusion, window,
        ops.heads_a_block,
    )
    hb, nsq = ops.heads_a_block, block_q // sub_q
    keys, steps = _needed_blocks(False, *_grid_form(common))
    plan = _walk_plan(False, sub_q, sub_k, *_grid_form(common))
    out, lse = pl.pallas_call(
        functools.partial(
            _kernel_of(ops, _fwd_kernel), sub_q=sub_q, sub_k=sub_k, **common),
        grid=(ops.groups, nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *ops.in_specs(block_q, block_k, ks=keys, use_bias=use_bias),
            ops.kvm_spec(kv_mask is not None, block_k, needed=keys),
        ],
        out_specs=[ops.spec("q", block_q, v=True), ops.row_spec(block_q, sub_q)],
        out_shape=[
            ops.result(sq, dtype, v=True),
            jax.ShapeDtypeStruct(
                (ops.batch * ops.heads, sq // sub_q, 1, sub_q), jnp.float32
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, nsq, 1, sub_q), jnp.float32),
            pltpu.VMEM((hb, nsq, 1, sub_q), jnp.float32),
            pltpu.VMEM((nsq, ops.v_lanes, sub_q), jnp.float32),
            _transposed_scratch(
                block_k // sub_k, ops.v_lanes, sub_k, dtype, interpret
            ),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(_scalars(seed, plan), *_operand_arrays(ops, q, k, v, bias, dtype),
      _kvm_column(kv_mask))
    return out, lse.reshape(ops.batch * ops.heads, sq)


def _operand_arrays(ops, q, k, v, bias, dtype):
    """The arrays behind ``_Operands.in_specs``."""
    if ops.rope_dim:
        return (*q, *k)
    return (q, k, v, *[_bias_row(bias, dtype)] * 3)


def _kernel_of(ops, kernel):
    """``kernel`` as the operands' layout calls it: in the LATENT layout q
    and k reach it as pairs of refs (``q_nope``, ``q_r``) and (``kv``,
    ``k_r``), v as the ``kv`` ref again, and there is no bias."""
    if not ops.rope_dim:
        return kernel

    def over_latent(seed_ref, qn_ref, qr_ref, kv_ref, kr_ref, *rest, **static):
        return kernel(
            seed_ref, (qn_ref, qr_ref), (kv_ref, kr_ref), kv_ref, None, None,
            None, *rest, **static)

    return over_latent


def _backward_calls(
    ops, q, k, v, bias, kv_mask, seed, do, lse, delta, sq, sk, causal,
    sm_scale, dropout_rate, block_q, block_k, block_diffusion=0, window=0,
):
    """(dq, dk, dv) in the operands' layout, from the backward that
    ``backward_plan`` chooses: the fused kernel, which runs as
    ``flash_bwd_dkv`` (its walk, now also putting out dq), or the pair
    ``flash_bwd_dq`` and ``flash_bwd_dkv``. ``lse``/``delta``:
    ``[B*H, Sq]`` float32. LATENT (the fused kernel only): ``(dq_nope,
    dq_r, dkv, dk_r a head)``."""
    dtype = _parts(q)[0].dtype
    use_mask, use_bias = kv_mask is not None, bias is not None
    common = _static(
        ops, sq, sk, block_q, block_k, causal, sm_scale, dropout_rate,
        use_mask, use_bias, block_diffusion, window,
    )
    nq, nk = common["nq"], common["nk"]
    interpret = not device.on_tpu()
    hb, lanes = ops.heads_a_block, ops.block_lanes
    plan = backward_plan(
        sq, sk, block_q, block_k, causal, lanes, jnp.dtype(dtype).itemsize,
        block_diffusion=block_diffusion, window=window,
    )

    def call(kernel, name, key_major, sub, out_specs, out_shape, scratch,
             **params):
        """One backward kernel over the operands they all read; lse and
        delta enter as one lane-dense row a query sub-tile."""
        sub_q, sub_k = sub
        rows = (ops.batch * ops.heads, sq // sub_q, 1, sub_q)
        # the inner axis's operands: queries of the key-major walk, keys
        # of the query-major one
        inner, steps = _needed_blocks(key_major, *_grid_form(common))
        walk = _walk_plan(key_major, sub_q, sub_k, *_grid_form(common))
        qs, ks = (inner, None) if key_major else (None, inner)
        return pl.pallas_call(
            functools.partial(
                _kernel_of(ops, kernel), sub_q=sub_q, sub_k=sub_k, **common),
            grid=(ops.groups, nk if key_major else nq, steps),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                *ops.in_specs(block_q, block_k, key_major, qs, ks, use_bias),
                ops.kvm_spec(use_mask, block_k, key_major, needed=ks),
                ops.spec("q", block_q, key_major, needed=qs, v=True),
                ops.row_spec(block_q, sub_q, key_major, needed=qs),
                ops.row_spec(block_q, sub_q, key_major, needed=qs),
            ],
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret, name=name, **params,
        )(_scalars(seed, walk), *_operand_arrays(ops, q, k, v, bias, dtype),
          _kvm_column(kv_mask), do, lse.reshape(rows), delta.reshape(rows))

    dkv_specs, dkv_shapes = ops.dkv_results(block_k, sk, dtype)
    dkv_scratch = [
        pltpu.VMEM((hb, block_k, ops.head_dim), jnp.float32),
        pltpu.VMEM((hb, block_k, ops.v_width), jnp.float32),
    ]
    sub_q, sub_k = sub = plan["sub_q"], plan["sub_k"]
    if plan["backward"] == "fused":
        dq_specs, dq_shapes = ops.dq_results(sq, dtype)
        return call(
            functools.partial(_bwd_dkv_kernel, fused=True), "flash_bwd_dkv",
            True, sub,
            [*dq_specs, *dkv_specs],
            [*dq_shapes, *dkv_shapes],
            [
                pltpu.VMEM((sq // sub_q, lanes, sub_q), jnp.float32),
                *dkv_scratch,
                _transposed_scratch(
                    block_k // sub_k, ops.kt_lanes, sub_k, dtype, interpret
                ),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=PAIR_VMEM_BYTES + plan["dq_vmem_bytes"]
            ),
        )

    dk, dv = call(
        functools.partial(_bwd_dkv_kernel, fused=False), "flash_bwd_dkv", True,
        sub, dkv_specs, dkv_shapes, dkv_scratch,
    )
    sub_q, sub_k = sub = pick_subtiles(block_q, block_k, nq, nk, key_major=False)
    dq = call(
        _bwd_dq_kernel, "flash_bwd_dq", False, sub, ops.spec("q", block_q),
        ops.result(sq, dtype),
        [
            pltpu.VMEM((block_q // sub_q, lanes, sub_q), jnp.float32),
            _transposed_scratch(block_k // sub_k, lanes, sub_k, dtype, interpret),
        ],
    )
    return dq, dk, dv


def _no_gradient(kv_mask, seed):
    """kv_mask is padding metadata (int), seed is RNG state."""
    return None if kv_mask is None else jnp.zeros_like(kv_mask), jnp.zeros_like(seed)


def _name_residuals(out, lse):
    """checkpoint_name tags let remat policies KEEP these residuals: under a
    plain dots-saveable policy the pallas outputs are not dot_generals, so
    per-layer remat would re-run the whole forward kernel in backward just
    to regenerate them (policy "...+flash_out+flash_lse" in
    ops/transformer.py saves them for a few MB per layer: lse is one
    float32 a query row, [B*H, Sq])."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")


# ---- split operands: q, k [B, H, S, D], v [B, H, S, D or Dv] ---------------
def _split_operands(q, v):
    """The operands of ``q`` [B, H, S, D] and ``v`` [B, H, S, Dv]: q and k
    share the D lanes the scores contract over; v, the context, dO and dv
    have Dv, which is D nearly everywhere (a latent mixer's q and k carry
    rotary lanes that its v does not: 192 and 128)."""
    b, h, _, d = q.shape
    return _Operands(b, h, d, packed=False, v_dim=v.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(
    q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k,
    block_diffusion=0, window=0,
):
    return _flash_fwd(
        q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q,
        block_k, block_diffusion, window,
    )[0]


def _flash_fwd(
    q, k, v, kv_mask, seed, causal, sm_scale, dropout_rate, block_q, block_k,
    block_diffusion=0, window=0,
):
    b, h, sq, d = q.shape
    out, lse = _forward_call(
        _split_operands(q, v),
        _reshape_bh(q), _reshape_bh(k), _reshape_bh(v), None, kv_mask, seed,
        sq, k.shape[2], causal, sm_scale, dropout_rate, block_q, block_k,
        block_diffusion, window,
    )
    out, lse = _name_residuals(out.reshape(b, h, sq, v.shape[-1]), lse)
    return out, (q, k, v, kv_mask, seed, out, lse)


def _flash_bwd(
    causal, sm_scale, dropout_rate, block_q, block_k, block_diffusion, window,
    residuals, g,
):
    q, k, v, kv_mask, seed, out, lse = residuals
    b, h, sq, d = q.shape
    sk = k.shape[2]
    # delta_i = rowsum(dO * O): cheap elementwise reduction, leave to XLA
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(b * h, sq)
    dq, dk, dv = _backward_calls(
        _split_operands(q, v),
        _reshape_bh(q), _reshape_bh(k), _reshape_bh(v), None, kv_mask, seed,
        _reshape_bh(g), lse, delta, sq, sk, causal, sm_scale, dropout_rate,
        block_q, block_k, block_diffusion, window,
    )
    return (
        dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
        *_no_gradient(kv_mask, seed),
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---- packed operands: the qkv projection's result [B, S, 3*H*D] -----------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_packed(
    qkv, bias, kv_mask, seed, heads, causal, sm_scale, dropout_rate, block_q,
    block_k,
):
    return _flash_packed_fwd(
        qkv, bias, kv_mask, seed, heads, causal, sm_scale, dropout_rate,
        block_q, block_k,
    )[0]


def _packed_operands(qkv, heads):
    b, s, width = qkv.shape
    return _Operands(b, heads, width // (3 * heads), packed=True)


def _flash_packed_fwd(
    qkv, bias, kv_mask, seed, heads, causal, sm_scale, dropout_rate, block_q,
    block_k,
):
    s = qkv.shape[1]
    out, lse = _forward_call(
        _packed_operands(qkv, heads), qkv, qkv, qkv, bias, kv_mask, seed, s, s,
        causal, sm_scale, dropout_rate, block_q, block_k,
    )
    out, lse = _name_residuals(out, lse)
    return out, (qkv, bias, kv_mask, seed, out, lse)


def _flash_packed_bwd(
    heads, causal, sm_scale, dropout_rate, block_q, block_k, residuals, g
):
    qkv, bias, kv_mask, seed, out, lse = residuals
    ops = _packed_operands(qkv, heads)
    s = qkv.shape[1]
    delta = _delta_by_head(g, out, heads)
    dq, dk, dv = _backward_calls(
        ops, qkv, qkv, qkv, bias, kv_mask, seed, g, lse, delta, s, s, causal,
        sm_scale, dropout_rate, block_q, block_k,
    )
    # With dq, dk and dv the three results of ONE kernel XLA writes their
    # concatenation out in three passes of its own (0.12 ms a layer at the
    # GPT-2 cells' shape, PERF.md PR 33); behind the barrier dq is another
    # operation's result, as it was when it had its own kernel, and the
    # concatenation folds into the projection's backward products again.
    dqkv = jnp.concatenate(
        [jax.lax.optimization_barrier(dq), dk, dv], axis=-1
    )
    dbias = None
    if bias is not None:
        dbias = jnp.sum(dqkv.astype(jnp.float32), axis=(0, 1)).astype(bias.dtype)
    return dqkv, dbias, *_no_gradient(kv_mask, seed)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def _delta_by_head(g, out, heads):
    """delta_i = rowsum(dO * O) over each head's D lanes of ``g`` and ``out``
    [B, S, H*D], [B*H, S] float32, as a product with a 0/1 matrix: one pass
    over dO and O as they lie, [B, H, S] out. (A reshape to [.., H, D] would
    lay both out anew for a 64-wide minor dimension.) A product of two bf16
    numbers is exact in float32 and splits into two bf16 terms, so
    ``HIGHEST`` sums exactly what the split path's float32 reduction sums."""
    b, s, width = g.shape
    d = width // heads
    own = jnp.arange(heads)[:, None] == jnp.arange(heads * d)[None, :] // d
    return jnp.einsum(
        "hk,bsk->bhs", own.astype(jnp.float32),
        g.astype(jnp.float32) * out.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(b * heads, s)


# ---- latent operands: q_nope, q_r, kv [B, S, H*.] and the shared k_r ------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_latent(q_nope, q_r, kv, k_r, heads, sm_scale, block_q, block_k):
    return _flash_latent_fwd(
        q_nope, q_r, kv, k_r, heads, sm_scale, block_q, block_k)[0]


def _latent_operands(q_nope, kv, k_r, heads):
    b, _, width = q_nope.shape
    nope, rope = width // heads, k_r.shape[-1]
    return _Operands(
        b, heads, nope + rope, packed=False,
        v_dim=kv.shape[-1] // heads - nope, rope_dim=rope)


def _flash_latent_fwd(q_nope, q_r, kv, k_r, heads, sm_scale, block_q, block_k):
    s, seed = q_nope.shape[1], jnp.asarray(0, jnp.int32)
    out, lse = _forward_call(
        _latent_operands(q_nope, kv, k_r, heads), (q_nope, q_r), (kv, k_r),
        None, None, None, seed, s, s, True, sm_scale, 0.0, block_q, block_k,
    )
    out, lse = _name_residuals(out, lse)
    return out, (q_nope, q_r, kv, k_r, out, lse)


def _flash_latent_bwd(heads, sm_scale, block_q, block_k, residuals, g):
    q_nope, q_r, kv, k_r, out, lse = residuals
    b, s, rope = k_r.shape
    dq_nope, dq_r, dkv, dk_r = _backward_calls(
        _latent_operands(q_nope, kv, k_r, heads), (q_nope, q_r), (kv, k_r),
        None, None, None, jnp.asarray(0, jnp.int32), g, lse,
        _delta_by_head(g, out, heads), s, s, True, sm_scale, 0.0, block_q,
        block_k,
    )
    # the rotated key part is every head's: its gradient is the heads' sum
    # (a program holds one pair of heads and the grid runs the pairs
    # outermost: no step sees all of a key block's). As a product with a 0/1
    # matrix, summed in float32: one pass over [B, S, H*rope] as it lies (a
    # reshape to [.., H, rope] would lay it out anew for a 64-wide minor
    # dimension, as in ``_delta_by_head``)
    own = jnp.arange(heads * rope)[:, None] % rope == jnp.arange(rope)[None, :]
    dk_r = jnp.einsum(
        "bsk,kj->bsj", dk_r, own.astype(dk_r.dtype),
        preferred_element_type=jnp.float32,
    ).astype(k_r.dtype)
    return dq_nope, dq_r, dkv, dk_r


_flash_latent.defvjp(_flash_latent_fwd, _flash_latent_bwd)


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


def block_diffusion_refusal(sq, sk, causal, block_diffusion):
    """Why the kernels cannot apply the block-diffusion mask to a call, or
    None where they can (or none is asked for)."""
    if not block_diffusion:
        return None
    if causal:
        return "block_diffusion is a mask form of its own, not causal's"
    if sq != sk or sq % 2:
        return f"a [noisy ; clean] row has 2 L positions each way, not {sq} x {sk}"
    if block_diffusion & (block_diffusion - 1) or (sq // 2) % block_diffusion:
        return (
            f"block length {block_diffusion} must be a power of two that "
            f"divides L = {sq // 2}"
        )
    return None


def window_refusal(sq, sk, causal, window, block_diffusion=0):
    """Why the kernels cannot apply the band to a call, or None where they
    can (or none is asked for)."""
    if not window:
        return None
    if window < 0:
        return f"a window of {window} keys"
    if not causal or block_diffusion:
        return "a window is a band under causal's diagonal, no other form's"
    if sq != sk:
        return f"a band over self-attention's square, not {sq} x {sk}"
    return None


def _checked_window(who, sq, sk, causal, block_diffusion, window):
    """``window`` as the kernels take it (0 where it reaches past the row:
    plain causal), once neither structural form refuses the call."""
    why = block_diffusion_refusal(sq, sk, causal, block_diffusion) \
        or window_refusal(sq, sk, causal, window, block_diffusion)
    if why:
        raise ValueError(f"{who}: {why}")
    return 0 if window >= sk else window


def window_visited_share(seq, window):
    """Share of a ``seq x seq`` score square that lies in sub-tiles the banded
    kernels' forward walk visits at the blocks ``attention()`` picks (the
    backward's: ``flash_tiling``), for a layer's ``attn/...`` counters."""
    block_q, block_k = _pick_blocks(seq, seq, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    if not (block_q and block_k):
        return 1.0
    return _visited_share(
        seq, seq, block_k,
        *pick_subtiles(
            block_q, block_k, seq // block_q, seq // block_k, False,
            forward=True),
        True, window=window if window < seq else 0)


def _pick_blocks(sq, sk, block_q, block_k, block_diffusion=0):
    """The largest dividing blocks; under the block-diffusion mask those
    that divide a HALF of the row, so that a block lies in one half."""
    if block_diffusion:
        sq, sk = sq // 2, sk // 2
    return pick_block(sq, block_q), pick_block(sk, block_k)


def flash_attention(
    q, k, v, mask=None, kv_mask=None, causal=False, sm_scale=None,
    dropout_rate=0.0, dropout_seed=0,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K, block_diffusion=0,
    window=0,
):
    """Blockwise flash attention. q,k,v: [B, H, S, D].

    Masking: pass ``kv_mask`` [B, Sk] (nonzero = attend) or a padding-style
    additive ``mask`` (converted). Query-dependent additive biases are not
    supported here — use ``attention()`` / ``mha_reference`` for those.
    ``block_diffusion=B``: the rows are ``[noisy ; clean]`` halves of one
    sequence in blocks of ``B`` and the kernels apply that mask
    (``block_diffusion_mask``) from the grid position, skipping what it
    empties. ``window=W`` beside ``causal``: a query sees its last W keys,
    itself among them (``band_mask``); the walks and the grids skip what lies
    outside the band, and ``W >= S`` is plain ``causal``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    sq, sk = q.shape[2], k.shape[2]
    window = _checked_window(
        "flash_attention", sq, sk, causal, block_diffusion, window)
    # shrink to the largest dividing block so e.g. seq 768 runs with
    # 256-blocks instead of failing the divisibility check on the default
    block_q, block_k = _pick_blocks(sq, sk, block_q, block_k, block_diffusion)
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
        int(block_q), int(block_k), int(block_diffusion), int(window),
    )


def flash_attention_packed(
    qkv, heads, bias=None, kv_mask=None, causal=False, sm_scale=None,
    dropout_rate=0.0, dropout_seed=0,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
):
    """``flash_attention`` over the fused qkv projection's own result.

    ``qkv``: [B, S, 3*H*D], q | k | v along the last axis, head by head in
    each; ``bias``: the projection's bias [3*H*D] where ``qkv`` is the bare
    product and still lacks it (the kernels add it as they load). Returns
    the context [B, S, H*D]. The same kernels, tiling, arithmetic and
    dropout bits as ``flash_attention`` on the split ``[B, H, S, D]``
    operands; ``packed_refusal`` says which shapes it takes.
    """
    b, s, width = qkv.shape
    d = width // (3 * heads)
    why = packed_refusal(heads, d, width)
    if why:
        raise ValueError(f"flash_attention_packed: {why}")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    block_q, block_k = pick_block(s, block_q), pick_block(s, block_k)
    if block_q == 0 or block_k == 0:
        raise ValueError(f"flash_attention_packed found no block dividing s={s}")
    return _flash_packed(
        qkv, bias, kv_mask, jnp.asarray(dropout_seed, jnp.int32), int(heads),
        causal, float(sm_scale), float(dropout_rate), int(block_q), int(block_k),
    )


def packed_refusal(heads, head_dim, width=None):
    """Why the kernels cannot read heads out of a ``[B, S, 3*H*D]``
    projection result, or None where they can: a 128-lane block must hold
    whole heads."""
    if width is not None and width != 3 * heads * head_dim:
        return f"width {width} is not 3 x {heads} heads x {head_dim}"
    if head_dim not in (64, LANES):
        return f"head_dim {head_dim} is neither 64 nor {LANES}"
    if heads % (LANES // head_dim):
        return (
            f"{heads} heads of {head_dim} do not pair into {LANES}-lane blocks"
        )
    return None


def flash_attention_latent(
    q_nope, q_r, kv, k_r, heads, sm_scale=None,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
):
    """Causal ``flash_attention`` over a latent mixer's operands where its
    projections wrote them (the LATENT layout above ``_Operands``).

    ``q_nope`` [B, S, H*nope] and the rotated ``q_r`` [B, S, H*rope]: a head's
    unrotated and rotated query lanes; ``kv`` [B, S, H*(nope + v)]: a head's
    unrotated key lanes then its value; ``k_r`` [B, S, rope]: the ONE rotated
    key part every head shares. Returns the context [B, S, H*v]. The scores
    are ``(q_nope k_nope^T + q_r k_r^T) * sm_scale`` (None: ``1 / sqrt(nope
    + rope)``), summed in float32; the same kernels, tiling and arithmetic
    as ``flash_attention`` on the assembled ``[B, H, S, .]`` operands;
    ``latent_refusal`` says which shapes it takes."""
    b, s, rope = k_r.shape
    nope = q_nope.shape[-1] // heads
    why = _latent_shape_refusal(
        s, heads, nope, rope, kv.shape[-1] // heads - nope)
    if why:
        raise ValueError(f"flash_attention_latent: {why}")
    if sm_scale is None:
        sm_scale = 1.0 / ((nope + rope) ** 0.5)
    return _flash_latent(
        q_nope, q_r, kv, k_r, int(heads), float(sm_scale),
        pick_block(s, block_q), pick_block(s, block_k))


def _latent_shape_refusal(seq, heads, nope, rope, v_dim):
    """``latent_refusal``'s part that the shapes alone decide."""
    if nope % LANES or nope != v_dim:
        return (
            f"{nope} unrotated lanes on {v_dim} of v: not equal whole "
            f"{LANES}-lane blocks")
    if rope != LANES // 2:
        return f"{rope} rotated lanes, not {LANES // 2}: two heads a block"
    if heads % 2:
        return f"{heads} heads do not pair into {LANES}-lane blocks"
    block_q = pick_block(seq, DEFAULT_BLOCK_Q)
    block_k = pick_block(seq, DEFAULT_BLOCK_K)
    if not (block_q and block_k):
        return f"no block divides seq={seq}"
    return backward_plan(
        seq, seq, block_q, block_k, True, lanes=2 * (nope + rope))["reason"]


def latent_refusal(batch, seq, heads, nope, rope, v_dim, mesh=None):
    """Why the kernels cannot read a latent mixer's heads out of its
    projections' own results, or None where they can. A head's unrotated
    key lanes and its value must be whole 128-lane blocks of one product
    (and equally wide: the context's blocks are q_nope's), two heads'
    rotated lanes must fill one, and dq's two parts must fit the fused
    backward's VMEM (the pair of kernels is not built for this layout). One
    device or a mesh of one: a kernel is not partitioned over devices, and
    no ``shard_map`` is built for this layout."""
    why, *_ = _flash_gate(seq, seq, None, 0.0, None, True)
    if why:
        return f"no flash kernel ({why})"
    route = _flash_route(mesh, batch, heads)
    if route == "sharded":
        return "a mesh of several devices: the layout is one device's"
    if route != "local" and FLASH_MODE != "always":
        return f"no flash kernel ({route})"
    return _latent_shape_refusal(seq, heads, nope, rope, v_dim)


def latent_layout(batch, seq, heads, nope, rope, v_dim, mesh=None):
    """``attention_layout`` for a latent mixer, chosen from what the caller
    sees and nothing else: ``("latent", 2, None)`` (the kernels read q_nope,
    q_r, kv and k_r as the projections wrote them: ``flash_attention_latent``)
    or ``("split", 1, reason)``: today's ``[B, H, S, .]`` operands through
    ``attention(why_split=reason)``, which logs the line then."""
    why = latent_refusal(batch, seq, heads, nope, rope, v_dim, mesh)
    if why:
        return "split", 1, why
    _log_layout(batch, seq, heads, nope + rope, "latent", LANES // rope, None)
    return "latent", LANES // rope, None


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
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K, block_diffusion=0,
    window=0,
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
    block_q, block_k = _pick_blocks(
        q.shape[2], k.shape[2], block_q, block_k, block_diffusion
    )
    if block_q == 0 or block_k == 0:
        raise ValueError(
            f"no block size divides sq={q.shape[2]}/sk={k.shape[2]}"
        )
    qspec = P(DATA_AXIS, MODEL_AXIS, None, None)
    use_mask = kv_mask is not None
    seed = jnp.asarray(dropout_seed, jnp.int32)

    def local(q, k, v, kvm, seed):
        if dropout_rate > 0.0:
            seed = _shard_seed(seed)
        return _flash(
            q, k, v, kvm if use_mask else None, seed, causal,
            float(sm_scale), float(dropout_rate), int(block_q), int(block_k),
            int(block_diffusion), int(window),
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, P(DATA_AXIS, None) if use_mask else P(), P()),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v, kv_mask if use_mask else jnp.zeros((), jnp.int32), seed)


def _flash_route(mesh, batch, heads):
    """How flash can run for ``batch`` rows of ``heads`` heads:
    ``"sharded"`` (per-shard over the mesh's data/model axes via
    ``shard_map``), ``"local"`` (the operands live on one device), or a
    reason string when it cannot (the caller has already validated mask and
    block tiling via ``_flash_gate``). A bare ``pallas_call`` inside a
    GSPMD-jitted program over several devices is not partitioned — XLA
    would all-gather its operands — so anything else goes to the XLA
    path."""
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
    if batch % dp or heads % mp:
        return (
            f"batch {batch} / heads {heads} do not divide the mesh's "
            f"data={dp} / model={mp} axes"
        )
    return "sharded"


def _flash_gate(
    sq, sk, mask, dropout_rate, dropout_rng, use_flash, block_diffusion=0
):
    """Whether the kernels can serve this call at all, before any mesh is
    looked at: ``(why_not or None, kv_mask, block_q, block_k,
    dropout_rate)``."""
    bq, bk = _pick_blocks(
        sq, sk, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, block_diffusion
    )
    if dropout_rng is None:
        dropout_rate = 0.0  # matches the XLA path's no-rng => no-dropout
    kv_mask = additive_mask_to_kv_valid(mask)
    why_not = None
    if not use_flash:
        why_not = "use_flash is off"
    elif FLASH_MODE == "never":
        why_not = 'FLASH_MODE is "never"'
    elif bq == 0 or bk == 0:
        why_not = f"no block divides sq={sq}/sk={sk}"
    elif mask is not None and kv_mask is None:
        why_not = "the mask depends on the query position"
    elif dropout_rate > 0.0 and not device.on_tpu():
        # interpret-mode PRNG is not available off-TPU
        why_not = "dropout needs the chip's generator"
    elif FLASH_MODE == "auto" and max(sq, sk) < FLASH_MIN_SEQ:
        why_not = f"seq {max(sq, sk)} is under FLASH_MIN_SEQ {FLASH_MIN_SEQ}"
    return why_not, kv_mask, bq, bk, dropout_rate


def attention_layout(batch, seq, heads, head_dim, flash, mesh=None):
    """Which operand layout the attention sublayer's kernels get, chosen
    from what the caller sees and nothing else: ``("packed", heads a
    block, None)`` — the kernels read q, k, v out of the qkv projection's
    ``[B, S, 3*H*D]`` result and write ``[B, S, H*D]`` — or ``("split", 1,
    reason)``: today's ``[B, H, S, D]`` operands (or no kernel at all).
    ``flash``: None where the flash gate passed, else why it did not."""
    from ..config.constants import MODEL_AXIS

    why = flash and f"no flash kernel ({flash})"
    if not why:
        route = _flash_route(mesh, batch, heads)
        if route not in ("local", "sharded") and FLASH_MODE != "always":
            why = f"no flash kernel ({route})"
        elif route == "sharded" and dict(mesh.shape)[MODEL_AXIS] > 1:
            why = (
                "the model axis shards the heads, and a shard of the "
                "projection is not q | k | v"
            )
    why = why or packed_refusal(heads, head_dim)
    layout = (
        ("split", 1, why) if why else ("packed", LANES // head_dim, None)
    )
    _log_layout(batch, seq, heads, head_dim, *layout)
    return layout


@functools.lru_cache(maxsize=None)
def _log_layout(batch, seq, heads, head_dim, layout, heads_a_block, reason):
    logger.debug(
        "attention_layout b=%d s=%d heads=%d d=%d layout=%s heads_a_block=%d%s",
        batch, seq, heads, head_dim, layout, heads_a_block,
        f" reason={reason!r}" if reason else "",
    )


def _shard_seed(seed):
    """Decorrelate in-kernel dropout streams across shards (the kernel
    seeds per LOCAL batch*head index)."""
    from ..config.constants import DATA_AXIS, MODEL_AXIS

    di = jax.lax.axis_index(DATA_AXIS).astype(jnp.int32)
    mi = jax.lax.axis_index(MODEL_AXIS).astype(jnp.int32)
    return seed + di * jnp.int32(7_368_787) + mi * jnp.int32(15_485_863)


def attention_packed(
    qkv, heads, bias=None, mask=None, causal=False, dropout_rate=0.0,
    dropout_rng=None, use_flash=True, mesh=None,
):
    """The attention sublayer between the fused qkv projection and the
    output projection: ``qkv`` [B, S, 3*H*D] (q | k | v, head by head) to
    the context [B, S, H*D]. ``bias``: the projection's bias where ``qkv``
    is the bare product (None where it is already in). Where
    ``attention_layout`` says ``packed`` the flash kernels take ``qkv`` as
    it is and add the bias as they load, on one device or per shard over
    the data axis; anything else gets the bias here, is split into
    ``[B, H, S, D]`` heads and goes through ``attention`` as before."""
    b, s, width = qkv.shape
    d = width // (3 * heads)
    why_not, kv_mask, bq, bk, rate = _flash_gate(
        s, s, mask, dropout_rate, dropout_rng, use_flash
    )
    layout, _, why_split = attention_layout(b, s, heads, d, why_not, mesh)
    if layout == "split":
        if bias is not None:
            qkv = qkv + bias
        q, k, v = (
            t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
            for t in jnp.split(qkv, 3, axis=-1)
        )
        ctx = _attention_split(
            q, k, v, mask, causal, None, dropout_rate, dropout_rng,
            use_flash, mesh, why_split,
        )
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * d)

    seed = jnp.asarray(0, jnp.int32)
    if rate > 0.0:
        seed = jax.random.randint(dropout_rng, (), 0, 2**31 - 1)
    use_mask, use_bias = kv_mask is not None, bias is not None

    def local(qkv, bias, kvm, seed, sharded=False):
        if sharded and rate > 0.0:
            seed = _shard_seed(seed)
        return _flash_packed(
            qkv, bias if use_bias else None, kvm if use_mask else None, seed,
            heads, causal, 1.0 / (d ** 0.5), float(rate), bq, bk,
        )

    if _flash_route(mesh, b, heads) != "sharded":
        return local(qkv, bias, kv_mask, seed)
    from jax.sharding import PartitionSpec as P

    from ..config.constants import DATA_AXIS

    rows, absent = P(DATA_AXIS, None, None), jnp.zeros((), jnp.int32)
    return jax.shard_map(
        functools.partial(local, sharded=True), mesh=mesh,
        in_specs=(rows, P(), P(DATA_AXIS, None) if use_mask else P(), P()),
        out_specs=rows, check_vma=False,
    )(qkv, bias if use_bias else absent, kv_mask if use_mask else absent, seed)


def attention(
    q, k, v, mask=None, causal=False, sm_scale=None, dropout_rate=0.0,
    dropout_rng=None, use_flash=True, mesh=None, block_diffusion=0, window=0,
    why_split=None,
):
    """Dispatcher: flash kernel when shapes tile cleanly and the mask is a
    padding mask; XLA reference otherwise (incl. learned additive biases,
    which need exact mask gradients). With ``mesh`` supplied and a
    data/model-parallel layout, flash runs per-shard via ``shard_map``;
    where several devices leave no way to run it (``_flash_route``), the
    O(S^2) path runs and, on a TPU, says why once. ``k``/``v`` may have
    fewer heads than ``q`` (grouped-query attention). A caller that holds
    the fused qkv projection's result takes ``attention_packed``.
    ``block_diffusion=B``: the block-diffusion mask over ``[noisy ; clean]``
    rows, inside the kernels; where no kernel runs, ``block_diffusion_mask``
    as a dense additive mask on the XLA path. ``window=W`` beside ``causal``:
    the band of the last W keys, inside the kernels or as two comparisons of
    ``mha_reference``. ``why_split``: why a caller that has another layout
    (``latent_layout``) hands over split operands, for the
    ``attention_layout`` line."""
    why = why_split or "q, k and v arrive as separate [B, H, S, D] arrays"
    refusal = packed_refusal(q.shape[1], q.shape[-1])
    return _attention_split(
        q, k, v, mask, causal, sm_scale, dropout_rate, dropout_rng,
        use_flash, mesh, f"{why}; {refusal}" if refusal else why,
        block_diffusion, window,
    )


def _attention_split(
    q, k, v, mask, causal, sm_scale, dropout_rate, dropout_rng, use_flash,
    mesh, why_split, block_diffusion=0, window=0,
):
    if k.shape[1] != q.shape[1]:
        # grouped-query heads: each kv head serves q_heads / kv_heads query
        # heads. Repeated here, before the kernel; a grouped kernel layout
        # that reads each kv head once is not built.
        k, v = (jnp.repeat(t, q.shape[1] // k.shape[1], axis=1)
                for t in (k, v))
    b, heads, sq, d = q.shape
    sk = k.shape[2]
    window = _checked_window(
        "attention", sq, sk, causal, block_diffusion, window)
    why_not, kv_mask, bq, bk, dropout_rate = _flash_gate(
        sq, sk, mask, dropout_rate, dropout_rng, use_flash, block_diffusion
    )
    _log_layout(
        b, sq, heads, d, "split", 1,
        f"no flash kernel ({why_not})" if why_not else why_split,
    )
    if not why_not:
        route = _flash_route(mesh, b, heads)
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
                block_diffusion=block_diffusion, window=window,
            )
        if route == "local":
            return flash_attention(
                q, k, v, kv_mask=kv_mask, causal=causal, sm_scale=sm_scale,
                dropout_rate=dropout_rate, dropout_seed=seed,
                block_q=bq, block_k=bk, block_diffusion=block_diffusion,
                window=window,
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
    if block_diffusion:
        dense = block_diffusion_mask(sq, block_diffusion)
        mask = dense if mask is None else mask + dense
    return mha_reference(
        q, k, v, mask=mask, causal=causal, sm_scale=sm_scale,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng, window=window,
    )
