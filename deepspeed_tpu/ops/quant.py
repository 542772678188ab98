"""Blockwise-quantized optimizer-state storage.

TPU-native replacement for the memory relief the reference family gets
from ZeRO-Offload (host-resident fp32 optimizer state, a later-DeepSpeed
feature; this v0.2.0 reference motivates it as "train models that don't
fit", docs/_posts/2020-05-19-zero-stage2.md).  Streaming the state
between host and device every step puts the host link on the critical
path (its cost on a v5e host: not measured), so the state stays in HBM
but SHRINKS instead: Adam moments stored as int8 with
per-block absmax scales (the 8-bit-optimizer formulation of Dettmers et
al., "8-bit Optimizers via Block-wise Quantization", 2022 — shown to match
fp32 Adam) or as bf16.  fp32 math happens transiently inside the fused
update; only the compressed representation persists between steps.

Layout per quantized leaf: ``{"q": int8[nblocks*BLOCK], "scale":
f32[nblocks]}`` over the flattened parameter (padding rows are zero and
decode to zero).  Everything here is elementwise + tiny reductions — XLA
fuses the decode -> update -> encode chain into the optimizer kernel, so
no fp32 copy of the state ever lands in HBM.
"""

import math

import jax
import jax.numpy as jnp

BLOCK = 2048  # absmax granularity (the 8-bit-optimizer default)


def quantized_zeros_like(p, pad_blocks=1):
    """Zeros quantized leaf for ``p``. ``pad_blocks`` rounds the block
    count up to a multiple (ZeRO: pad to the dp size so the flat ``q`` and
    ``scale`` arrays split evenly across the data axis with shard
    boundaries on block boundaries — the padded tail decodes to zero and
    never receives updates)."""
    n = p.size
    nb = max(1, math.ceil(n / BLOCK))
    nb = -(-nb // pad_blocks) * pad_blocks
    return {
        "q": jnp.zeros((nb * BLOCK,), jnp.int8),
        "scale": jnp.zeros((nb,), jnp.float32),
    }


def is_quantized(state_leaf):
    return (
        isinstance(state_leaf, dict)
        and set(state_leaf.keys()) == {"q", "scale"}
    )


def dequantize(state_leaf, shape):
    n = math.prod(shape) if shape else 1
    q = state_leaf["q"].astype(jnp.float32).reshape(-1, BLOCK)
    x = q * state_leaf["scale"][:, None]
    return x.reshape(-1)[:n].reshape(shape)


def quantize(x, nb=None):
    """Symmetric blockwise int8: scale = absmax/127 per BLOCK elements.
    ``nb`` pins the output block count (>= the minimum) so re-encoding a
    padded leaf keeps its (ZeRO-aligned) storage shape."""
    n = x.size
    if nb is None:
        nb = max(1, math.ceil(n / BLOCK))
    flat = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, nb * BLOCK - n))
    blocks = flat.reshape(nb, BLOCK)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(blocks * inv[:, None]), -127, 127).astype(jnp.int8)
    return {"q": q.reshape(-1), "scale": scale}


def moments_zeros_like(params, state_dtype: str, role: str = "mu", pad_blocks=1):
    """A zeros moment tree in the requested storage format.

    ``state_dtype="int8"`` applies blockwise int8 only to the FIRST moment
    (``role="mu"``); the second moment stores as bf16 instead. The second
    moment sits in the update's denominator (1/(sqrt(v)+eps)): linear int8
    decodes small-v elements of a large-absmax block to exactly 0, turning
    the update into m/eps and diverging. bf16 keeps fp32's exponent, so
    relative error stays 2^-8 across v's wide dynamic range.

    ``pad_blocks``: block-count alignment for quantized leaves (ZeRO dp
    sharding; see quantized_zeros_like).
    """
    if state_dtype == "fp32":
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
    if state_dtype == "bf16" or (state_dtype == "int8" and role == "nu"):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.bfloat16), params
        )
    if state_dtype == "int8":
        return jax.tree_util.tree_map(
            lambda p: quantized_zeros_like(p, pad_blocks=pad_blocks), params
        )
    raise ValueError(f"unknown optimizer state_dtype {state_dtype!r}")


def decode_moment(state_leaf, shape):
    """Storage -> fp32 working value (free for fp32; a cast for bf16;
    blockwise decode for int8)."""
    if is_quantized(state_leaf):
        return dequantize(state_leaf, shape)
    return state_leaf.astype(jnp.float32)


def encode_moment(value_f32, like_leaf):
    """fp32 working value -> the same storage format as ``like_leaf``
    (including its padded block count, so ZeRO-aligned leaves re-encode
    into the same sharded shape)."""
    if is_quantized(like_leaf):
        return quantize(value_f32, nb=like_leaf["scale"].shape[0])
    return value_f32.astype(like_leaf.dtype)


def moment_is_leaf(x):
    """is_leaf predicate treating a quantized {'q','scale'} dict as one
    logical leaf (so tree_maps align moment trees with param trees)."""
    return is_quantized(x)


# --------------------------------------------------------------------------
# Kahan-style master compensation: bf16 params + int8 rounding-error carry.
#
# Storing fp32 master params costs 4 bytes/param AND (with bf16 compute)
# forces a full bf16 cast copy of the tree to live across backward — ~9.3
# bytes/param of HBM at GPT-2 1.5B.  Compensated masters instead keep the
# params IN bf16 (compute dtype == storage dtype, no cast copies) plus a
# 1-byte code for the rounding error the bf16 store dropped:
#
#   master ≈ bf16(p) + code * ulp(p) / 254,   code ∈ [-127, 127] int8
#
# Each update reconstructs the master, applies the fp32 update, re-rounds
# to bf16 and re-encodes the new error — classic compensated (Kahan)
# summation, quantized.  Per-step quantization residue is <= ulp/508 with
# random sign, a sqrt(N) walk that stays well under one bf16 ulp for any
# realistic run length, which is why bf16+Kahan training is known to match
# fp32-master training.

_ULP_FRAC = jnp.float32(2.0 ** -8)  # bf16 mantissa step relative to |x|
_CODE_MAX = 127.0


def _ulp_of(p_f32):
    # magnitude-relative ulp with a tiny floor so zero params still carry
    # a (vanishing) representable error range
    return jnp.maximum(jnp.abs(p_f32), jnp.float32(1e-30)) * _ULP_FRAC


def comp_zeros_like(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.int8), params
    )


def decode_master(p, comp_code):
    """bf16 param + int8 code -> fp32 master value."""
    p32 = p.astype(jnp.float32)
    return p32 + comp_code.astype(jnp.float32) * (_ulp_of(p32) / _CODE_MAX)


def encode_master(master_f32, p_dtype):
    """fp32 master -> (stored param, int8 error code).

    The rounding residue is computed against ``lax.reduce_precision`` —
    NOT an ``astype`` roundtrip, which XLA's excess-precision
    simplification folds away under jit (the residue would silently
    become 0 and compensation a no-op in every compiled training step).
    reduce_precision is defined as the rounding itself, so it survives.
    """
    if p_dtype == jnp.bfloat16 or jnp.dtype(p_dtype) == jnp.dtype("bfloat16"):
        p32 = jax.lax.reduce_precision(master_f32, 8, 7)  # bf16 grid
    else:
        p32 = jax.lax.reduce_precision(master_f32, 5, 10)  # fp16 grid
    p_new = p32.astype(p_dtype)  # exact: p32 already on the target grid
    err = master_f32 - p32
    code = jnp.clip(
        jnp.round(err / (_ulp_of(p32) / _CODE_MAX)), -_CODE_MAX, _CODE_MAX
    ).astype(jnp.int8)
    return p_new, code
