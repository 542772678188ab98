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

Layout per quantized leaf (PR 27): the storage follows the PARAMETER.
``{"q": int8[p.shape], "scale": f32[p.shape[:-2] + (nruns, rows)]}`` for a
leaf ``p[..., rows, width]``: one scale per RUN of ``run_length(width)``
consecutive elements of the minor axis (never more than BLOCK elements,
so no scale is coarser than the flat 2,048-element blocks this replaced),
``scale[..., j, r]`` being that of run ``j`` of row ``r``.  The runs sit
on the second-minor axis so that the rows lie on the chip's 128 lanes: a
``[rows, nruns]`` array would be padded 32 to 128 times by the (8, 128)
tiling.  Nothing is flattened or padded, so ``q`` shards like the
parameter and no float32 array of a leaf's size is laid out anew to decode
or encode it (ops/pallas.py:adam_leaf_update is the one-pass kernel; the
functions here are its plain XLA twin, and what checkpoints and the
benchmark decode with).  Leaves with no such run (fewer than two axes, a
width under MIN_RUN or with no divisor in range) keep a bf16 moment, which
is never less precise than int8 under an absmax scale.
"""

import jax
import jax.numpy as jnp

BLOCK = 2048  # the most elements one scale may cover (the 8-bit-optimizer default)
MIN_RUN = 128  # a shorter run would spend a float32 scale on too few int8 codes


def run_length(width):
    """Elements per scale along a minor axis of ``width``: the largest
    divisor of ``width`` that is at most BLOCK, a multiple of 128 (whole
    lane tiles) where ``width`` is one; None where no divisor reaches
    MIN_RUN."""
    step = 128 if width % 128 == 0 else 1
    for run in range(min(width, BLOCK) // step * step, MIN_RUN - 1, -step):
        if width % run == 0:
            return run
    return None


def quantized_run(shape):
    """The run length a leaf of ``shape`` is stored with, or None for one
    that keeps a bf16 moment."""
    return run_length(shape[-1]) if len(shape) >= 2 else None


def quantized_zeros_like(p):
    """Zeros quantized leaf for ``p`` (``quantized_run(p.shape)`` is set)."""
    nruns = p.shape[-1] // quantized_run(p.shape)
    return {
        "q": jnp.zeros(p.shape, jnp.int8),
        "scale": jnp.zeros(p.shape[:-2] + (nruns, p.shape[-2]), jnp.float32),
    }


def is_quantized(state_leaf):
    return (
        isinstance(state_leaf, dict)
        and set(state_leaf.keys()) == {"q", "scale"}
    )


def spec_shards(entry, axis_sizes):
    """Into how many shards one PartitionSpec entry (None, an axis name or
    a tuple of them) cuts its dimension; 0 where ``axis_sizes`` lacks an
    axis it names."""
    names = () if entry is None else entry if isinstance(entry, tuple) else (entry,)
    shards = 1
    for name in names:
        shards *= axis_sizes.get(name, 0)
    return shards


def scale_spec(spec, shape, axis_sizes):
    """The PartitionSpec of a quantized leaf's ``scale`` given its
    parameter's: the leading axes' entries as they are, the rows' entry on
    the (minor) rows axis, and the width's entry on the runs axis where
    the shards hold whole runs (else, or where ``axis_sizes`` lacks an
    axis's size, that axis is replicated and the leaf takes the plain
    update)."""
    from jax.sharding import PartitionSpec

    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    nruns = shape[-1] // quantized_run(shape)
    shards = spec_shards(entries[-1], axis_sizes)
    width_entry = entries[-1] if shards and nruns % shards == 0 else None
    return PartitionSpec(*entries[:-2], width_entry, entries[-2])


def _by_run(x, nruns):
    return x.reshape(x.shape[:-1] + (nruns, x.shape[-1] // nruns))


def dequantize(state_leaf):
    """int8 codes times their run's scale, float32, in the leaf's shape."""
    q, scale = state_leaf["q"], state_leaf["scale"]
    per_row = jnp.swapaxes(scale, -1, -2)[..., None]  # [..., rows, nruns, 1]
    x = _by_run(q.astype(jnp.float32), scale.shape[-2]) * per_row
    return x.reshape(q.shape)


def quantize(x):
    """Symmetric int8 per run of the minor axis: scale = absmax / 127."""
    nruns = x.shape[-1] // quantized_run(x.shape)
    runs = _by_run(x.astype(jnp.float32), nruns)
    scale = jnp.max(jnp.abs(runs), axis=-1) / 127.0  # [..., rows, nruns]
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = round_to_code(runs * inv[..., None])
    return {"q": q.reshape(x.shape), "scale": jnp.swapaxes(scale, -1, -2)}


def moments_zeros_like(params, state_dtype: str, role: str = "mu"):
    """A zeros moment tree in the requested storage format.

    ``state_dtype="int8"`` applies int8 only to the FIRST moment
    (``role="mu"``); the second moment stores as bf16 instead. The second
    moment sits in the update's denominator (1/(sqrt(v)+eps)): linear int8
    decodes small-v elements of a large-absmax run to exactly 0, turning
    the update into m/eps and diverging. bf16 keeps fp32's exponent, so
    relative error stays 2^-8 across v's wide dynamic range.
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
            lambda p: quantized_zeros_like(p)
            if quantized_run(p.shape)
            else jnp.zeros(p.shape, jnp.bfloat16),
            params,
        )
    raise ValueError(f"unknown optimizer state_dtype {state_dtype!r}")


def decode_moment(state_leaf, shape=None):
    """Storage -> fp32 working value (free for fp32; a cast for bf16;
    the per-run decode for int8). ``shape`` is the parameter's, which
    every format stores its moment in."""
    if is_quantized(state_leaf):
        return dequantize(state_leaf)
    return state_leaf.astype(jnp.float32)


def encode_moment(value_f32, like_leaf):
    """fp32 working value -> the same storage format as ``like_leaf``."""
    if is_quantized(like_leaf):
        return quantize(value_f32)
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

# plain Python numbers: a Pallas kernel traces these functions too, and may
# close over no array
_ULP_FRAC = 2.0 ** -8  # bf16 mantissa step relative to |x|
_CODE_MAX = 127.0


def _ulp_of(p_f32):
    # magnitude-relative ulp with a tiny floor so zero params still carry
    # a (vanishing) representable error range
    return jnp.maximum(jnp.abs(p_f32), 1e-30) * _ULP_FRAC


def round_to_code(x):
    """Nearest integer (ties to even) within [-127, 127], as int8. Written
    with ``lax`` primitives: the ``jnp`` spellings are jitted functions, and
    the update kernel, which traces this per chunk of lanes, pays for every
    nested call at each start."""
    nearest = jax.lax.round(x, jax.lax.RoundingMethod.TO_NEAREST_EVEN)
    return jax.lax.clamp(-_CODE_MAX, nearest, _CODE_MAX).astype(jnp.int8)


def comp_zeros_like(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.int8), params
    )


def decode_master(p, comp_code):
    """bf16 param + int8 code -> fp32 master value."""
    p32 = p.astype(jnp.float32)
    return p32 + comp_code.astype(jnp.float32) * (_ulp_of(p32) / _CODE_MAX)


def encode_master(master_f32, p_dtype, to_grid=None):
    """fp32 master -> (stored param, int8 error code).

    The rounding residue is computed against ``lax.reduce_precision`` —
    NOT an ``astype`` roundtrip, which XLA's excess-precision
    simplification folds away under jit (the residue would silently
    become 0 and compensation a no-op in every compiled training step).
    reduce_precision is defined as the rounding itself, so it survives.
    ``to_grid`` replaces it inside a Pallas kernel, where Mosaic has no
    reduce_precision and folds no cast away.
    """
    if to_grid is not None:
        p32 = to_grid(master_f32)
    elif jnp.dtype(p_dtype) == jnp.dtype("bfloat16"):
        p32 = jax.lax.reduce_precision(master_f32, 8, 7)  # bf16 grid
    else:
        p32 = jax.lax.reduce_precision(master_f32, 5, 10)  # fp16 grid
    p_new = p32.astype(p_dtype)  # exact: p32 already on the target grid
    err = master_f32 - p32
    code = round_to_code(err / (_ulp_of(p32) / _CODE_MAX))
    return p_new, code
