"""What lies between a q or k projection and the flash kernels, in one pass a
direction: the per-head RMS norm (where the mixer has one) and the half-split
rotary rotation, as the Pallas kernels ``qk_prep_fwd`` and ``qk_prep_bwd``.

A program takes a block of rows of ONE head out of the projection's own
``[B, S, W]`` result (a head's D lanes side by side, as the product wrote
them), does in VMEM and in float32 what ``rms_norm`` and ``apply_rotary``
(ops/transformer.py) do as two XLA functions, and rounds to the
activations' dtype once (the two functions round twice: after the norm and
after the rotation). The rotation is a lane roll by half the rotated lanes and
a signed sine: no half-lane array exists anywhere. Cosine and signed sine come
in as ``[S, 128 k]`` float32 tables made once a call; the grid runs heads
innermost, so a row block's tables are fetched once for all its heads.

Two result layouts, one kernel body:

- ``qk_prep`` writes ``[B, heads, S, D]``, what ``attention()`` takes: the
  out-spec does the head transpose. The backward kernel reads the cotangent
  in that layout and the same projection result again (remat recomputes the
  product anyway; no residual is added) and writes the projection's
  cotangent ``[B, S, heads * D]`` once, and a ``[1, D]`` float32 partial of
  the gain's gradient a row block.
- ``qk_prep_in_place`` rotates the first ``heads`` heads of a packed ``q | k
  | v`` product IN its buffer (``input_output_aliases``) and leaves the
  other lanes as they are: ``attention_packed`` gets the buffer the product
  wrote. A rotation's backward needs no residual: it is the rotation by the
  negated angle, in place on the cotangent.

``qk_prep_path`` chooses between the kernels and the XLA functions from the
shapes and the mesh, and logs the choice once a shape.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from ..utils.logging import logger
from .attention import LANES, _flash_route, pick_block

# Elements of a grid step's block of one head and of one walk inside it, from
# a sweep at the SDAR cell's q (32 heads of 128 over 2 x 16,384 rows, bf16;
# my chip run, PR 39; forward / backward ms, 0.66 / 0.98 at the chip's
# bandwidth): a walk's chain of dependent steps (load, square, lane sum,
# rsqrt, roll, round, store) is not overlapped with the next walk's, so short
# walks are latency-bound (blocks of 2,048 rows: walks of 128 rows 1.81 /
# 2.30, of 256 1.17 / 1.52, of 512 1.07 / 1.51, one walk 1.08 / 1.51), and a
# grid step costs ~0.35 us (walks of 512: blocks of 1,024 rows 1.27 / 1.64,
# of 4,096 0.97 / 1.40, of 8,192 0.91 / 1.35, whose double-buffered blocks
# and tables would fill VMEM).
BLOCK_ELEMENTS = 4096 * LANES
WALK_ELEMENTS = 512 * LANES


def qk_prep_path(batch, seq, heads, head_dim, rotary_lanes, mesh=None):
    """``("fused", None)`` where the kernels take a mixer's q and k, else
    ``("xla", reason)``: ``apply_rotary(rms_norm(..))`` as before. Chosen from
    what the caller sees and nothing else; logged once a shape."""
    reason = None
    if head_dim % LANES:
        reason = f"head_dim {head_dim} does not fill {LANES}-lane blocks"
    elif rotary_lanes % 2 or not 0 < rotary_lanes <= head_dim:
        reason = f"{rotary_lanes} rotary lanes of {head_dim}"
    elif not _row_blocks(seq, head_dim)[1]:
        reason = f"no block of rows divides seq={seq}"
    else:
        route = _flash_route(mesh, batch, heads)
        if route == "sharded":
            from ..config.constants import MODEL_AXIS

            reason = (
                "the model axis shards the projection's lanes"
                if dict(mesh.shape)[MODEL_AXIS] > 1
                else "a kernel is not partitioned over devices")
        elif route != "local":
            reason = route
    path = "xla" if reason else "fused"
    _log_path(batch, seq, heads, head_dim, rotary_lanes, path, reason)
    return path, reason


@functools.lru_cache(maxsize=None)
def _log_path(batch, seq, heads, head_dim, rotary_lanes, path, reason):
    logger.debug(
        "qk_prep b=%d s=%d heads=%d d=%d rotary_lanes=%d path=%s%s",
        batch, seq, heads, head_dim, rotary_lanes, path,
        f" reason={reason!r}" if reason else "")


def _row_blocks(seq, head_dim):
    """(rows a grid step, rows a walk inside it); 0 where none divides."""
    rows = pick_block(seq, max(BLOCK_ELEMENTS // head_dim, 8))
    return rows, rows and pick_block(rows, max(WALK_ELEMENTS // head_dim, 8))


def rotary_tables(angle, factor=1.0):
    """``angle`` [S, rotary lanes / 2] float32, each row's angles -> the two
    [S, 128 k] tables the kernels multiply by, over the whole 128-lane blocks
    that hold the rotated lanes: ``cos | cos | 1`` and ``-sin | sin | 0``,
    cosine and sine times ``factor`` (the lanes that pass through keep 1 and
    0; the backward's rotation by the negated angle reads the same tables)."""
    seq, half = angle.shape
    cos, sin, rest = jnp.cos(angle), jnp.sin(angle), -2 * half % LANES
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return (
        jnp.concatenate(
            [cos, cos, jnp.ones((seq, rest), jnp.float32)], axis=1),
        jnp.concatenate(
            [-sin, sin, jnp.zeros((seq, rest), jnp.float32)], axis=1),
    )


def _rotate(y, cos, sin, rotary_lanes, back=False):
    """The rotation of ``y`` [rows, 128 k] float32 by the tables' angles
    (``back``: by their negatives, which is the rotation's transpose). Lane i
    pairs with lane i +- rotary_lanes / 2: a roll of the lanes either way."""
    width, half = y.shape[1], rotary_lanes // 2
    partner = pltpu.roll(y, half, 1)
    if rotary_lanes != width:
        lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        partner = jnp.where(
            lane < half, pltpu.roll(y, width - half, 1), partner)
    return y * cos - partner * sin if back else y * cos + partner * sin


def _walk(rows, walk, body, carry=None):
    """``body(rows of one walk, carry)`` over a block's rows."""
    def step(i, carry):
        return body(pl.ds(pl.multiple_of(i * walk, walk), walk), carry)

    return jax.lax.fori_loop(0, rows // walk, step, carry)


def _fwd_kernel(*refs, rows, walk, rotary_lanes, eps, normed):
    x_ref, cos_ref, sin_ref = refs[:3]
    o_ref = refs[-1]
    span = cos_ref.shape[1]
    gain = refs[3][...] if normed else None

    def body(at, _):
        y = x_ref[at, :].astype(jnp.float32)
        if normed:
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=1, keepdims=True) + eps) * gain
        z = _rotate(y[:, :span], cos_ref[at, :], sin_ref[at, :], rotary_lanes)
        if span != y.shape[1]:
            z = jnp.concatenate([z, y[:, span:]], axis=1)
        o_ref[at, :] = z.astype(o_ref.dtype)

    _walk(rows, walk, body)


def _bwd_kernel(*refs, rows, walk, rotary_lanes, eps, normed):
    dz_ref, cos_ref, sin_ref = refs[:3]
    span = cos_ref.shape[1]
    if normed:
        x_ref, gain_ref, dx_ref, dgain_ref = refs[3:]
        gain = gain_ref[...]
    else:
        dx_ref, = refs[3:]

    def body(at, dgain):
        dz = dz_ref[at, :].astype(jnp.float32)
        dy = _rotate(
            dz[:, :span], cos_ref[at, :], sin_ref[at, :], rotary_lanes,
            back=True)
        if span != dz.shape[1]:
            dy = jnp.concatenate([dy, dz[:, span:]], axis=1)
        if normed:
            xs = x_ref[at, :].astype(jnp.float32)
            inv = jax.lax.rsqrt(
                jnp.mean(xs * xs, axis=1, keepdims=True) + eps)
            unit = xs * inv
            dgain = dgain + jnp.sum(dy * unit, axis=0, keepdims=True)
            dy = dy * gain
            dy = inv * (
                dy - unit * jnp.mean(dy * unit, axis=1, keepdims=True))
        dx_ref[at, :] = dy.astype(dx_ref.dtype)
        return dgain

    if not normed:
        _walk(rows, walk, body)
        return
    dgain = _walk(rows, walk, body, jnp.zeros(gain.shape, jnp.float32))

    # heads run innermost: a row block's partial sums over them in VMEM
    @pl.when(pl.program_id(2) == 0)
    def _():
        dgain_ref[...] = dgain

    @pl.when(pl.program_id(2) != 0)
    def _():
        dgain_ref[...] += dgain


def _call(kernel, name, grid, static, **kwargs):
    return pl.pallas_call(
        functools.partial(kernel, **static), grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=not device.on_tpu(), name=name, **kwargs)


def _specs(x, heads, head_dim, span):
    """The grid (batch, row block, head) and the BlockSpecs that every call
    shares: a head's rows in a ``[B, S, W]`` array (the projection's result,
    its cotangent), in ``[B, heads, S, D]``, and the row block's tables."""
    b, s, _ = x.shape
    rows, walk = _row_blocks(s, head_dim)
    spec = {
        "lanes": pl.BlockSpec(
            (None, rows, head_dim), lambda b, r, h: (b, r, h)),
        "heads": pl.BlockSpec(
            (None, None, rows, head_dim), lambda b, r, h: (b, h, r, 0)),
        "table": pl.BlockSpec((rows, span), lambda b, r, h: (r, 0)),
        "gain": pl.BlockSpec((1, head_dim), lambda b, r, h: (0, 0)),
        "dgain": pl.BlockSpec(
            (None, None, 1, head_dim), lambda b, r, h: (b, r, 0, 0)),
    }
    return (b, s // rows, heads), dict(rows=rows, walk=walk), spec


# ---- [B, S, W] -> [B, heads, S, D]: norm, gain, rotation --------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _prep(x, gain, cos, sin, head_dim, rotary_lanes, eps):
    return _prep_fwd(x, gain, cos, sin, head_dim, rotary_lanes, eps)[0]


def _prep_fwd(x, gain, cos, sin, head_dim, rotary_lanes, eps):
    heads = x.shape[2] // head_dim
    grid, static, spec = _specs(x, heads, head_dim, cos.shape[1])
    normed = gain is not None
    out = _call(
        _fwd_kernel, "qk_prep_fwd", grid,
        dict(static, rotary_lanes=rotary_lanes, eps=eps, normed=normed),
        in_specs=[spec["lanes"], spec["table"], spec["table"]]
        + [spec["gain"]] * normed,
        out_specs=spec["heads"],
        out_shape=jax.ShapeDtypeStruct(
            (x.shape[0], heads, x.shape[1], head_dim), x.dtype),
    )(x, cos, sin, *([gain] if normed else []))
    return out, (x, gain, cos, sin)


def _prep_bwd(head_dim, rotary_lanes, eps, residuals, dz):
    x, gain, cos, sin = residuals
    grid, static, spec = _specs(
        x, x.shape[2] // head_dim, head_dim, cos.shape[1])
    normed = gain is not None
    dx = jax.ShapeDtypeStruct(x.shape, x.dtype)
    out = _call(
        _bwd_kernel, "qk_prep_bwd", grid,
        dict(static, rotary_lanes=rotary_lanes, eps=eps, normed=normed),
        in_specs=[spec["heads"], spec["table"], spec["table"]]
        + [spec["lanes"], spec["gain"]] * normed,
        out_specs=[spec["lanes"], spec["dgain"]] if normed
        else spec["lanes"],
        out_shape=[dx, jax.ShapeDtypeStruct(
            grid[:2] + (1, head_dim), jnp.float32)] if normed else dx,
    )(dz, cos, sin, *([x, gain] if normed else []))
    if not normed:
        return out, None, None, None
    return out[0], out[1].sum(axis=(0, 1)), None, None


_prep.defvjp(_prep_fwd, _prep_bwd)


def _gain_row(gain, zero_centered):
    """The gain as the kernels multiply by it: [1, D] float32."""
    gain = gain.astype(jnp.float32)
    return (1.0 + gain if zero_centered else gain)[None, :]


def qk_prep(x, gain, angle, *, head_dim, eps=0.0, zero_centered=False,
            factor=1.0):
    """``apply_rotary(rms_norm(x's heads, gain), rotary_lanes)`` as ``[B,
    heads, S, D]``. ``x`` [B, S, heads * D]: a projection's result. ``gain``
    [D] (``zero_centered``: applied as ``1 + gain``) or None: no norm.
    ``angle`` [S, rotary_lanes / 2] float32: each row's angles
    (ops/transformer.py:rotary_angles); ``factor`` on their cosine and sine
    (``apply_rotary``'s)."""
    return _prep(
        x, None if gain is None else _gain_row(gain, zero_centered),
        *rotary_tables(angle, factor), int(head_dim), 2 * angle.shape[1],
        float(eps))


# ---- [B, S, W] in place: rotation of the first heads ------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rotate_in_place(x, cos, sin, heads, head_dim, rotary_lanes):
    return _rotate_in_place_fwd(x, cos, sin, heads, head_dim, rotary_lanes)[0]


def _in_place_call(kernel, name, x, cos, sin, heads, head_dim, rotary_lanes):
    grid, static, spec = _specs(x, heads, head_dim, cos.shape[1])
    return _call(
        kernel, name, grid,
        dict(static, rotary_lanes=rotary_lanes, eps=0.0, normed=False),
        in_specs=[spec["lanes"], spec["table"], spec["table"]],
        out_specs=spec["lanes"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
    )(x, cos, sin)


def _rotate_in_place_fwd(x, cos, sin, heads, head_dim, rotary_lanes):
    return _in_place_call(
        _fwd_kernel, "qk_prep_fwd", x, cos, sin, heads, head_dim,
        rotary_lanes), (cos, sin)


def _rotate_in_place_bwd(heads, head_dim, rotary_lanes, residuals, dz):
    return _in_place_call(
        _bwd_kernel, "qk_prep_bwd", dz, *residuals, heads, head_dim,
        rotary_lanes), None, None


_rotate_in_place.defvjp(_rotate_in_place_fwd, _rotate_in_place_bwd)


def qk_prep_in_place(x, angle, *, heads, head_dim):
    """``x`` [B, S, W] with ``apply_rotary`` by ``angle`` [S, rotary_lanes /
    2] done on its first ``heads`` heads of D lanes and every other lane as
    it was, in ``x``'s buffer."""
    return _rotate_in_place(
        x, *rotary_tables(angle), int(heads), int(head_dim),
        2 * angle.shape[1])
