"""What lies between a Gated DeltaNet mixer's projections and its delta rule,
and between the rule and ``out_proj``, in one pass a side and a direction: the
Pallas kernels ``gdn_in_fwd`` / ``gdn_in_bwd`` and ``gdn_out_fwd`` /
``gdn_out_bwd``.

**Before the rule.** A program takes a block of rows of ONE column block (a
key head's lanes, 128 or a multiple, as the product wrote them) out of the
projection's own ``mixed`` ``[B, S, 2 Hk dk + Hv dv]`` result and does in
VMEM and in float32 what ``gdn_inputs`` does as XLA functions: the causal depthwise
convolution, SiLU, and for the q and k columns the L2 norm of the head (q
times ``dk ** -0.5``); the v columns pass through. It rounds to the
activations' dtype once and writes q, k ``[B, S, Hk dk]`` and v ``[B, S, Hv
dv]``, the layout the delta rule takes. The convolution's K - 1 rows before a
row block come in through a second in-spec on the ``HALO`` rows before it
(zeros at a sequence's start: nothing is read across a batch row's boundary).
The grid runs the column blocks innermost, q's first, then k's, then v's; the
three results' index maps clamp the column to their own range, so a result's
block is written while the grid is in its range and goes back to HBM once.

The backward kernel reads ``mixed`` again and the three cotangents (remat
computes the product again anyway; no residual is added), with ``HALO`` rows
on both sides: ``d mixed`` at a row reads the convolution's cotangent at the
K - 1 rows after it, which the program computes again from the rows it has
(nothing at a sequence's end). It writes ``d mixed`` once and a ``[K, lanes]``
float32 partial of ``d conv_w`` a row block, summed by XLA.

**After the rule.** ``gated_head_rms_norm``: a program takes a block of rows of
one value head of ``o`` and ``z`` ``[B, S, Hv dv]``; the backward reads o, z
and the cotangent, writes ``d o``, ``d z`` and a ``[1, dv]`` float32 partial of
the gain's gradient a row block.

``gdn_glue_path`` chooses between the kernels and the XLA functions from the
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

F32 = jnp.float32
L2_EPS = 1e-6

# Rows of the in-specs that reach past a row block: bfloat16's sublane tile,
# which holds the K - 1 rows a convolution looks back (and its cotangent
# ahead).
HALO = 16

# Elements of a grid step's block of one column block and of one walk inside
# it, before and after the rule, from a sweep at the Qwen3-Next cell's shape (2
# x 16,384 rows, 64 column blocks of 128 before the rule and 32 heads of 128
# after it, bf16; my chip run, PR 41, tools/gdn_glue_alone.py sweep; ms a call
# in_fwd / in_bwd / out_fwd / out_bwd, 1.31 / 1.97 / 0.98 / 1.64 at the chip's
# bandwidth). Blocks of 4,096 rows, walks of 128 rows 2.85 / 4.73 / 1.51 /
# 2.53, of 256 2.41 / 4.14 / 1.50 / 2.36, of 512 2.41 / 4.34 / 1.47 / 2.24, of
# 1,024 2.60 / 4.78 / 1.45 / 2.25: the backward before the rule holds a walk's
# K shifted operands and the convolution's cotangent at once and wants the
# shorter walk. Walks of 256: blocks of 1,024 rows 2.97 / 4.71 / 1.82 / 2.54,
# of 2,048 2.62 / 4.33 / 1.68 / 2.40, of 8,192 2.35 / 4.07 / 1.44 / 2.25 (2%
# for twice the VMEM: not taken).
BLOCK_ELEMENTS = 4096 * LANES
IN_WALK_ELEMENTS = 256 * LANES
OUT_WALK_ELEMENTS = 512 * LANES


def gdn_glue_path(batch, seq, key_heads, value_heads, key_dim, value_dim,
                  taps, mesh=None):
    """``("fused", None)`` where the kernels take a mixer's glue, else
    ``("xla", reason)``: ``gdn_inputs`` and ``gated_head_rms_norm`` over the
    whole array. Chosen from what the caller sees and nothing else; logged
    once a shape."""
    reason = None
    if key_dim % LANES or value_dim % LANES:
        reason = (f"head widths {key_dim} / {value_dim} do not fill "
                  f"{LANES}-lane blocks")
    elif value_heads * value_dim % key_dim:
        reason = (f"the {value_heads * value_dim} lanes of v are no whole "
                  f"number of {key_dim}-lane column blocks")
    elif taps - 1 > HALO:
        reason = f"{taps} taps reach past the {HALO} rows before a block"
    elif not (_row_blocks(seq, key_dim, IN_WALK_ELEMENTS)[1]
              and _row_blocks(seq, value_dim, OUT_WALK_ELEMENTS)[1]):
        reason = f"no block of rows divides seq={seq}"
    else:
        route = _flash_route(mesh, batch, key_heads)
        if route != "local":
            reason = ("a kernel is not partitioned over devices" if
                      route == "sharded" else route)
    path = "xla" if reason else "fused"
    _log_path(batch, seq, key_heads, value_heads, key_dim, value_dim, taps,
              path, reason)
    return path, reason


@functools.lru_cache(maxsize=None)
def _log_path(batch, seq, key_heads, value_heads, key_dim, value_dim, taps,
              path, reason):
    logger.debug(
        "gdn_glue b=%d s=%d heads=%d/%d d=%d/%d taps=%d path=%s%s",
        batch, seq, key_heads, value_heads, key_dim, value_dim, taps, path,
        f" reason={reason!r}" if reason else "")


def _row_blocks(seq, width, walk_elements):
    """(rows a grid step, rows a walk inside it), both whole ``HALO``s; 0
    where none divides."""
    rows = pick_block(seq, max(BLOCK_ELEMENTS // width, HALO))
    if rows % HALO:
        return 0, 0
    walk = pick_block(rows, max(walk_elements // width, HALO))
    return rows, 0 if walk % HALO else walk


def _call(kernel, name, grid, static, **kwargs):
    return pl.pallas_call(
        functools.partial(kernel, **static), grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=not device.on_tpu(), name=name, **kwargs)


def _walks(rows, walk, body, carry=None):
    """``body(walk's index, its first row, carry)`` over a block's rows."""
    def step(i, carry):
        return body(i, pl.multiple_of(i * walk, walk), carry)

    return jax.lax.fori_loop(0, rows // walk, step, carry)


def _rows_before(ref, halo_ref, i, at, at_start):
    """The ``HALO`` rows before row ``at`` of a block, float32: the block's
    own, or for its first walk the halo's (zeros where ``at_start``: the
    block is a sequence's first)."""
    own = ref[pl.ds(pl.multiple_of(jnp.maximum(at - HALO, 0), HALO), HALO), :]
    halo = jnp.where(at_start, jnp.zeros_like(own), halo_ref[...])
    return jnp.where(i == 0, halo, own).astype(F32)


def _rows_after(ref, halo_ref, i, at, walk, rows, at_end=None):
    """The ``HALO`` rows after a walk, as ``_rows_before`` (``at_end`` None:
    whatever the halo holds, for a caller that reads nothing of it there)."""
    own = ref[pl.ds(pl.multiple_of(
        jnp.minimum(at + walk, rows - HALO), HALO), HALO), :]
    halo = halo_ref[...]
    if at_end is not None:
        halo = jnp.where(at_end, jnp.zeros_like(own), halo)
    return jnp.where(i == rows // walk - 1, halo, own).astype(F32)


def _down(x, j):
    """``x`` [n, lanes] float32 moved ``j`` rows down: row t holds row t - j
    (the first ``j`` rows wrap: the callers cut them off)."""
    return pltpu.roll(x, j, 0) if j else x


def _up(x, j):
    """Row t holds row t + j (the last ``j`` rows wrap)."""
    return pltpu.roll(x, x.shape[0] - j, 0) if j else x


def _taps(w_ref):
    """The taps [K, lanes] float32 as K rows [1, lanes], the NEWEST row's
    first: row j multiplies the operand ``j`` rows back."""
    taps = w_ref.shape[0]
    return [w_ref[taps - 1 - j:taps - j, :] for j in range(taps)]


def _convolve(ext, w, n):
    """Rows ``HALO .. HALO + n`` of the causal depthwise convolution of
    ``ext`` [HALO + n (+ more), lanes] with ``_taps``' rows: (its result, the
    K shifted operands, the newest tap's first)."""
    shifted = [_down(ext, j)[HALO:HALO + n] for j in range(len(w))]
    return sum(t * x for t, x in zip(w, shifted)), shifted


# ---- before the rule: conv, SiLU, L2 norms, the split into q | k | v --------
def _by_section(key_blocks, scale, run, q, k, v):
    """``run(refs, normed, scale)`` with the refs of the section that the
    program's column block lies in: q's (normalised, times ``scale``), k's
    (normalised) or v's."""
    column = pl.program_id(2)
    pl.when(column < key_blocks)(lambda: run(q, True, scale))
    pl.when((column >= key_blocks) & (column < 2 * key_blocks))(
        lambda: run(k, True, 1.0))
    pl.when(column >= 2 * key_blocks)(lambda: run(v, False, 1.0))


def _in_fwd_kernel(x_ref, before_ref, w_ref, q_ref, k_ref, v_ref, *, rows,
                   walk, key_blocks, scale):
    at_start = pl.program_id(1) == 0
    w = _taps(w_ref)

    def run(o_ref, normed, scale):
        def body(i, at, _):
            ext = jnp.concatenate(
                [_rows_before(x_ref, before_ref, i, at, at_start),
                 x_ref[pl.ds(at, walk), :].astype(F32)], axis=0)
            y = jax.nn.silu(_convolve(ext, w, walk)[0])
            if normed:
                y = y * (jax.lax.rsqrt(
                    jnp.sum(y * y, axis=1, keepdims=True) + L2_EPS) * scale)
            o_ref[pl.ds(at, walk), :] = y.astype(o_ref.dtype)

        _walks(rows, walk, body)

    _by_section(key_blocks, scale, run, q_ref, k_ref, v_ref)


def _in_bwd_kernel(x_ref, before_ref, after_ref, w_ref, gq_ref, gq_after_ref,
                   gk_ref, gk_after_ref, gv_ref, gv_after_ref, dx_ref, dw_ref,
                   *, rows, walk, key_blocks, scale):
    at_start = pl.program_id(1) == 0
    at_end = pl.program_id(1) == pl.num_programs(1) - 1
    w = _taps(w_ref)
    taps, lanes = w_ref.shape
    n = walk + HALO

    def run(g_refs, normed, scale):
        g_ref, g_after_ref = g_refs

        def body(i, at, dw):
            # the walk's rows and the HALO after them, whose convolution's
            # cotangent the walk's last K - 1 rows read
            ext = jnp.concatenate(
                [_rows_before(x_ref, before_ref, i, at, at_start),
                 x_ref[pl.ds(at, walk), :].astype(F32),
                 _rows_after(x_ref, after_ref, i, at, walk, rows)],
                axis=0)
            g = jnp.concatenate(
                [g_ref[pl.ds(at, walk), :].astype(F32),
                 _rows_after(g_ref, g_after_ref, i, at, walk, rows, at_end)],
                axis=0)
            c, shifted = _convolve(ext, w, n)
            sig = jax.nn.sigmoid(c)
            if normed:
                s = c * sig
                inv = jax.lax.rsqrt(
                    jnp.sum(s * s, axis=1, keepdims=True) + L2_EPS)
                unit = s * inv
                g = g * scale
                g = inv * (g - unit * jnp.sum(g * unit, axis=1, keepdims=True))
            dc = g * sig * (1.0 + c * (1.0 - sig))
            dx_ref[pl.ds(at, walk), :] = sum(
                t * _up(dc, j)[:walk] for j, t in enumerate(w)
            ).astype(dx_ref.dtype)
            return tuple(
                d + jnp.sum(dc[:walk] * x[:walk], axis=0, keepdims=True)
                for d, x in zip(dw, shifted))

        dw = _walks(rows, walk, body,
                    tuple(jnp.zeros((1, lanes), F32) for _ in range(taps)))
        for j in range(taps):                     # newest first -> tap's row
            dw_ref[taps - 1 - j:taps - j, :] = dw[j]

    _by_section(key_blocks, scale, run, (gq_ref, gq_after_ref),
                (gk_ref, gk_after_ref), (gv_ref, gv_after_ref))


def _in_specs(mixed, lanes, key_blocks):
    """The grid (batch, row block, column block of ``lanes``) and the
    BlockSpecs over ``mixed`` [B, S, C] and over q, k, v: a block of rows,
    the ``HALO`` rows before and after it, and for each of the three results
    (and cotangents) the same with the column clamped to its own range."""
    b, s, c = mixed.shape
    rows, walk = _row_blocks(s, lanes, IN_WALK_ELEMENTS)
    per, last = rows // HALO, s // HALO - 1

    def before(r):
        return jnp.maximum(r * per - 1, 0)

    def after(r):
        return jnp.minimum((r + 1) * per, last)

    def clamped(first, count):
        def column(c):
            return jnp.clip(c - first, 0, count - 1)

        return (
            pl.BlockSpec((None, rows, lanes),
                         lambda b, r, c: (b, r, column(c))),
            pl.BlockSpec((None, HALO, lanes),
                         lambda b, r, c: (b, after(r), column(c))))

    spec = {
        "rows": pl.BlockSpec((None, rows, lanes), lambda b, r, c: (b, r, c)),
        "before": pl.BlockSpec(
            (None, HALO, lanes), lambda b, r, c: (b, before(r), c)),
        "after": pl.BlockSpec(
            (None, HALO, lanes), lambda b, r, c: (b, after(r), c)),
        "q": clamped(0, key_blocks),
        "k": clamped(key_blocks, key_blocks),
        "v": clamped(2 * key_blocks, c // lanes - 2 * key_blocks),
    }
    return (b, s // rows, c // lanes), dict(rows=rows, walk=walk), spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _inputs(mixed, conv_w, key_heads, key_dim):
    return _inputs_fwd(mixed, conv_w, key_heads, key_dim)[0]


def _inputs_fwd(mixed, conv_w, key_heads, key_dim):
    b, s, c = mixed.shape
    taps, qk = conv_w.shape[0], key_heads * key_dim
    grid, static, spec = _in_specs(mixed, key_dim, key_heads)
    out = _call(
        _in_fwd_kernel, "gdn_in_fwd", grid,
        dict(static, key_blocks=key_heads, scale=key_dim ** -0.5),
        in_specs=[spec["rows"], spec["before"],
                  pl.BlockSpec((taps, key_dim), lambda b, r, c: (0, c))],
        out_specs=[spec[t][0] for t in "qkv"],
        out_shape=[jax.ShapeDtypeStruct((b, s, w), mixed.dtype)
                   for w in (qk, qk, c - 2 * qk)],
    )(mixed, mixed, conv_w)
    return tuple(out), (mixed, conv_w)


def _inputs_bwd(key_heads, key_dim, residuals, cotangents):
    mixed, conv_w = residuals
    taps = conv_w.shape[0]
    grid, static, spec = _in_specs(mixed, key_dim, key_heads)
    d_mixed, d_w = _call(
        _in_bwd_kernel, "gdn_in_bwd", grid,
        dict(static, key_blocks=key_heads, scale=key_dim ** -0.5),
        in_specs=[spec["rows"], spec["before"], spec["after"],
                  pl.BlockSpec((taps, key_dim), lambda b, r, c: (0, c))]
        + [s for t in "qkv" for s in spec[t]],
        out_specs=[spec["rows"], pl.BlockSpec(
            (None, None, taps, key_dim), lambda b, r, c: (b, r, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct(mixed.shape, mixed.dtype),
            jax.ShapeDtypeStruct(grid[:2] + conv_w.shape, F32)],
    )(mixed, mixed, mixed, conv_w, *(g for g in cotangents for _ in (0, 1)))
    return d_mixed, d_w.sum(axis=(0, 1))


_inputs.defvjp(_inputs_fwd, _inputs_bwd)


def gdn_inputs_fused(mixed, conv_w, *, key_heads, key_dim):
    """``mixed`` [B, S, 2 Hk dk + Hv dv] (q | k | v, a projection's result)
    and ``conv_w`` [K, the same lanes] -> (q, k [B, S, Hk dk], v [B, S, Hv
    dv]): ``silu(causal_depthwise_conv(mixed))`` split, q and k L2-normalised
    a head and q times ``dk ** -0.5``."""
    return _inputs(mixed, conv_w.astype(F32), int(key_heads), int(key_dim))


# ---- after the rule: the gated per-head RMS norm ----------------------------
def _out_fwd_kernel(o_ref, z_ref, gain_ref, y_ref, *, rows, walk, eps):
    gain = gain_ref[...]

    def body(_, at, carry):
        o = o_ref[pl.ds(at, walk), :].astype(F32)
        z = z_ref[pl.ds(at, walk), :].astype(F32)
        unit = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
        y_ref[pl.ds(at, walk), :] = (
            unit * gain * jax.nn.silu(z)).astype(y_ref.dtype)
        return carry

    _walks(rows, walk, body)


def _out_bwd_kernel(o_ref, z_ref, gain_ref, g_ref, do_ref, dz_ref, dgain_ref,
                    *, rows, walk, eps):
    gain = gain_ref[...]

    def body(_, at, dgain):
        o = o_ref[pl.ds(at, walk), :].astype(F32)
        z = z_ref[pl.ds(at, walk), :].astype(F32)
        g = g_ref[pl.ds(at, walk), :].astype(F32)
        inv = jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
        unit, sig = o * inv, jax.nn.sigmoid(z)
        gate = z * sig
        d_unit = g * gain * gate
        do_ref[pl.ds(at, walk), :] = (inv * (
            d_unit - unit * jnp.mean(d_unit * unit, axis=1, keepdims=True))
        ).astype(do_ref.dtype)
        g_unit = g * unit
        dz_ref[pl.ds(at, walk), :] = (
            g_unit * gain * sig * (1.0 + z * (1.0 - sig))).astype(dz_ref.dtype)
        return dgain + jnp.sum(g_unit * gate, axis=0, keepdims=True)

    dgain = _walks(rows, walk, body, jnp.zeros(gain.shape, F32))

    # heads run innermost: a row block's partial sums over them in VMEM
    @pl.when(pl.program_id(2) == 0)
    def _():
        dgain_ref[...] = dgain

    @pl.when(pl.program_id(2) != 0)
    def _():
        dgain_ref[...] += dgain


def _out_specs(o, head_dim):
    b, s, width = o.shape
    rows, walk = _row_blocks(s, head_dim, OUT_WALK_ELEMENTS)
    spec = {
        "lanes": pl.BlockSpec(
            (None, rows, head_dim), lambda b, r, h: (b, r, h)),
        "gain": pl.BlockSpec((1, head_dim), lambda b, r, h: (0, 0)),
        "dgain": pl.BlockSpec(
            (None, None, 1, head_dim), lambda b, r, h: (b, r, 0, 0)),
    }
    return (b, s // rows, width // head_dim), dict(rows=rows, walk=walk), spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _output(o, z, gain, eps):
    return _output_fwd(o, z, gain, eps)[0]


def _output_fwd(o, z, gain, eps):
    grid, static, spec = _out_specs(o, gain.shape[1])
    y = _call(
        _out_fwd_kernel, "gdn_out_fwd", grid, dict(static, eps=eps),
        in_specs=[spec["lanes"], spec["lanes"], spec["gain"]],
        out_specs=spec["lanes"],
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
    )(o, z, gain)
    return y, (o, z, gain)


def _output_bwd(eps, residuals, g):
    o, z, gain = residuals
    grid, static, spec = _out_specs(o, gain.shape[1])
    d_o, d_z, d_gain = _call(
        _out_bwd_kernel, "gdn_out_bwd", grid, dict(static, eps=eps),
        in_specs=[spec["lanes"], spec["lanes"], spec["gain"], spec["lanes"]],
        out_specs=[spec["lanes"], spec["lanes"], spec["dgain"]],
        out_shape=[
            jax.ShapeDtypeStruct(o.shape, o.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct(grid[:2] + gain.shape, F32)],
    )(o, z, gain, g)
    return d_o, d_z, d_gain.sum(axis=(0, 1))


_output.defvjp(_output_fwd, _output_bwd)


def gdn_output_fused(o, z, gain, eps):
    """``gated_head_rms_norm`` over the heads of ``o`` and ``z`` [B, S, Hv
    dv] with ``gain`` [dv]: ``o / rms(o) * gain * silu(z)`` a head."""
    return _output(o, z, gain.astype(F32)[None, :], float(eps))
