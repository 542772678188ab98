"""The Gated DeltaNet mixer's glue kernels (ops/gdn_glue.py: ``gdn_in_fwd`` /
``gdn_in_bwd`` before the delta rule, ``gdn_out_fwd`` / ``gdn_out_bwd`` after
it; interpret mode on the CPU) against the XLA functions they stand for
(``linear_attention.gdn_inputs``, ``gated_head_rms_norm``) and against
``jax.grad`` of them: float32 and bfloat16, the convolution at a sequence's
first rows and across the edges of a row block and of a walk, ``d conv_w`` and
the gain's gradient, ``gdn_glue_path``'s choices and its log line, and the
whole mixer through the kernels against the same mixer through the XLA
functions."""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gdn_glue as gg
from deepspeed_tpu.ops import linear_attention as la
from deepspeed_tpu.utils.logging import logger

B, S, HK, HV, D, TAPS, EPS = 2, 64, 2, 4, 128, 4, 1e-6
QK, VZ = HK * D, HV * D

# (rows a grid step, rows a walk): one block in one walk; two walks a block;
# two blocks of one walk (the halo in-specs carry the rows across the edge);
# four blocks of two walks.
CUTS = {"one_block": (64, 64), "two_walks": (64, 32), "two_blocks": (32, 32),
        "four_blocks_two_walks": (32, 16)}


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """The suite runs on eight virtual devices, where ``gdn_glue_path`` says
    ``xla``; the mixer is tested as the one-chip cell runs it."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


@pytest.fixture
def cut(request, monkeypatch):
    rows, walk = CUTS[request.param]
    monkeypatch.setattr(gg, "BLOCK_ELEMENTS", rows * D)
    monkeypatch.setattr(gg, "IN_WALK_ELEMENTS", walk * D)
    monkeypatch.setattr(gg, "OUT_WALK_ELEMENTS", walk * D)
    assert gg._row_blocks(S, D, walk * D) == (rows, walk)
    return rows, walk


every_cut = pytest.mark.parametrize("cut", sorted(CUTS), indirect=True)


def operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, *shape: jax.random.normal(k, shape).astype(dtype)
    return dict(
        mixed=normal(ks[0], B, S, 2 * QK + VZ),
        conv_w=(0.5 * jax.random.normal(ks[1], (TAPS, 2 * QK + VZ))).astype(
            dtype),
        probes=(normal(ks[2], B, S, QK), normal(ks[3], B, S, QK),
                normal(ks[4], B, S, VZ)),
        o=normal(ks[5], B, S, VZ), z=normal(ks[6], B, S, VZ),
        gain=(1.0 + 0.2 * jax.random.normal(ks[7], (D,))).astype(dtype),
        dy=normal(ks[8], B, S, VZ))


def inputs_xla(mixed, conv_w):
    return la.gdn_inputs(mixed, conv_w, HK, D)


def inputs_fused(mixed, conv_w):
    return gg.gdn_inputs_fused(mixed, conv_w, key_heads=HK, key_dim=D)


def output_xla(o, z, gain):
    heads = (B, S, HV, D)
    return la.gated_head_rms_norm(
        o.reshape(heads), z.reshape(heads), gain, EPS).reshape(o.shape)


def output_fused(o, z, gain):
    return gg.gdn_output_fused(o, z, gain, EPS)


def inputs_grad(fn, t):
    return jax.grad(lambda m, w: sum(
        jnp.sum((a * p).astype(jnp.float32))
        for a, p in zip(fn(m, w), t["probes"])), (0, 1))(
            t["mixed"], t["conv_w"])


def output_grad(fn, t):
    return jax.grad(lambda o, z, g: jnp.sum(
        (fn(o, z, g) * t["dy"]).astype(jnp.float32)), (0, 1, 2))(
            t["o"], t["z"], t["gain"])


def bf16_steps(a, b, lanes=D):
    """Largest |a - b| in steps of bfloat16 at the largest magnitude of the
    row of ``lanes`` lanes it lies in."""
    a, b = (np.asarray(x, np.float32).reshape(-1, lanes) for x in (a, b))
    scale = np.maximum(np.abs(b).max(axis=-1, keepdims=True), 1e-30)
    return float((np.abs(a - b) / np.exp2(np.floor(np.log2(scale)) - 7)).max())


def close(got, want, rtol=1e-4):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        got, want, rtol=rtol, atol=1e-5 * float(jnp.max(jnp.abs(want))))


@every_cut
def test_float32_inputs_match_the_xla_functions_and_their_gradient(cut):
    t = operands(jnp.float32)
    for got, want in zip(inputs_fused(t["mixed"], t["conv_w"]),
                         inputs_xla(t["mixed"], t["conv_w"])):
        close(got, want, rtol=1e-5)
    for got, want in zip(inputs_grad(inputs_fused, t),
                         inputs_grad(inputs_xla, t)):
        close(got, want)


@every_cut
def test_float32_output_norm_matches_the_xla_function_and_its_gradient(cut):
    t = operands(jnp.float32)
    close(output_fused(t["o"], t["z"], t["gain"]),
          output_xla(t["o"], t["z"], t["gain"]), rtol=1e-5)
    for got, want in zip(output_grad(output_fused, t),
                         output_grad(output_xla, t)):
        close(got, want)


@pytest.mark.parametrize("cut", ["four_blocks_two_walks"], indirect=True)
def test_bfloat16_rounds_once_and_stays_within_a_few_steps(cut):
    """The kernels round to bf16 once: they are within half a step of the XLA
    functions over the same operands in float32 (which round every product of
    the convolution, SiLU and the norm), never farther from it than the XLA
    functions in bf16 are, and within a few steps of those."""
    t = operands(jnp.bfloat16)
    exact = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)
    got = (*inputs_fused(t["mixed"], t["conv_w"]),
           output_fused(t["o"], t["z"], t["gain"]))
    want = (*inputs_xla(t["mixed"], t["conv_w"]),
            output_xla(t["o"], t["z"], t["gain"]))
    ref = (*inputs_xla(exact["mixed"], exact["conv_w"]),
           output_xla(exact["o"], exact["z"], exact["gain"]))
    for a, b, r in zip(got, want, ref):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        assert bf16_steps(a, r) <= 0.5 + 1e-3
        assert bf16_steps(a, r) <= bf16_steps(b, r) + 1e-3
        assert bf16_steps(a, b) <= 4
    grads = (*inputs_grad(inputs_fused, t), *output_grad(output_fused, t))
    wants = (*inputs_grad(inputs_xla, t), *output_grad(output_xla, t))
    refs = (*inputs_grad(inputs_xla, exact), *output_grad(output_xla, exact))
    for name, a, b, r in zip(
            ("d_mixed", "d_conv_w", "d_o", "d_z", "d_gain"), grads, wants,
            refs):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape, name
        # the parameters' gradients are sums over every row: one scale
        lanes = a.size if name in ("d_conv_w", "d_gain") else D
        assert bf16_steps(a, r, lanes) <= 0.5 + 1e-3, name
        assert bf16_steps(a, b, lanes) <= 6, name


@pytest.mark.parametrize("cut", ["two_blocks", "four_blocks_two_walks"],
                         indirect=True)
def test_convolution_reads_zeros_before_a_sequence_and_rows_across_an_edge(
        cut):
    """An impulse at one row reaches the TAPS - 1 rows after it and no other:
    across a walk's and a row block's edge, not past a batch row's end into
    the next row's start; the cotangent's way back the same."""
    rows, walk = cut
    w = jnp.arange(1.0, TAPS + 1)[:, None] * jnp.ones((TAPS, 2 * QK + VZ))
    v_lane = 2 * QK + 5                    # a v lane: conv and SiLU only
    for at in (0, walk - 1, rows - 2, S - 2):
        mixed = jnp.zeros((B, S, 2 * QK + VZ)).at[0, at, v_lane].set(1.0)
        v = inputs_fused(mixed, w)[2]
        want = jnp.zeros((B, S)).at[0, at:at + TAPS].set(
            jax.nn.silu(w[::-1, 0])[:S - at])
        np.testing.assert_allclose(v[:, :, 5], want, atol=1e-6)
        assert not np.any(np.asarray(v[1]))
        # d mixed at a row sums the cotangents of the TAPS rows from it on
        back = jax.grad(lambda m: inputs_fused(m, w)[2][0, at, 5])(
            jnp.zeros_like(mixed))
        lo = max(at - TAPS + 1, 0)
        want = jnp.zeros((B, S)).at[0, lo:at + 1].set(
            0.5 * w[TAPS - 1 - (at - lo):, 0])
        np.testing.assert_allclose(back[:, :, v_lane], want, atol=1e-6)
        assert np.count_nonzero(np.asarray(back)) == at + 1 - lo


def test_first_rows_of_every_batch_row_match_for_q_k_and_v():
    """Rows 0 .. TAPS - 2 see zeros before position 0 in EVERY batch row:
    the second row's first rows read nothing of the first row's last."""
    t = operands(jnp.float32, seed=3)
    for got, want in zip(inputs_fused(t["mixed"], t["conv_w"]),
                         inputs_xla(t["mixed"], t["conv_w"])):
        np.testing.assert_allclose(
            got[:, :TAPS], want[:, :TAPS], rtol=1e-5, atol=1e-6)
    for got, want in zip(inputs_grad(inputs_fused, t),
                         inputs_grad(inputs_xla, t)):
        if got.ndim == 3:
            np.testing.assert_allclose(
                got[:, -TAPS:], want[:, -TAPS:], rtol=1e-4, atol=1e-5)


CELL = (2, 16384, 16, 32, 128, 128, 4)


@pytest.mark.parametrize("shape,devices,reason", [
    (CELL, 1, None),
    ((2, 64, 2, 4, 16, 16, 4), 1, "do not fill 128-lane blocks"),
    ((2, 16384, 16, 32, 256, 128, 4), 1, None),
    ((2, 64, 2, 3, 256, 128, 4), 1, "no whole number of 256-lane column"),
    ((2, 16384 + 8, 16, 32, 128, 128, 4), 1, "no block of rows divides"),
    ((2, 24, 2, 4, 128, 128, 4), 1, "no block of rows divides"),
    (CELL[:6] + (18,), 1, "18 taps reach past the 16 rows"),
    (CELL, 4, "4 devices and no mesh"),
], ids=["cell", "toy_width", "key_256", "v_no_column_block", "seq_odd",
        "seq_24", "taps_18", "four_devices"])
def test_path_is_chosen_from_shapes_and_mesh_and_logged_once(
        shape, devices, reason, monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    gg._log_path.cache_clear()
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    was = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        path, why = gg.gdn_glue_path(*shape)
        assert gg.gdn_glue_path(*shape) == (path, why)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(was)
    assert (path == "fused") == (reason is None)
    assert reason is None or reason in why, why
    mine = [l for l in lines if l.startswith("gdn_glue ")]
    assert len(mine) == 1, lines
    b, s, hk, hv, dk, dv, taps = shape
    assert re.fullmatch(
        rf"gdn_glue b={b} s={s} heads={hk}/{hv} d={dk}/{dv} taps={taps} "
        rf"path={path}( reason='.*')?", mine[0]), mine[0]
    assert ("reason=" in mine[0]) == (path == "xla")


def test_sharded_operands_take_the_xla_functions():
    from jax.sharding import Mesh

    from deepspeed_tpu.config.constants import DATA_AXIS, MODEL_AXIS

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                (DATA_AXIS, MODEL_AXIS))
    assert gg.gdn_glue_path(*CELL, mesh=mesh) == (
        "xla", "a kernel is not partitioned over devices")


def mixer(p, x, fused, monkeypatch):
    calls = []
    real = gg._call

    def counted(kernel, name, *a, **k):
        calls.append(name)
        return real(kernel, name, *a, **k)

    monkeypatch.setattr(gg, "_call", counted)
    if not fused:
        monkeypatch.setattr(
            la, "gdn_glue_path", lambda *a, **k: ("xla", "held by the test"))
    run = lambda p, x: jnp.sum(la.gated_deltanet_mixer(
        p, x, key_heads=HK, value_heads=HV, key_dim=D, value_dim=D, chunk=16,
        eps=EPS).astype(jnp.float32))
    out = jax.value_and_grad(run, (0, 1))(p, x)
    return out, calls


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_mixer_through_the_kernels_matches_the_mixer_through_xla(
        dtype, monkeypatch):
    e = 64
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    p = {
        "in_qkvz": 0.1 * jax.random.normal(ks[0], (e, 2 * QK + 2 * VZ)),
        "in_ba": 0.1 * jax.random.normal(ks[1], (e, 2 * HV)),
        "conv_w": 0.5 * jax.random.normal(ks[2], (TAPS, 2 * QK + VZ)),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (HV,), minval=1.0, maxval=16.0)),
        "dt_bias": 0.1 * jax.random.normal(ks[4], (HV,)),
        "out_norm": 1.0 + 0.1 * jax.random.normal(ks[5], (D,)),
        "out_proj": 0.1 * jax.random.normal(ks[6], (VZ, e)),
    }
    p = {k: v.astype(dtype) for k, v in p.items()}
    x = jax.random.normal(ks[7], (B, S, e)).astype(dtype)
    (got, got_grads), calls = mixer(p, x, True, monkeypatch)
    assert calls == ["gdn_in_fwd", "gdn_out_fwd", "gdn_out_bwd", "gdn_in_bwd"]
    (want, want_grads), calls = mixer(p, x, False, monkeypatch)
    assert calls == []
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got_grads),
            jax.tree_util.tree_leaves(want_grads)):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), path
