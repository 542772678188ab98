"""The chunked gated delta rule (ops/linear_attention.py) against the
per-position recurrence of its plain reference
(benchmark/reference/qwen3_next.py:delta_rule), values and every gradient,
on both of its paths: the Pallas kernels in interpret mode at head width 128
and the XLA form at a toy width; the triangular inverse of each alone; the
whole Gated DeltaNet mixer against the reference's; the ``gdn_chunking`` and
``gdn_path`` log lines."""

import functools
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import linear_attention as la
from deepspeed_tpu.utils.logging import logger

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402

B, HK, HV, DK, DV = 2, 2, 4, 8, 16
# the width at which ``gdn_path`` hands the rule to the kernels
WIDE = 128
DOT = ref_ops.make_dot("float32")
# (scale of the log decay, shift of beta's logit): weak and strong decay,
# beta near 0 and near 1
REGIMES = {"weak-decay": (-0.01, 0.0), "strong-decay": (-8.0, 0.0),
           "beta-near-1": (-1.0, 6.0), "beta-near-0": (-1.0, -6.0)}


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """The suite runs on eight virtual devices, where a kernel is not
    partitioned and ``gdn_path`` says ``xla``; the rule is tested as the
    benchmark's one-chip cell runs it."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def operands(seq, regime, seed=0, dims=(B, HK, HV, DK, DV)):
    b, hk, hv, dk, dv = dims
    rng = np.random.default_rng(seed)
    decay, shift = REGIMES[regime]
    q = la.l2_normalise(normal(rng, b, seq, hk, dk)) * dk ** -0.5
    k = la.l2_normalise(normal(rng, b, seq, hk, dk))
    v = normal(rng, b, seq, hv, dv)
    g = decay * jnp.abs(normal(rng, b, seq, hv))
    beta = jax.nn.sigmoid(normal(rng, b, seq, hv) + shift)
    return (q, k, v, g, beta), normal(rng, b, seq, hv, dv)


def recurrence(q, k, v, g, beta, state=None):
    """The reference's per-position rule, each key head repeated for its
    value heads; from ``state`` [B,Hk,R,dk,dv] where one is given (the
    reference starts from zeros)."""
    r = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    if state is None:
        return ref.delta_rule(q, k, v, g, beta)

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.sum(state * k_t[..., :, None], axis=-2))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    _, o = jax.lax.scan(
        step, state.reshape((state.shape[0], -1) + state.shape[3:]),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


@functools.partial(jax.jit, static_argnums=(5, 6))
def chunked(q, k, v, g, beta, chunk, segment, state=None):
    return la.gated_delta_rule_chunked(
        q, k, v, g, beta, chunk, segment, initial_state=state)


@functools.partial(jax.jit, static_argnums=(6, 7))
def chunked_grads(probe, q, k, v, g, beta, chunk, segment):
    return jax.grad(lambda *a: jnp.sum(
        la.gated_delta_rule_chunked(*a, chunk, segment) * probe),
        argnums=range(5))(q, k, v, g, beta)


# (path, (B, Hk, Hv, dk, dv), seq, chunk, chunks a segment). XLA form: 37 and
# 5 are no multiples of the chunk and 48 is under it; 64 is two segments of
# two chunks, 70 three segments of three with two chunks of padding, each
# handing its state on. Kernels: 16 chunks of 8, 8 of 16, 4 of 32 or 2 of 64
# make one block of 128 positions; 37 and 5 are no multiples of the chunk
# and pad one block; 300 is three segments of one block (four chunks of 32,
# two of them padding), 200 two segments of one block of two chunks, 520 two
# segments of two blocks (R = 1: every key head serves one value head).
CASES = [
    ("xla", (B, HK, HV, DK, DV), 37, 8, 32),
    ("xla", (B, HK, HV, DK, DV), 64, 16, 2),
    ("xla", (B, HK, HV, DK, DV), 5, 8, 32),
    ("xla", (B, HK, HV, DK, DV), 48, 64, 32),
    ("xla", (B, HK, HV, DK, DV), 70, 8, 4),
    ("kernel", (2, 1, 2, WIDE, WIDE), 37, 16, None),
    ("kernel", (1, 2, 4, WIDE, WIDE), 5, 8, None),
    ("kernel", (1, 1, 2, WIDE, WIDE), 300, 32, 4),
    ("kernel", (1, 2, 4, WIDE, WIDE), 200, 64, 2),
    ("kernel", (1, 2, 2, WIDE, WIDE), 520, 64, 4),
]


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize(
    "path,dims,seq,chunk,segment", CASES,
    ids=[f"{c[0]}-{c[2]}-{c[3]}-{c[4]}" for c in CASES])
def test_chunked_delta_rule_matches_the_recurrence(
        path, dims, seq, chunk, segment, regime):
    """Values and the gradient of every operand (q, k, v, the log decay and
    beta), on the path that ``gdn_path`` picks for the shape."""
    b, hk, hv, dk, dv = dims
    assert la.gdn_path(b, seq, hk, hv, dk, dv, chunk, None, segment)[
        "path"] == path
    args, probe = operands(seq, regime, dims=dims)

    np.testing.assert_allclose(
        chunked(*args, chunk, segment), recurrence(*args),
        rtol=2e-4, atol=2e-5)
    g_ours = chunked_grads(probe, *args, chunk, segment)
    g_theirs = jax.grad(
        lambda *a: jnp.sum(recurrence(*a) * probe), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), g_ours, g_theirs):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))),
            err_msg=name)


@pytest.mark.parametrize("path,dims,seq,chunk,segment", [
    ("xla", (B, HK, HV, DK, DV), 40, 8, 2),
    ("kernel", (1, 1, 2, WIDE, WIDE), 200, 32, 4)], ids=["xla", "kernel"])
def test_the_state_before_the_first_position_and_its_gradient(
        path, dims, seq, chunk, segment):
    """``initial_state``: the output from it, its gradient (what the
    backward pass hands from the first segment on) and the operands'."""
    b, hk, hv, dk, dv = dims
    args, probe = operands(seq, "beta-near-1", dims=dims)
    state = 0.3 * normal(np.random.default_rng(7), b, hk, hv // hk, dk, dv)
    assert la.gdn_path(b, seq, hk, hv, dk, dv, chunk, None, segment)[
        "path"] == path

    np.testing.assert_allclose(
        chunked(*args, chunk, segment, state), recurrence(*args, state),
        rtol=2e-4, atol=2e-5)
    g_ours = jax.grad(lambda *a: jnp.sum(
        chunked(*a[:5], chunk, segment, a[5]) * probe), argnums=range(6))(
            *args, state)
    g_theirs = jax.grad(lambda *a: jnp.sum(
        recurrence(*a) * probe), argnums=range(6))(*args, state)
    for name, a, b in zip("q k v g beta state".split(), g_ours, g_theirs):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))),
            err_msg=name)


@pytest.mark.parametrize("size", [1, 2, 8, 64])
def test_unit_lower_inverse_and_its_backward(size):
    """The XLA form's: against forward substitution (``solve_triangular``),
    values and the gradient; entries of the delta rule's size (|k . k| <= 1,
    decayed)."""
    from jax.scipy.linalg import solve_triangular

    rng = np.random.default_rng(size)
    a = jnp.tril(normal(rng, 3, size, size) * 0.2, -1)
    eye = jnp.eye(size)

    def substituted(a):
        return solve_triangular(
            eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
            unit_diagonal=True)

    inv = la.unit_lower_inverse(a)
    scale = float(jnp.max(jnp.abs(inv)))
    np.testing.assert_allclose(inv, substituted(a), atol=1e-5 * scale)
    np.testing.assert_allclose(
        jnp.matmul(eye + a, inv, precision="highest"),
        jnp.broadcast_to(eye, a.shape), atol=1e-5 * scale)
    probe = normal(rng, 3, size, size)
    ours = jax.grad(lambda a: jnp.sum(la.unit_lower_inverse(a) * probe))(a)
    theirs = jax.grad(lambda a: jnp.sum(substituted(a) * probe))(a)
    np.testing.assert_allclose(
        jnp.tril(ours, -1), jnp.tril(theirs, -1),
        atol=1e-4 * float(jnp.max(jnp.abs(theirs))))


@pytest.mark.parametrize("chunk", [8, 16, 32, 64, 128])
def test_the_kernels_block_inverse(chunk):
    """The kernels': ``(I + A)^-1`` of a block of ``max(128, chunk)``
    positions whose chunks sit on the diagonal, two value heads at once,
    against ``jnp.linalg.solve``. It comes out with the chunks' inverses
    side by side (what the forward kernel keeps for the backward kernel);
    unpacked, nothing leaks from one chunk of the block into the next."""
    m = max(chunk, la.LANES)
    blk = la._Block(m, chunk)
    rng = np.random.default_rng(chunk)
    a = jnp.where(blk.strict, normal(rng, 2, m, m) * 0.2, 0.0)
    packed = blk.inverse(a)
    assert packed.shape == (2, chunk, m)
    inv = blk.unpack(packed)
    solved = jnp.linalg.solve(jnp.eye(m) + a, jnp.broadcast_to(
        jnp.eye(m), a.shape))
    scale = float(jnp.max(jnp.abs(solved)))
    np.testing.assert_allclose(inv, solved, atol=1e-5 * scale)
    assert not bool(jnp.any(jnp.where(blk.same, 0.0, inv)))
    first = la.unit_lower_inverse(a[:, :chunk, :chunk])
    np.testing.assert_allclose(
        inv[:, :chunk, :chunk], first, atol=1e-5 * scale)
    np.testing.assert_array_equal(blk.pack(inv), packed)


@pytest.mark.parametrize("path,dims,chunk", [
    ("xla", (B, HK, HV, DK, DV), 8), ("kernel", (1, 1, 2, WIDE, WIDE), 16)],
    ids=["xla", "kernel"])
def test_state_decays_to_nothing_under_a_strong_decay_without_overflow(
        path, dims, chunk):
    """exp(-2000) a step: every decay underflows to 0, nothing is inf or
    nan, and each output reads its own position only."""
    b, hk, hv, dk, dv = dims
    assert la.gdn_path(b, 32, hk, hv, dk, dv, chunk)["path"] == path
    (q, k, v, _g, beta), _ = operands(32, "weak-decay", dims=dims)
    g = jnp.full((b, 32, hv), -2000.0)
    out = la.gated_delta_rule_chunked(q, k, v, g, beta, chunk)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, recurrence(q, k, v, g, beta), atol=1e-6)
    grads = jax.grad(lambda g: jnp.sum(
        la.gated_delta_rule_chunked(q, k, v, g, beta, chunk)))(g)
    assert bool(jnp.all(jnp.isfinite(grads)))


CFG = dict(
    hidden_size=32, linear_num_key_heads=HK, linear_num_value_heads=HV,
    linear_key_head_dim=DK, linear_value_head_dim=DV,
    linear_conv_kernel_dim=4, rms_norm_eps=1e-6)


def mixer_leaves(rng, hk, hv, dk, dv, vectors=True):
    qk, vz = hk * dk, hv * dv
    return {"in_qkvz": 0.3 * normal(rng, 32, 2 * qk + 2 * vz),
            "in_ba": 0.3 * normal(rng, 32, 2 * hv),
            "conv_w": 0.5 * normal(rng, 4, 2 * qk + vz),
            "A_log": (jnp.log(jnp.linspace(1.0, 16.0, hv)) if vectors
                      else jnp.zeros(hv)),
            "dt_bias": normal(rng, hv) if vectors else jnp.zeros(hv),
            "out_norm": (1 + 0.1 * normal(rng, dv) if vectors
                         else jnp.ones(dv)),
            "out_proj": 0.3 * normal(rng, vz, 32)}


@pytest.mark.parametrize("path,heads", [
    ("xla", (HK, HV, DK, DV)), ("kernel", (1, 2, WIDE, WIDE))],
    ids=["xla", "kernel"])
def test_deltanet_mixer_matches_reference(path, heads):
    """The whole mixer: projections, the bias-free convolution over q | k |
    v, the L2 norms, each key head serving two value heads, the gated
    per-head output norm; output and the gradient of every leaf."""
    hk, hv, dk, dv = heads
    assert la.gdn_path(2, 21, hk, hv, dk, dv, 8)["path"] == path
    rng = np.random.default_rng(3)
    p = mixer_leaves(rng, hk, hv, dk, dv)
    x, probe = normal(rng, 2, 21, 32), normal(rng, 2, 21, 32)
    cfg = dict(CFG, linear_num_key_heads=hk, linear_num_value_heads=hv,
               linear_key_head_dim=dk, linear_value_head_dim=dv)

    def ours(p, x):
        return la.gated_deltanet_mixer(
            p, x, key_heads=hk, value_heads=hv, key_dim=dk, value_dim=dv,
            chunk=8, eps=1e-6)

    def theirs(p, x):
        p = dict(p, **{"out_norm.g": p["out_norm"]})
        return ref.gdn_mixer(p, x, cfg, DOT)

    np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=2e-4, atol=2e-6)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x) * probe), (0, 1))(p, x)
    g_theirs = jax.grad(lambda p, x: jnp.sum(theirs(p, x) * probe), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_theirs)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-5 * (1 + float(jnp.max(jnp.abs(b)))))


def debug_lines(caplog, prefix, calls):
    logger.propagate = True
    try:
        with caplog.at_level(logging.DEBUG, logger=logger.name):
            results = [call() for call in calls]
    finally:
        logger.propagate = False
    return results, [r.getMessage() for r in caplog.records
                     if r.getMessage().startswith(prefix)]


def test_chunking_is_logged_once_a_shape_and_refuses_an_odd_chunk(caplog):
    la._log_chunking.cache_clear()
    (first, _, _), lines = debug_lines(caplog, "gdn_chunking", [
        lambda: la.gdn_chunking(16384, 64), lambda: la.gdn_chunking(16384, 64),
        lambda: la.gdn_chunking(100, 64)])
    assert len(lines) == 2, lines
    assert lines[0].startswith(
        "gdn_chunking seq=16384 chunk=64 chunks=256 segments=64 padded=0 "
        "inverse=")
    assert "6 factors" in lines[0] and "A^32" in lines[0]
    assert "seq=100 chunk=64 chunks=2 segments=1 padded=28" in lines[1]
    assert first["chunks"] == 256 and first["segments"] == 64
    # 33 chunks at 32 a segment are two segments of 17, one chunk of padding
    assert la.gdn_chunking(33 * 64, 64, 32)["chunks"] == 34
    # segments of whole blocks: 5 chunks of 8 at 16 a block are one segment
    # of 16, and 8 chunks a segment asked for become 16
    assert la.gdn_chunking(37, 8, 8, 16)["chunks"] == 16
    assert la.gdn_chunking(16384, 8, 8, 16)["padded"] == 0
    with pytest.raises(ValueError, match="power of two"):
        la.gdn_chunking(128, 48)


def test_the_path_is_chosen_from_the_shape_and_logged_once(caplog):
    """``gdn_path`` is the function that decides, and its debug line is the
    kernels' engagement counter: ``kernel`` at the benchmark cell's shape
    (with chunks a grid step, chunks a segment, states kept and how T is
    computed), ``xla`` with its reason at a toy width, under 8 rows a chunk
    and on several devices without a mesh."""
    la._log_path.cache_clear()
    cell = (2, 16384, 16, 32, 128, 128, 64)
    results, lines = debug_lines(caplog, "gdn_path", [
        lambda: la.gdn_path(*cell), lambda: la.gdn_path(*cell),
        lambda: la.gdn_path(B, 37, HK, HV, DK, DV, 8),
        lambda: la.gdn_path(2, 64, 16, 32, 128, 128, 4)])
    jax.device_count = lambda: 4        # the fixture puts it back
    several, more = debug_lines(
        caplog, "gdn_path", [lambda: la.gdn_path(8, *cell[1:])])
    assert len(lines) == 3 and len(more) == 4, lines + more
    assert lines[0].startswith(
        "gdn_path b=2 s=16384 heads=16/32 d=128/128 chunk=64 path=kernel "
        "chunks_a_step=8 chunks_a_segment=8 states=32 inverse='product of 6 "
        "factors")
    assert "float32 at highest precision" in lines[0]
    assert ("path=xla reason='head widths 8 / 16 do not fill 128-lane "
            "blocks' chunks_a_step=0 chunks_a_segment=3 states=2") in lines[1]
    assert "path=xla reason='chunk 4 is under 8 rows'" in lines[2]
    assert "path=xla reason='4 devices and no mesh" in more[3]
    assert [r["path"] for r in results] == ["kernel", "kernel", "xla", "xla"]
    assert results[0]["block"] == 2 and results[0]["padded"] == 0
    assert several[0]["path"] == "xla" and several[0]["segments"] == 64


@pytest.mark.parametrize("path,heads", [
    ("xla", (HK, HV, DK, DV)), ("kernel", (1, 2, WIDE, WIDE))],
    ids=["xla", "kernel"])
def test_backward_ops_of_the_rule_keep_the_delta_rule_scope(path, heads):
    """The per-layer metrics read device operations by ``/gdn_delta_rule/``
    in their scope path. The mixer enters that scope around the rule, outside
    anything the rule differentiates again, so it keeps its name in the
    backward pass: on the XLA form's hand-written backward over segments and
    on both kernels (interpret mode lowers a kernel to operations that carry
    its name where the chip has one custom call)."""
    import re

    hk, hv, dk, dv = heads
    assert la.gdn_path(1, 64, hk, hv, dk, dv, 8)["path"] == path
    rng = np.random.default_rng(3)
    p = mixer_leaves(rng, hk, hv, dk, dv, vectors=False)
    x = normal(rng, 1, 64, 32)

    def loss(p, x):
        return jnp.sum(la.gated_deltanet_mixer(
            p, x, key_heads=hk, value_heads=hv, key_dim=dk, value_dim=dv,
            chunk=8, eps=1e-6) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    ruled = [n for n in names if "delta_rule" in n]
    assert ruled and all("/gdn_delta_rule/" in n for n in ruled)
    assert any(n.startswith("jit(loss)/transpose(") for n in ruled)
    kernels = {k: [n for n in names if f"/{k}/" in n]
               for k in ("gdn_fwd", "gdn_bwd")}
    for kernel, ops in kernels.items():
        assert bool(ops) == (path == "kernel"), (kernel, path)
        assert all("/gdn_delta_rule/" + kernel in n for n in ops)
