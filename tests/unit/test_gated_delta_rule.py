"""The chunked gated delta rule (ops/linear_attention.py) against the
per-position recurrence of its plain reference
(benchmark/reference/qwen3_next.py:delta_rule), values and every gradient,
at toy size on the CPU; the triangular inverse alone; the whole Gated
DeltaNet mixer against the reference's; the ``gdn_chunking`` log line."""

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
DOT = ref_ops.make_dot("float32")
# (scale of the log decay, shift of beta's logit): weak and strong decay,
# beta near 0 and near 1
REGIMES = {"weak-decay": (-0.01, 0.0), "strong-decay": (-8.0, 0.0),
           "beta-near-1": (-1.0, 6.0), "beta-near-0": (-1.0, -6.0)}


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def operands(seq, regime, seed=0):
    rng = np.random.default_rng(seed)
    decay, shift = REGIMES[regime]
    q = la.l2_normalise(normal(rng, B, seq, HK, DK)) * DK ** -0.5
    k = la.l2_normalise(normal(rng, B, seq, HK, DK))
    v = normal(rng, B, seq, HV, DV)
    g = decay * jnp.abs(normal(rng, B, seq, HV))
    beta = jax.nn.sigmoid(normal(rng, B, seq, HV) + shift)
    return (q, k, v, g, beta), normal(rng, B, seq, HV, DV)


def recurrence(q, k, v, g, beta):
    r = HV // HK
    return ref.delta_rule(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta)


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize(
    "seq,chunk,segment", [(37, 8, 32), (64, 16, 2), (5, 8, 32), (48, 64, 32),
                          (70, 8, 4)])
def test_chunked_delta_rule_matches_the_recurrence(seq, chunk, segment, regime):
    """Values and the gradient of every operand (q, k, v, the log decay and
    beta); 37 and 5 are no multiples of the chunk and 48 is under it; 64 is
    two segments of two chunks, 70 three segments of three with two chunks
    of padding, each handing its state on."""
    args, probe = operands(seq, regime)

    def ours(*a):
        return jnp.sum(
            la.gated_delta_rule_chunked(*a, chunk, segment) * probe)

    def theirs(*a):
        return jnp.sum(recurrence(*a) * probe)

    np.testing.assert_allclose(
        la.gated_delta_rule_chunked(*args, chunk, segment), recurrence(*args),
        rtol=2e-4, atol=2e-5)
    g_ours = jax.grad(ours, argnums=range(5))(*args)
    g_theirs = jax.grad(theirs, argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), g_ours, g_theirs):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))),
            err_msg=name)


@pytest.mark.parametrize("size", [1, 2, 8, 64])
def test_unit_lower_inverse_and_its_backward(size):
    """Against forward substitution (``solve_triangular``), values and the
    gradient; entries of the delta rule's size (|k . k| <= 1, decayed)."""
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


def test_state_decays_to_nothing_under_a_strong_decay_without_overflow():
    """exp(-2000) a step: every decay underflows to 0, nothing is inf or
    nan, and each output reads its own position only."""
    (q, k, v, _g, beta), _ = operands(32, "weak-decay")
    g = jnp.full((B, 32, HV), -2000.0)
    out = la.gated_delta_rule_chunked(q, k, v, g, beta, 8)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, recurrence(q, k, v, g, beta), atol=1e-6)
    grads = jax.grad(lambda g: jnp.sum(
        la.gated_delta_rule_chunked(q, k, v, g, beta, 8)))(g)
    assert bool(jnp.all(jnp.isfinite(grads)))


CFG = dict(
    hidden_size=32, linear_num_key_heads=HK, linear_num_value_heads=HV,
    linear_key_head_dim=DK, linear_value_head_dim=DV,
    linear_conv_kernel_dim=4, rms_norm_eps=1e-6)


def test_deltanet_mixer_matches_reference():
    """The whole mixer: projections, the bias-free convolution over q | k |
    v, the L2 norms, each key head serving two value heads, the gated
    per-head output norm; output and the gradient of every leaf."""
    rng = np.random.default_rng(3)
    qk, vz = HK * DK, HV * DV
    p = {"in_qkvz": 0.3 * normal(rng, 32, 2 * qk + 2 * vz),
         "in_ba": 0.3 * normal(rng, 32, 2 * HV),
         "conv_w": 0.5 * normal(rng, 4, 2 * qk + vz),
         "A_log": jnp.log(jnp.linspace(1.0, 16.0, HV)),
         "dt_bias": normal(rng, HV), "out_norm": 1 + 0.1 * normal(rng, DV),
         "out_proj": 0.3 * normal(rng, vz, 32)}
    x, probe = normal(rng, 2, 21, 32), normal(rng, 2, 21, 32)

    def ours(p, x):
        return la.gated_deltanet_mixer(
            p, x, key_heads=HK, value_heads=HV, key_dim=DK, value_dim=DV,
            chunk=8, eps=1e-6)

    def theirs(p, x):
        p = dict(p, **{"out_norm.g": p["out_norm"]})
        return ref.gdn_mixer(p, x, CFG, DOT)

    np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=2e-4, atol=2e-6)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x) * probe), (0, 1))(p, x)
    g_theirs = jax.grad(lambda p, x: jnp.sum(theirs(p, x) * probe), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_theirs)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-5 * (1 + float(jnp.max(jnp.abs(b)))))


def test_chunking_is_logged_once_a_shape_and_refuses_an_odd_chunk(caplog):
    la._log_chunking.cache_clear()
    logger.propagate = True
    try:
        with caplog.at_level(logging.DEBUG, logger=logger.name):
            first = la.gdn_chunking(16384, 64)
            la.gdn_chunking(16384, 64)
            la.gdn_chunking(100, 64)
    finally:
        logger.propagate = False
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("gdn_chunking")]
    assert len(lines) == 2, lines
    assert lines[0].startswith(
        "gdn_chunking seq=16384 chunk=64 chunks=256 segments=64 padded=0 "
        "inverse=")
    assert "6 factors" in lines[0] and "A^32" in lines[0]
    assert "seq=100 chunk=64 chunks=2 segments=1 padded=28" in lines[1]
    assert first["chunks"] == 256 and first["segments"] == 64
    # 33 chunks at 32 a segment are two segments of 17, one chunk of padding
    assert la.gdn_chunking(33 * 64, 64, 32)["chunks"] == 34
    with pytest.raises(ValueError, match="power of two"):
        la.gdn_chunking(128, 48)


def test_backward_ops_of_the_segments_keep_the_delta_rule_scope():
    """The per-layer metrics read device operations by ``/gdn_delta_rule/``
    in their scope path. The segments' hand-written backward differentiates
    the segment body again, and a trace renames the first scope entered in a
    differentiated function (``jvp(...)``): the body runs under
    ``gdn_segment`` so that the rule's own scope keeps its name there."""
    import re

    rng = np.random.default_rng(3)
    qk, vz = HK * DK, HV * DV
    p = {"in_qkvz": 0.3 * normal(rng, 32, 2 * qk + 2 * vz),
         "in_ba": 0.3 * normal(rng, 32, 2 * HV),
         "conv_w": 0.5 * normal(rng, 4, 2 * qk + vz),
         "A_log": jnp.zeros(HV), "dt_bias": jnp.zeros(HV),
         "out_norm": jnp.ones(DV), "out_proj": 0.3 * normal(rng, vz, 32)}
    x = normal(rng, 1, 64, 32)

    def loss(p, x):
        return jnp.sum(la.gated_deltanet_mixer(
            p, x, key_heads=HK, value_heads=HV, key_dim=DK, value_dim=DV,
            chunk=8, eps=1e-6) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if "delta_rule" in n]
    assert names and all("/gdn_delta_rule/" in n for n in names)
    assert any("jvp(gdn_segment)/gdn_delta_rule/" in n for n in names)
