"""The flash kernels at UNEQUAL head widths (ops/attention.py: q and k of Dqk
lanes, v, the context, dO and dv of Dv), in interpret mode on the CPU against
``mha_reference``: the forward, ``lse``, and dq / dk / dv of the fused backward
and of the pair, at a latent mixer's 192 / 128 and at one other pair of widths,
on one block and on a grid of blocks; the dispatcher and the sharded route take
them; and a caller with equal widths gets the program it got before."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

att = importlib.import_module("deepspeed_tpu.ops.attention")


def operands(seed, b, h, s, dqk, dv, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(
        jax.random.normal(k, (b, h, s, d), jnp.float32).astype(dtype)
        for k, d in zip(keys, (dqk, dqk, dv, dv)))


# float32 operands through float32 accumulation: what is left is the order of
# the sums (blocks of keys against one softmax row), a few ulp of numbers of
# size 1; bf16 products where float32 is stated would read 1e-2
TIGHT = dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [1024, 128, 64])
@pytest.mark.parametrize("dqk,dv", [(192, 128), (48, 16), (64, 128)])
def test_forward_and_gradients_against_the_reference(dqk, dv, block):
    """``block`` 1024: one block each way; 128 and 64: grids of 2 x 2 and
    4 x 4 (a body ON the diagonal and one UNDER it, the causal skip of whole
    blocks); the walk static on all three."""
    q, k, v, g = operands(dqk + dv, 2, 2, 256, dqk, dv)
    tiling = att.flash_tiling(
        256, 256, min(block, 256), min(block, 256), True, lanes=dqk, itemsize=4)
    assert tiling["walk"] == tiling["backward"]["walk"] == "static"

    def ours(q, k, v):
        return jnp.sum(att.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block) * g)

    def theirs(q, k, v):
        return jnp.sum(att.mha_reference(q, k, v, causal=True) * g)

    out = att.flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    assert out.shape == (2, 2, 256, dv)
    np.testing.assert_allclose(
        out, att.mha_reference(q, k, v, causal=True), **TIGHT)
    got = jax.grad(ours, (0, 1, 2))(q, k, v)
    want = jax.grad(theirs, (0, 1, 2))(q, k, v)
    for a, r, like in zip(got, want, (q, k, v)):
        assert a.shape == like.shape
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(a / scale, r / scale, atol=2e-5)


@pytest.mark.parametrize("block", [128, 64, 32])
def test_lse_and_residuals_follow_the_value_width(block):
    """``lse`` is the scores' log-sum-exp at 1 / sqrt(Dqk) (one float32 a
    query row); the named residual ``flash_out`` has v's width. On one block
    and on grids of 2 x 2 and 4 x 4."""
    q, k, v, _ = operands(3, 1, 2, 128, 192, 128)
    out, residuals = att._flash_fwd(
        q, k, v, None, jnp.int32(0), True, 192 ** -0.5, 0.0, block, block)
    lse = residuals[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 192 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), scores, -1e30)
    np.testing.assert_allclose(
        lse.reshape(1, 2, 128), jax.nn.logsumexp(scores, -1), **TIGHT)
    assert residuals[-2].shape == out.shape == (1, 2, 128, 128)


@pytest.mark.parametrize("sq,sk", [(256, 256), (512, 256)])
def test_the_pair_of_backward_kernels_takes_them_too(sq, sk, monkeypatch):
    """A budget of 0 forces ``flash_bwd_dq`` + ``flash_bwd_dkv``; on a 2 x 2
    grid, and on 4 x 2 with the diagonal two blocks down."""
    q, _, _, g = operands(5, 1, 2, sq, 192, 128)
    _, k, v, _ = operands(6, 1, 2, sk, 192, 128)

    def loss(q, k, v):
        return jnp.sum(att.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128) * g)

    fused = jax.grad(loss, (0, 1, 2))(q, k, v)
    monkeypatch.setattr(att, "FUSED_DQ_VMEM_BUDGET", 0)
    pair = jax.grad(loss, (0, 1, 2))(q, k, v)
    for a, b in zip(fused, pair):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_key_mask_and_bf16_operands():
    """A padding mask beside the unequal widths, and bf16 operands: the
    kernels round p and ds to bf16 before their products, which reads 2e-2 of
    the largest entry (the equal-width kernels' own tolerance in
    test_flash_attention.py)."""
    q, k, v, g = operands(7, 2, 2, 256, 192, 128, jnp.bfloat16)
    valid = (jnp.arange(256)[None, :] < jnp.array([[256], [200]])).astype(
        jnp.int32)
    bias = jnp.where(valid > 0, 0.0, att.NEG_INF)[:, None, None, :]

    def f32(t):
        return t.astype(jnp.float32)

    def ours(q, k, v):
        return jnp.sum(f32(att.flash_attention(
            q, k, v, kv_mask=valid, block_q=128, block_k=128)) * f32(g))

    def theirs(q, k, v):
        return jnp.sum(f32(att.mha_reference(q, k, v, mask=bias)) * f32(g))

    got = jax.grad(ours, (0, 1, 2))(q, k, v)
    want = jax.grad(theirs, (0, 1, 2))(q, k, v)
    for a, r in zip(got, want):
        scale = float(jnp.max(jnp.abs(f32(r))))
        np.testing.assert_allclose(f32(a) / scale, f32(r) / scale, atol=2e-2)


def test_the_dispatcher_and_the_sharded_route_take_unequal_widths(monkeypatch):
    from deepspeed_tpu.parallel.mesh import build_mesh

    q, k, v, _ = operands(9, 2, 2, 256, 48, 16)
    want = att.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        att.attention(q, k, v, causal=True), want, **TIGHT)
    mesh = build_mesh(devices=jax.devices()[:2])
    monkeypatch.setattr(att, "FLASH_MODE", "always")
    text = jax.jit(lambda q, k, v: att.attention(
        q, k, v, causal=True, mesh=mesh)).lower(q, k, v).as_text()
    assert "shard_map" in text or "sdy.manual_computation" in text
    np.testing.assert_allclose(
        att.attention(q, k, v, causal=True, mesh=mesh), want, **TIGHT)


def test_equal_widths_lower_to_the_program_they_had():
    """With v as wide as q the operands carry no second width and every
    BlockSpec, scratch and result shape is the one the kernels had: the
    lowered text of a forward + backward at 128 / 128 holds no shape of 192
    (tests/unit/test_looped_stack.py pins the five hybrid configurations'
    whole programs on the parent's code)."""
    q, k, v, g = operands(11, 1, 2, 256, 128, 128)
    ops = att._split_operands(q, v)
    assert ops.v_width == ops.head_dim == ops.v_lanes == ops.block_lanes == 128
    plain = att._Operands(1, 2, 128, packed=False)
    assert (plain.v_width, plain.v_lanes) == (128, 128)
    for made in (ops, plain):
        assert made.result(256, jnp.float32, v=True) == made.result(
            256, jnp.float32)

    def step(q, k, v, g):
        return jax.grad(lambda q, k, v: jnp.sum(att.flash_attention(
            q, k, v, causal=True) * g), (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(q, k, v, g).as_text()
    assert "x192" not in text and "192x" not in text
    unequal = att._split_operands(*operands(11, 1, 2, 256, 192, 128)[1:3])
    assert (unequal.head_dim, unequal.v_width) == (192, 128)
    assert unequal.result(256, jnp.float32, v=True).shape == (2, 256, 128)
    assert unequal.result(256, jnp.float32).shape == (2, 256, 192)
