"""The block-diffusion mask inside the flash kernels (ops/attention.py,
``block_diffusion=B``): the forward, the fused backward and the fallback
pair against ``mha_reference`` under the dense mask, values and gradients,
in interpret mode; the loops' bounds and the blocks the index maps ask for
against the dense mask by enumeration; the visited share in the
``flash_tiling`` log line at the benchmark cell's shape; and the causal and
key-mask callers, which take the same bounds as before."""

import importlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.utils.logging import logger

A = importlib.import_module("deepspeed_tpu.ops.attention")


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def dense(seq, block):
    return np.asarray(A.block_diffusion_mask(seq, block)) == 0


def test_the_mask_by_hand():
    # L 4 in blocks of 2: rows and keys are [n0 n1 n2 n3 ; c0 c1 c2 c3]
    assert dense(8, 2).astype(int).tolist() == [
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 1, 1, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 1, 1],
        [0, 0, 0, 0, 1, 1, 1, 1],
    ]
    for half, block in ((64, 4), (64, 32), (96, 8)):
        # L^2 + L B pairs: a quarter of the square, and B / 4 L more
        assert dense(2 * half, block).sum() == half * half + half * block


SHAPES = [
    # L, B, sub_q, sub_k, block_q, block_k
    (64, 4, 16, 16, 32, 32), (64, 4, 8, 16, 32, 64), (64, 32, 16, 8, 64, 32),
    (128, 32, 32, 32, 64, 64), (96, 4, 16, 32, 32, 96), (64, 8, 16, 4, 16, 16),
]


@pytest.mark.parametrize("half,block,sub_q,sub_k,block_q,block_k", SHAPES)
def test_loop_bounds_against_the_dense_mask(
        half, block, sub_q, sub_k, block_q, block_k):
    """A sub-tile the walk takes without a mask is wholly allowed, one it
    skips is empty, in both walks; a block a grid step skips is empty and
    the block its index map asks for instead is one the walk needs."""
    seq, allowed = 2 * half, dense(2 * half, block)
    nsk, nsq = block_k // sub_k, block_q // sub_q
    for q0 in range(0, seq, sub_q):
        for kb in range(0, seq, block_k):
            lo, n_full, hi = A._bd_key_range(
                q0, sub_q, kb, sub_k, nsk, half, block)
            assert 0 <= lo <= n_full <= hi <= nsk
            for c in range(nsk):
                tile = allowed[q0:q0 + sub_q, kb + c * sub_k:kb + (c + 1) * sub_k]
                if lo <= c < n_full:
                    assert tile.all()
                elif not n_full <= c < hi:
                    assert not tile.any()
    for k0 in range(0, seq, sub_k):
        for qb in range(0, seq, block_q):
            lo, full, hi = A._bd_query_range(
                k0, sub_k, qb, sub_q, nsq, half, block)
            assert 0 <= lo <= full <= hi <= nsq
            for r in range(nsq):
                tile = allowed[qb + r * sub_q:qb + (r + 1) * sub_q, k0:k0 + sub_k]
                if full <= r < hi:
                    assert tile.all()
                elif not lo <= r < full:
                    assert not tile.any()
    nq, nk = seq // block_q, seq // block_k
    live = np.array([[allowed[i * block_q:(i + 1) * block_q,
                              j * block_k:(j + 1) * block_k].any()
                      for j in range(nk)] for i in range(nq)])
    for iq in range(nq):
        for ik in range(nk):
            key = A._bd_key_block(iq, ik, block_q, block_k, half, block)
            query = A._bd_query_block(ik, iq, block_q, block_k, half, block)
            assert live[iq, key] and live[query, ik]
            if live[iq, ik]:
                assert (key, query) == (ik, iq)
    # a skipped step holds its neighbour's block: no more distinct blocks
    # are asked for along a walk than it needs (+1 where it needs none)
    for iq in range(nq):
        asked = [A._bd_key_block(iq, ik, block_q, block_k, half, block)
                 for ik in range(nk)]
        assert len(set(asked)) == live[iq].sum()
    for ik in range(nk):
        asked = [A._bd_query_block(ik, iq, block_q, block_k, half, block)
                 for iq in range(nq)]
        assert len(set(asked)) == live[:, ik].sum()


def reference(q, k, v, block):
    k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    return A.mha_reference(
        q, k, v, mask=A.block_diffusion_mask(q.shape[2], block))


def kernels(q, k, v, block, blocks):
    k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    return A.flash_attention(
        q, k, v, block_diffusion=block, block_q=blocks[0], block_k=blocks[1])


@pytest.mark.parametrize("backward", ["fused", "pair"])
@pytest.mark.parametrize("block,blocks", [
    (4, (64, 64)), (32, (64, 64)), (4, (32, 64)), (32, (128, 32)),
    (4, (128, 128)), (64, (128, 64)), (32, (64, 64, "loop"))])
def test_kernels_against_the_dense_mask(block, blocks, backward, monkeypatch):
    """Values and the three gradients, 8 query heads on one kv head (the
    kv head's gradient is the sum over its query heads), L 128 = 32 or 4
    blocks a side, on grids of 2 x 2 and 4 x 4 to 2 x 8 blocks; ``pair``: the
    fallback kernels, by a VMEM budget that dq does not fit. Every grid's
    walk is static (one body a class of step, PR 46); ``loop``: the walk
    that shapes with more classes keep, by a ceiling of no body."""
    if backward == "pair":
        monkeypatch.setattr(A, "FUSED_DQ_VMEM_BUDGET", 1)
    if blocks[2:]:
        monkeypatch.setattr(A, "MAX_WALK_BODIES", 0)
    walk = "loop" if blocks[2:] else "static"
    blocks = blocks[:2]
    tiling = A.flash_tiling(
        256, 256, *blocks, False, lanes=16, itemsize=4, block_diffusion=block)
    assert tiling["walk"] == tiling["backward"]["walk"] == walk
    assert tiling["steps"]["fetched"] == tiling["steps"]["run"]
    rng = np.random.default_rng(block + blocks[0])
    q = normal(rng, 1, 8, 256, 16)
    k, v = normal(rng, 1, 1, 256, 16), normal(rng, 1, 1, 256, 16)
    w = normal(rng, 1, 8, 256, 16)
    plan = A.backward_plan(256, 256, *blocks, False, lanes=16, itemsize=4,
                           block_diffusion=block)
    assert plan["backward"] == backward

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    ours = jax.jit(lambda q, k, v: kernels(q, k, v, block, blocks))
    np.testing.assert_allclose(
        ours(q, k, v), reference(q, k, v, block), atol=2e-5, rtol=2e-5)
    got = jax.jit(jax.grad(loss(
        lambda q, k, v: kernels(q, k, v, block, blocks)), (0, 1, 2)))(q, k, v)
    want = jax.grad(loss(
        lambda q, k, v: reference(q, k, v, block)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


def test_with_a_key_mask_too():
    """The validity column joins the structural mask (padded documents)."""
    rng = np.random.default_rng(3)
    q, k, v = (normal(rng, 2, 2, 128, 16) for _ in range(3))
    valid = jnp.asarray(rng.random((2, 128)) < 0.8).at[:, :4].set(True)
    valid = valid.at[:, 64:68].set(True)   # a noisy row keeps a key
    both = A.block_diffusion_mask(128, 4)[None, None] + jnp.where(
        valid, 0.0, A.NEG_INF)[:, None, None, :]
    got = A.flash_attention(q, k, v, kv_mask=valid, block_diffusion=4,
                            block_q=32, block_k=32)
    want = A.mha_reference(q, k, v, mask=both)
    # a row that keeps no key reads zeros here and a mean there
    keeps = np.asarray(jnp.any(both > A.NEG_INF / 2, axis=-1))[:, 0].all(0)
    assert keeps.sum() > 64
    np.testing.assert_allclose(
        np.asarray(got)[:, :, keeps], np.asarray(want)[:, :, keeps],
        atol=2e-5, rtol=2e-5)


def test_dispatcher_takes_the_xla_path_under_the_kernels_length():
    rng = np.random.default_rng(5)
    q = normal(rng, 1, 4, 64, 8)
    k, v = normal(rng, 1, 2, 64, 8), normal(rng, 1, 2, 64, 8)
    np.testing.assert_allclose(
        A.attention(q, k, v, block_diffusion=4), reference(q, k, v, 4),
        atol=1e-6)


@pytest.mark.parametrize("kwargs,why", [
    (dict(causal=True), "not causal"), (dict(block=3), "power of two"),
    (dict(seq=96, block=32), "divides L"), (dict(sk=64), "2 L positions")])
def test_refusals(kwargs, why):
    seq, sk = kwargs.get("seq", 128), kwargs.get("sk", kwargs.get("seq", 128))
    q, k = jnp.zeros((1, 1, seq, 8)), jnp.zeros((1, 1, sk, 8))
    with pytest.raises(ValueError, match=why):
        A.flash_attention(
            q, k, k, causal=kwargs.get("causal", False),
            block_diffusion=kwargs.get("block", 4))


def test_visited_share_at_the_cell_shape_is_logged():
    """micro 2 x 32 heads x (2 x 8,192) x 128: 16 blocks a side; the forward
    (since PR 50; 512 square until then: 288 of 1,024) and the fused backward
    (since PR 46) in 256 square sub-tiles, 1,088 of 4,096 visited: 27% of the
    (2L)^2 square, where a causal walk over 2L visits 51%; both walks static
    on the 16 x 16 grid, the forward's steps key-major over four chains."""
    tiling = A.flash_tiling(
        16384, 16384, 1024, 1024, False, lanes=128, block_diffusion=4)
    assert tiling["visited_share"] == 1088 / 4096
    assert (tiling["sub_q"], tiling["sub_k"]) == (256, 256)
    assert (tiling["order"], tiling["chains"]) == ("key_major", 4)
    assert tiling["backward"]["visited_share"] == 1088 / 4096
    assert (tiling["backward"]["sub_q"], tiling["backward"]["sub_k"]) == (256, 256)
    assert tiling["backward"]["backward"] == "fused"
    assert A.flash_tiling(
        16384, 16384, 1024, 1024, True, lanes=128)["visited_share"] == 2080 / 4096
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        A._log_tiling.cache_clear()
        jax.eval_shape(
            lambda q: A.flash_attention(q, q, q, block_diffusion=4),
            jax.ShapeDtypeStruct((2, 32, 16384, 128), jnp.bfloat16))
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    line, = [m for m in seen if m.startswith("flash_tiling")]
    assert " visited_share=0.2656" in line and "bwd_visited_share=0.2656" in line
    assert " order=key_major chains=4 " in line
    assert line.count("walk=static bodies=3 steps=80/176/80 ") == 2
    assert "backward=fused" in line and line.endswith("block_diffusion=4")


@pytest.mark.parametrize("causal,use_mask", [(True, False), (False, True)])
def test_causal_and_key_mask_callers_unchanged(causal, use_mask):
    """The older forms against the XLA path, as before; and their log line
    carries no new field."""
    rng = np.random.default_rng(11)
    q, k, v = (normal(rng, 1, 2, 128, 16) for _ in range(3))
    valid = jnp.asarray(rng.random((1, 128)) < 0.7).at[:, 0].set(True)
    mask = jnp.where(valid, 0.0, A.NEG_INF)[:, None, None, :]

    def ours(q, k, v):
        return A.flash_attention(
            q, k, v, kv_mask=valid if use_mask else None, causal=causal,
            block_q=64, block_k=32)

    def theirs(q, k, v):
        return A.mha_reference(
            q, k, v, mask=mask if use_mask else None, causal=causal)

    np.testing.assert_allclose(ours(q, k, v), theirs(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) ** 2), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) ** 2), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)
    assert A._visited_share(1024, 1024, 1024, 512, 512, True) == 0.75
    assert A._visited_share(512, 512, 512, 512, 512, False) == 1.0


@pytest.fixture(scope="module")
def v5e():
    """A described, not attached, v5e (on-chip-measurement guide, section
    2): what Mosaic refuses here the chip refuses too."""
    import os

    from jax.experimental import topologies

    load_env = {"TPU_LOG_DIR": "disabled", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    saved = {k: os.environ.get(k) for k in load_env}
    os.environ.update(load_env)
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_kernels_compile_for_v5e_at_the_cell_shape(v5e, monkeypatch):
    """Forward and the fused backward at [2, 32, 2 x 8192, 128] bf16 under
    the mask, the index maps that hold a neighbour's block included: two
    Mosaic kernels under the names ``flash_ms.train`` sums."""
    import re

    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.utils import device

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    operand = jax.ShapeDtypeStruct(
        (2, 32, 16384, 128), jnp.bfloat16, sharding=SingleDeviceSharding(v5e))

    def loss(q, k, v):
        return A.attention(q, k, v, block_diffusion=4).astype(jnp.float32).sum()

    try:
        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            operand, operand, operand).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    calls = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
             for line in text.splitlines() if "tpu_custom_call" in line]
    kernels = sorted(
        re.search(r"flash_(?:fwd|bwd_dq|bwd_dkv)(?![a-z])", c).group(0)
        for c in calls)
    assert kernels == ["flash_bwd_dkv", "flash_fwd"], calls
