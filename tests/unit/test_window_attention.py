"""The band inside the flash kernels (ops/attention.py, ``window=W`` beside
``causal``): the forward, the fused backward and the fallback pair against
``mha_reference`` under the dense band, values and gradients, in interpret
mode, over W below a sub-tile, between a sub-tile and a block, equal to a
block, above it and at or above S, at S of one block and of several; the
loops' bounds and the blocks a grid's inner axis holds against the dense band
by enumeration; the ``flash_tiling`` line at the benchmark cell's shape; and
the older callers and ``W >= S``, which trace to the programs they traced to
before."""

import importlib
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.utils.logging import logger

A = importlib.import_module("deepspeed_tpu.ops.attention")


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def dense(seq, window):
    return np.asarray(A.band_mask(seq, seq, window)) == 0


def test_the_band_by_hand():
    assert dense(6, 3).astype(int).tolist() == [
        [1, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0],
        [1, 1, 1, 0, 0, 0],
        [0, 1, 1, 1, 0, 0],
        [0, 0, 1, 1, 1, 0],
        [0, 0, 0, 1, 1, 1],
    ]
    for seq, window in ((64, 1), (64, 5), (96, 64), (128, 128), (64, 200)):
        w = min(window, seq)
        # W keys a query, but for the first W - 1 queries
        assert dense(seq, window).sum() == seq * w - w * (w - 1) // 2


SHAPES = [
    # S, W, sub_q, sub_k, block_q, block_k
    (256, 5, 16, 16, 64, 64), (256, 16, 16, 32, 64, 64),
    (256, 40, 32, 16, 64, 128), (256, 64, 16, 16, 64, 64),
    (256, 100, 16, 16, 128, 64), (256, 255, 32, 32, 64, 64),
    (192, 50, 16, 32, 32, 96), (128, 1, 16, 16, 32, 32),
]


@pytest.mark.parametrize("seq,window,sub_q,sub_k,block_q,block_k", SHAPES)
def test_loop_bounds_against_the_dense_band(
        seq, window, sub_q, sub_k, block_q, block_k):
    """A sub-tile the walk takes without a mask is wholly allowed, one it
    skips is empty and one it masks is neither, in both walks; the blocks
    that an inner axis holds for a block of the outer one are the ones the
    band touches, no more and no fewer."""
    allowed = dense(seq, window)
    nsk, nsq = block_k // sub_k, block_q // sub_q
    for q0 in range(0, seq, sub_q):
        for kb in range(0, seq, block_k):
            lo, a, b, hi = A._band_key_range(
                q0, sub_q, kb, sub_k, nsk, 0, window)
            assert 0 <= lo <= a <= b <= hi <= nsk
            for c in range(nsk):
                tile = allowed[q0:q0 + sub_q, kb + c * sub_k:kb + (c + 1) * sub_k]
                if a <= c < b:
                    assert tile.all()
                else:
                    assert tile.any() == (lo <= c < hi)
    for k0 in range(0, seq, sub_k):
        for qb in range(0, seq, block_q):
            lo, a, b, hi = A._band_query_range(
                k0, sub_k, qb, sub_q, nsq, 0, window)
            assert 0 <= lo <= a <= b <= hi <= nsq
            for r in range(nsq):
                tile = allowed[qb + r * sub_q:qb + (r + 1) * sub_q, k0:k0 + sub_k]
                if a <= r < b:
                    assert tile.all()
                else:
                    assert tile.any() == (lo <= r < hi)
    nq, nk = seq // block_q, seq // block_k
    band = (block_q, block_k, nq, nk, 0, window)
    live = np.array([[allowed[i * block_q:(i + 1) * block_q,
                              j * block_k:(j + 1) * block_k].any()
                      for j in range(nk)] for i in range(nq)])
    for iq in range(nq):
        first, last = A._band_blocks(iq, *band, False)
        assert np.flatnonzero(live[iq]).tolist() == list(range(first, last + 1))
    for ik in range(nk):
        first, last = A._band_blocks(ik, *band, True)
        assert np.flatnonzero(live[:, ik]).tolist() == list(
            range(first, last + 1))
    assert A._band_inner_steps(*band, False) == live.sum(1).max()
    assert A._band_inner_steps(*band, True) == live.sum(0).max()
    # the share of the square in visited sub-tiles, from the same bounds
    visited = sum(
        allowed[q0:q0 + sub_q, k0:k0 + sub_k].any()
        for q0 in range(0, seq, sub_q) for k0 in range(0, seq, sub_k))
    assert A._visited_share(
        seq, seq, block_k, sub_q, sub_k, True, window=window
    ) == visited * sub_q * sub_k / seq ** 2


def reference(q, k, v, window):
    k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    seq = q.shape[2]
    return A.mha_reference(q, k, v, mask=A.band_mask(seq, seq, window))


def kernels(q, k, v, window, blocks):
    k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    return A.flash_attention(
        q, k, v, causal=True, window=window, block_q=blocks[0],
        block_k=blocks[1])


@pytest.mark.parametrize("backward", ["fused", "pair"])
@pytest.mark.parametrize("seq", [256, 512])
@pytest.mark.parametrize("window", [50, 200, 256, 300, 600])
def test_kernels_against_the_dense_band(window, seq, backward, monkeypatch):
    """Values and the three gradients, 4 query heads on one kv head (the kv
    head's gradient is the sum over its query heads), 256-blocks in 128
    sub-tiles: one block a side and a 2 x 2 grid (an inner axis of as many
    steps as the band needs; one body a class of step, PR 46), every bound
    static and the walks unrolled in both; W 50 under a sub-tile, 200 between
    sub-tile and block, 256 a block, 300 above it, 600 past S (plain causal);
    ``pair``: the fallback kernels, by a VMEM budget that dq does not fit."""
    for name in ("SUB_QUERY_MAJOR", "SUB_KEY_MAJOR", "SUB_FUSED"):
        monkeypatch.setattr(A, name, 128)
    if backward == "pair":
        monkeypatch.setattr(A, "FUSED_DQ_VMEM_BUDGET", 1)
    blocks = (256, 256)
    plan = A.backward_plan(seq, seq, *blocks, True, lanes=16, itemsize=4,
                           window=window if window < seq else 0)
    assert (plan["backward"], plan["sub_q"], plan["sub_k"]) == (
        backward, 128, 128)
    rng = np.random.default_rng(window + seq)
    q = normal(rng, 1, 4, seq, 16)
    k, v = normal(rng, 1, 1, seq, 16), normal(rng, 1, 1, seq, 16)
    w = normal(rng, 1, 4, seq, 16)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    ours = jax.jit(lambda q, k, v: kernels(q, k, v, window, blocks))
    np.testing.assert_allclose(
        ours(q, k, v), reference(q, k, v, window), atol=2e-5, rtol=2e-5)
    got = jax.jit(jax.grad(loss(
        lambda q, k, v: kernels(q, k, v, window, blocks)), (0, 1, 2)))(q, k, v)
    want = jax.grad(loss(
        lambda q, k, v: reference(q, k, v, window)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window,blocks", [
    (5, (64, 64)), (24, (64, 128)), (100, (128, 32)), (64, (32, 32)),
    (100, (128, 128)), (150, (128, 128, 384)), (70, (64, 64, 256, "loop"))])
def test_kernels_on_uneven_grids(window, blocks, monkeypatch):
    """Blocks that differ each way and a band that divides nothing: grids of
    2 x 2, 3 x 3 (S 384) and 2 x 4 to 8 x 8 blocks, whose inner axes take 2 to
    5 steps; the walk static on each (PR 46), and once the ``loop`` that
    shapes with more classes of step keep, by a ceiling of no body."""
    blocks, seq, loop = blocks[:2], (*blocks, 256)[2], blocks[3:]
    if loop:
        monkeypatch.setattr(A, "MAX_WALK_BODIES", 0)
    tiling = A.flash_tiling(
        seq, seq, *blocks, True, lanes=16, itemsize=4, window=window)
    assert tiling["walk"] == tiling["backward"]["walk"] == (
        "loop" if loop else "static")
    assert tiling["steps"]["fetched"] == tiling["steps"]["run"]
    rng = np.random.default_rng(window)
    q, k, v, w = (normal(rng, 1, 2, seq, 16) for _ in range(4))
    np.testing.assert_allclose(
        kernels(q, k, v, window, blocks), reference(q, k, v, window),
        atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a, window, blocks) * w),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(reference(*a, window) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


def test_with_a_key_mask_too():
    """The validity column joins the band (padded documents): a row whose
    window holds no valid key reads zeros here and a mean there."""
    rng = np.random.default_rng(3)
    q, k, v = (normal(rng, 2, 2, 128, 16) for _ in range(3))
    valid = jnp.asarray(rng.random((2, 128)) < 0.8)
    both = A.band_mask(128, 128, 10)[None, None] + jnp.where(
        valid, 0.0, A.NEG_INF)[:, None, None, :]
    got = A.flash_attention(q, k, v, kv_mask=valid, causal=True, window=10,
                            block_q=32, block_k=32)
    want = A.mha_reference(q, k, v, mask=both)
    keeps = np.asarray(jnp.any(both > A.NEG_INF / 2, axis=-1))[:, 0].all(0)
    assert 64 < keeps.sum()
    np.testing.assert_allclose(
        np.asarray(got)[:, :, keeps], np.asarray(want)[:, :, keeps],
        atol=2e-5, rtol=2e-5)


def test_dispatcher_takes_the_xla_path_under_the_kernels_length():
    """Grouped-query heads, S 64: ``mha_reference``'s two comparisons."""
    rng = np.random.default_rng(5)
    q = normal(rng, 1, 4, 64, 8)
    k, v = normal(rng, 1, 2, 64, 8), normal(rng, 1, 2, 64, 8)
    np.testing.assert_allclose(
        A.attention(q, k, v, causal=True, window=7), reference(q, k, v, 7),
        atol=1e-6)


@pytest.mark.parametrize("kwargs,why", [
    (dict(causal=False), "under causal"),
    (dict(block_diffusion=4, causal=False), "under causal"),
    (dict(sk=64), "self-attention's square"), (dict(window=-3), "-3 keys")])
def test_refusals(kwargs, why):
    q = jnp.zeros((1, 1, 128, 8))
    k = jnp.zeros((1, 1, kwargs.get("sk", 128), 8))
    with pytest.raises(ValueError, match=why):
        A.flash_attention(
            q, k, k, causal=kwargs.get("causal", True),
            window=kwargs.get("window", 16),
            block_diffusion=kwargs.get("block_diffusion", 0))
    assert A.window_refusal(128, 128, True, 0) is None
    assert A.window_refusal(128, 128, True, 16) is None


def traced(fn, *shapes):
    """The jaxpr's text, kernels' bodies included, less the addresses of
    the functions it names."""
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*shapes)))


@pytest.mark.parametrize("form", ["causal", "key_mask", "block_diffusion"])
def test_older_callers_and_a_window_past_the_row_trace_as_before(
        form, monkeypatch):
    """``W >= S`` is plain causal, to the program: the forward and backward
    kernels' jaxprs, their grids and index maps are those of a call without
    ``window``; and no older form's program mentions a band (its grid is the
    whole ``nq x nk``, its walks have the old two spans)."""
    from deepspeed_tpu.utils import device

    monkeypatch.setattr(device, "on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1, 2, 2048, 128), jnp.bfloat16)
    valid = jax.ShapeDtypeStruct((1, 2048), jnp.int32)

    def grads(**kw):
        def loss(q, k, v, m):
            return A.flash_attention(
                q, k, v, kv_mask=m if form == "key_mask" else None,
                causal=form != "block_diffusion",
                block_diffusion=4 if form == "block_diffusion" else 0, **kw
            ).astype(jnp.float32).sum()

        return traced(jax.grad(loss, (0, 1, 2)), x, x, x, valid)

    plain = grads()
    assert plain == grads(window=0)
    assert "grid=(2, 2, 2)" in plain
    if form != "block_diffusion":
        assert plain == grads(window=2048) == grads(window=5000)
        banded = grads(window=1024)    # a 2 x 2 grid either way, other walks
        assert banded != plain and "grid=(2, 2, 2)" in banded
        assert "grid=(2, 2, 1)" in grads(window=1)


def test_visited_share_at_the_cell_shape_is_logged(monkeypatch):
    """micro 2 x 72 heads x 8,192 x 128 under W 512: 8 blocks a side; the
    forward (since PR 50; 512 square until then: 31 of 256, 12%) and the
    fused backward (since PR 46) in 256 square sub-tiles, 93 of 1,024 visited
    (a stripe's own and the two before), 9% of the square where the allowed
    pairs are 6% and a causal walk visits 52%; both walks static on the 8 x 2
    grid; the inner axes take 2 steps of 8."""
    tiling = A.flash_tiling(
        8192, 8192, 1024, 1024, True, lanes=128, window=512)
    assert tiling["visited_share"] == 93 / 1024
    assert tiling["backward"]["visited_share"] == 93 / 1024
    assert tiling["backward"]["backward"] == "fused"
    assert (tiling["sub_q"], tiling["sub_k"]) == (256, 256)
    assert (tiling["order"], tiling["chains"]) == ("key_major", 4)
    assert (tiling["backward"]["sub_q"], tiling["backward"]["sub_k"]) == (256, 256)
    assert A.flash_tiling(
        8192, 8192, 1024, 1024, True, lanes=128)["visited_share"] == 528 / 1024
    band = (1024, 1024, 8, 8, 0, 512)
    assert A._band_inner_steps(*band, False) == 2
    assert A._band_inner_steps(*band, True) == 2
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        A._log_tiling.cache_clear()
        A._log_layout.cache_clear()
        monkeypatch.setattr(jax, "device_count", lambda: 1)
        for heads, window in ((72, 512), (48, 0)):
            jax.eval_shape(
                lambda q, k: A.attention(q, k, k, causal=True, window=window),
                jax.ShapeDtypeStruct((2, heads, 8192, 128), jnp.bfloat16),
                jax.ShapeDtypeStruct((2, 8, 8192, 128), jnp.bfloat16))
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    banded, causal = [m for m in seen if m.startswith("flash_tiling")]
    # the mixers hand the kernels split [B, H, S, D] operands (the kv heads
    # repeated first): one layout line a head count
    assert [m for m in seen if m.startswith("attention_layout")] == [
        f"attention_layout b=2 s=8192 heads={heads} d=128 layout=split "
        "heads_a_block=1 reason='q, k and v arrive as separate [B, H, S, D] "
        "arrays'" for heads in (72, 48)]
    assert " visited_share=0.0908" in banded
    assert "bwd_visited_share=0.0908" in banded
    assert banded.count("walk=static bodies=2 steps=15/1/15 ") == 2
    assert "backward=fused" in banded and banded.endswith("window=512")
    assert " visited_share=0.5156" in causal and "window" not in causal
