"""Transformer layer + attention + model tests.

The analog of the reference's tests/unit/test_cuda_forward.py /
test_cuda_backward.py: numerical parity of the fused layer against a naive
baseline across batch/seq/pre-post-LN grids, in fwd and bwd.
"""

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import flash_attention, mha_reference
from deepspeed_tpu.ops.transformer import (
    DeepSpeedTransformerConfig,
    DeepSpeedTransformerLayer,
)

# the layer tests are compile-heavy and excluded from `make test-fast`; the
# flash-kernel tests (interpret mode, seconds each) run in it
slow = pytest.mark.slow


def naive_layer_forward(params, x, cfg, causal=False, mask=None):
    """Hand-written baseline of the same block (the 'vendored BertEncoder'
    role from the reference parity tests)."""

    def ln(t, w, b):
        t32 = t.astype(jnp.float32)
        mu = t32.mean(-1, keepdims=True)
        var = t32.var(-1, keepdims=True)
        return ((t32 - mu) / jnp.sqrt(var + cfg.layer_norm_eps)) * w + b

    H, heads = cfg.hidden_size, cfg.heads
    hd = H // heads
    b, s, _ = x.shape
    residual = x
    h = ln(x, params["attn_nw"], params["attn_nb"]) if cfg.pre_layer_norm else x
    qkv = h @ params["attn_qkvw"] + params["attn_qkvb"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads_split(t):
        return t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)

    ctx = mha_reference(
        heads_split(q), heads_split(k), heads_split(v), causal=causal, mask=mask
    )
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, H)
    attn_out = ctx @ params["attn_ow"] + params["attn_ob"]
    x1 = residual + attn_out
    if not cfg.pre_layer_norm:
        x1 = ln(x1, params["attn_nw"], params["attn_nb"])
    residual = x1
    h = ln(x1, params["norm_w"], params["norm_b"]) if cfg.pre_layer_norm else x1
    h = h @ params["inter_w"] + params["inter_b"]
    h = nn.gelu(h, approximate=True)
    h = h @ params["output_w"] + params["output_b"]
    x2 = residual + h
    if not cfg.pre_layer_norm:
        x2 = ln(x2, params["norm_w"], params["norm_b"])
    return x2


@slow
@pytest.mark.parametrize("pre_ln", [True, False])
@pytest.mark.parametrize("batch,seq", [(2, 64), (1, 128)])
def test_layer_parity_forward(pre_ln, batch, seq):
    cfg = DeepSpeedTransformerConfig(
        hidden_size=64, heads=4, attn_dropout_ratio=0.0,
        hidden_dropout_ratio=0.0, pre_layer_norm=pre_ln,
    )
    layer = DeepSpeedTransformerLayer(config=cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, seq, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x, train=False)["params"]
    out = layer.apply({"params": params}, x, train=False)
    ref = naive_layer_forward(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@slow
@pytest.mark.parametrize("pre_ln", [True, False])
def test_layer_parity_backward(pre_ln):
    cfg = DeepSpeedTransformerConfig(
        hidden_size=64, heads=4, attn_dropout_ratio=0.0,
        hidden_dropout_ratio=0.0, pre_layer_norm=pre_ln,
    )
    layer = DeepSpeedTransformerLayer(config=cfg)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 64, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x, train=False)["params"]

    def loss_ds(p):
        return jnp.sum(layer.apply({"params": p}, x, train=False) ** 2)

    def loss_ref(p):
        return jnp.sum(naive_layer_forward(p, x, cfg) ** 2)

    g1 = jax.grad(loss_ds)(params)
    g2 = jax.grad(loss_ref)(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g1[k]), np.asarray(g2[k]), rtol=2e-3, atol=2e-3,
            err_msg=f"grad mismatch for {k}",
        )


@slow
def test_stochastic_mode_changes_bf16_path_and_warns():
    """stochastic_mode must be a real behavior change (reference builds a
    distinct relaxed kernel, setup.py:44-118), announced at rank 0 — never
    a silent no-op: under bf16 the LN statistics stay in bf16, so outputs
    differ from the default fp32-stat path while remaining close."""
    from deepspeed_tpu.ops import transformer as tr

    base = dict(
        hidden_size=64, heads=4, attn_dropout_ratio=0.0,
        hidden_dropout_ratio=0.0,
    )
    layer_d = DeepSpeedTransformerLayer(
        config=DeepSpeedTransformerConfig(**base)
    )
    layer_s = DeepSpeedTransformerLayer(
        config=DeepSpeedTransformerConfig(stochastic_mode=True, **base)
    )
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 64, 64)), jnp.bfloat16)
    params = layer_d.init(jax.random.PRNGKey(0), x, train=False)["params"]

    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    ds_logger.addHandler(handler)
    tr._STOCHASTIC_NOTICED[0] = False
    try:
        out_s = layer_s.apply({"params": params}, x, train=False)
    finally:
        ds_logger.removeHandler(handler)
    assert any("stochastic_mode" in m for m in records)
    out_d = layer_d.apply({"params": params}, x, train=False)
    a, b = np.asarray(out_d, np.float32), np.asarray(out_s, np.float32)
    assert not np.array_equal(a, b), "stochastic_mode must not be a no-op"
    np.testing.assert_allclose(a, b, rtol=0.1, atol=0.1)


@slow
def test_stochastic_mode_fp16_keeps_fp32_statistics():
    """fp16's narrow range (max 65504; eps underflow) must NOT take the
    relaxed path: outputs stay bit-identical to the default, and large
    activations don't overflow the variance."""
    base = dict(
        hidden_size=64, heads=4, attn_dropout_ratio=0.0,
        hidden_dropout_ratio=0.0,
    )
    layer_d = DeepSpeedTransformerLayer(
        config=DeepSpeedTransformerConfig(**base)
    )
    layer_s = DeepSpeedTransformerLayer(
        config=DeepSpeedTransformerConfig(stochastic_mode=True, **base)
    )
    rng = np.random.default_rng(4)
    # scale drives |x - mean| past fp16's sqrt(max) so a relaxed fp16 var
    # would overflow to inf
    x = jnp.asarray(rng.normal(size=(2, 64, 64)) * 500.0, jnp.float16)
    params = layer_d.init(jax.random.PRNGKey(0), x, train=False)["params"]
    out_d = layer_d.apply({"params": params}, x, train=False)
    out_s = layer_s.apply({"params": params}, x, train=False)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_s))
    assert np.isfinite(np.asarray(out_s, np.float32)).all()


@slow
def test_remat_modes_same_output():
    """The reference's memory modes change memory, not numerics
    (ds_transformer_cuda.cpp:189-191) — remat must be invisible."""
    base = dict(
        hidden_size=64, heads=4, attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0
    )
    cfg_plain = DeepSpeedTransformerConfig(**base)
    cfg_remat = DeepSpeedTransformerConfig(
        **base, normalize_invertible=True, gelu_checkpoint=True
    )
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 64, 64)), jnp.float32)
    l1 = DeepSpeedTransformerLayer(config=cfg_plain)
    l2 = DeepSpeedTransformerLayer(config=cfg_remat)
    params = l1.init(jax.random.PRNGKey(0), x, train=False)["params"]
    o1 = l1.apply({"params": params}, x, train=False)
    o2 = l2.apply({"params": params}, x, train=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda p: jnp.sum(l1.apply({"params": p}, x, train=False) ** 2))(params)
    g2 = jax.grad(lambda p: jnp.sum(l2.apply({"params": p}, x, train=False) ** 2))(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g1[k]), np.asarray(g2[k]), rtol=1e-5, atol=1e-5
        )


@slow
def test_dropout_determinism_same_rng():
    cfg = DeepSpeedTransformerConfig(
        hidden_size=64, heads=4, attn_dropout_ratio=0.1, hidden_dropout_ratio=0.1
    )
    layer = DeepSpeedTransformerLayer(config=cfg)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 64, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x, train=False)["params"]
    key = jax.random.PRNGKey(7)
    o1 = layer.apply({"params": params}, x, train=True, rngs={"dropout": key})
    o2 = layer.apply({"params": params}, x, train=True, rngs={"dropout": key})
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = layer.apply(
        {"params": params}, x, train=True, rngs={"dropout": jax.random.PRNGKey(8)}
    )
    assert not np.allclose(np.asarray(o1), np.asarray(o3))


# --------------------------------------------------------------- flash kernel
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_mask", [False, True])
def test_flash_attention_parity(causal, with_mask):
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    mask = None
    if with_mask:
        mask = jnp.where(
            jnp.arange(S)[None, None, None, :] < 200, 0.0, -1e30
        ).astype(jnp.float32)
    o1 = flash_attention(q, k, v, mask=mask, causal=causal)
    o2 = mha_reference(q, k, v, mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


def test_flash_attention_grads():
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 2, 128, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    g1 = jax.grad(lambda a, b, c: jnp.sum(flash_attention(a, b, c, causal=True) ** 2), (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(mha_reference(a, b, c, causal=True) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_long_sequence_no_cap():
    """No seq<=1024 limit (the reference kernel hard-caps there)."""
    B, H, S, D = 1, 1, 2048, 64
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    o1 = flash_attention(q, k, v, causal=True)
    o2 = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


# Two-level tiling (PR 25): every case runs forward AND gradients against
# mha_reference. (sq, sk, causal, masked keys per batch row as [lo, hi),
# largest outer block)
FLASH_CASES = {
    # diag_offset 256 > 0: the diagonal starts in the third key sub-tile
    "causal_sq256_sk512": (256, 512, True, None, 1024),
    # one 768-block: 256 sub-tiles in the forward and the backward
    "causal_768": (768, 768, True, None, 1024),
    # 768 under the old default: 256-blocks on a 3 x 3 grid, each its own
    # sub-tile, loop bounds from the grid position
    "causal_768_blocks256": (768, 768, True, None, 512),
    # the block IS the sub-tile
    "causal_128": (128, 128, True, None, 1024),
    # the cells' sequence: one block, every bound static, 2 x 2 sub-tiles
    # in the forward, 4 x 4 in the fused backward (dq 2 x 2 and dkv 8 x 8
    # where the pair runs)
    "causal_1024": (1024, 1024, True, None, 1024),
    # 2 x 2 blocks of 2 x 2 sub-tiles: the fori_loop walk
    "causal_2048": (2048, 2048, True, None, 1024),
    # row 0: the whole first key sub-tile and two keys of the second are
    # padding, so under the causal mask queries 0..129 see no key at all;
    # row 1: trailing padding
    "causal_384_leading_keys_masked": (384, 384, True, [(0, 130), (300, 384)], 1024),
    "noncausal_384_masked": (384, 384, False, [(0, 130), (300, 384)], 1024),
    # grids of several blocks at blocks of 128 (PR 46: one body a class of
    # step, ON the diagonal or UNDER it, the class from a table in SMEM; the
    # skipped steps hold their neighbour's block): 2 x 2; 3 x 3; 2 x 4 with the
    # diagonal two blocks to the right; 4 x 2 with it two blocks down, so the
    # first 256 rows see no key and two of the four Q blocks' steps all skip
    "causal_256_blocks128": (256, 256, True, None, 128),
    "causal_384_blocks128": (384, 384, True, None, 128),
    "causal_sq256_sk512_blocks128": (256, 512, True, None, 128),
    "causal_sq512_sk256_blocks128": (512, 256, True, None, 128),
    # a key mask on a grid of several blocks, row 0's first block all padding
    "causal_384_blocks128_masked": (384, 384, True, [(0, 130), (300, 384)], 128),
}


# The packed entry (PR 29: the kernels read heads out of the fused qkv
# projection's [B, S, 3*H*D] result, two heads of 64 or one of 128 to a
# 128-lane block, and write [B, S, H*D]) runs the same comparison: (case,
# dtype, head_dim). Only self-attention shapes have a packed form.
_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
FLASH_RUNS = [
    pytest.param(case, dtype, None, False, id=f"{case}-{dtype}")
    for case in sorted(FLASH_CASES) for dtype in _DTYPES
    if dtype == "f32" or "_blocks128" not in case
] + [
    pytest.param(case, "f32", 64, False, id=f"{case}-f32-packed_d64")
    for case in sorted(FLASH_CASES)
    if FLASH_CASES[case][0] == FLASH_CASES[case][1]
] + [
    pytest.param(case, dtype, d, False, id=f"{case}-{dtype}-packed_d{d}")
    for case, dtype, d in [
        ("causal_1024", "bf16", 64),
        ("causal_2048", "bf16", 64),
        ("noncausal_384_masked", "bf16", 64),
        ("causal_1024", "f32", 128),
        ("causal_1024", "bf16", 128),
        ("causal_2048", "f32", 128),
        ("causal_768_blocks256", "f32", 128),
        ("noncausal_384_masked", "f32", 128),
    ]
] + [
    # Two backwards (PR 33). Every case above takes the one
    # ``backward_plan`` picks for its shape, which at these sizes is the
    # fused kernel; these run the PAIR as well (the budget that the plan
    # reads set to nothing) and hold it to the reference and to the fused
    # kernel's gradients: sq != sk, a leading masked sub-tile, a 3 x 3 and
    # a 2 x 2 grid, two heads of 64 and one of 128 to a block.
    pytest.param(case, dtype, d, True, id=f"{case}-{dtype}-{d and f'packed_d{d}-'}pair")
    for case, dtype, d in [
        ("causal_sq256_sk512", "f32", ""),
        ("causal_384_leading_keys_masked", "f32", ""),
        ("causal_768_blocks256", "f32", ""),
        ("causal_2048", "f32", ""),
        ("causal_1024", "f32", 64),
        ("causal_2048", "bf16", 64),
        ("noncausal_384_masked", "f32", 64),
        ("causal_768_blocks256", "f32", 128),
        ("causal_sq256_sk512_blocks128", "f32", ""),
        ("causal_sq512_sk256_blocks128", "f32", ""),
    ]
] + [
    # The walk whose bounds follow from the grid position as a step runs (a
    # ``fori_loop``), which shapes with more classes of step than
    # ``MAX_WALK_BODIES`` keep: the same cases with the ceiling at nothing.
    pytest.param(case, "f32", d, "loop", id=f"{case}-f32-{d and f'packed_d{d}-'}loop")
    for case, d in [
        ("causal_sq256_sk512_blocks128", ""), ("causal_sq512_sk256_blocks128", ""),
        ("causal_384_blocks128_masked", 64), ("causal_2048", ""),
    ]
]


def _packed_as_split(q, k, v, **kw):
    """``flash_attention_packed`` behind the split signature: q, k, v
    [B, H, S, D] are laid side by side as the projection would, and the
    context comes back [B, H, S, D]."""
    from deepspeed_tpu.ops.attention import flash_attention_packed

    def merge(t):
        return t.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    b, h, s, d = q.shape
    qkv = jnp.concatenate([merge(q), merge(k), merge(v)], axis=-1)
    out = flash_attention_packed(qkv, h, **kw)
    return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)


def _attention_module():
    """``deepspeed_tpu.ops.attention`` the attribute is the dispatcher."""
    import importlib

    return importlib.import_module("deepspeed_tpu.ops.attention")


@pytest.mark.parametrize("case,dtype,packed_d,pair", FLASH_RUNS)
def test_flash_tiled_matches_reference(case, dtype, packed_d, pair, monkeypatch):
    att = _attention_module()
    sq, sk, causal, masked, block = FLASH_CASES[case]
    dtype = _DTYPES[dtype]
    blocks = att.pick_block(sq, block), att.pick_block(sk, block)
    several = sq // blocks[0] > 1 or sk // blocks[1] > 1
    if pair == "loop":
        monkeypatch.setattr(att, "MAX_WALK_BODIES", 0)
    walk = att.flash_tiling(sq, sk, *blocks, causal)
    assert walk["walk"] == walk["backward"]["walk"] == (
        "loop" if pair == "loop" else "static")
    assert walk["bodies"] == (1 if pair == "loop" or not several else 2)
    pair = pair is True
    B, H, D = (1, 1, 64) if sq > 1024 else (2, 2, 64)
    if packed_d:
        # a 128-lane block holds whole heads: two of 64, one of 128
        H, D = max(H, 128 // packed_d), packed_d
    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, s, D)), dtype) for s in (sq, sk, sk)
    )
    w = rng.normal(size=(B, H, sq, D)).astype(np.float32)
    kv_mask = add = None
    valid = np.ones((B, sk), np.int32)
    if masked is not None:
        for b, (lo, hi) in enumerate(masked):
            valid[b, lo:hi] = 0
        kv_mask = jnp.asarray(valid)
        add = jnp.where(kv_mask[:, None, None, :] > 0, 0.0, -1e30)
    # query rows that see at least one key: row i sees keys 0 .. i + sk - sq
    live = np.ones((B, sq), bool)
    if causal:
        before = np.concatenate(
            [np.zeros((B, 1), np.int32), np.cumsum(valid, axis=1)], axis=1)
        live = before[:, np.clip(np.arange(sq) + sk - sq + 1, 0, sk)] > 0
    # rows without a live key: flash gives zeros, the reference an average
    # over masked keys; they take no part in the comparison
    w = jnp.asarray(w * live[:, None, :, None])

    def f32(x):
        return x.astype(jnp.float32)

    kernel = _packed_as_split if packed_d else flash_attention

    def flash(q, k, v):
        return f32(kernel(
            q, k, v, kv_mask=kv_mask, causal=causal, block_q=block, block_k=block
        ))

    def reference(q, k, v):
        return mha_reference(f32(q), f32(k), f32(v), mask=add, causal=causal)

    lse = None
    if packed_d:
        out = flash(q, k, v)
    else:
        # the forward's other result too, lse a row, which the backward reads
        out, (*_, lse) = att._flash_fwd(
            q, k, v, kv_mask, jnp.zeros((), jnp.int32), causal, D ** -0.5, 0.0,
            *blocks)
    out, ref = np.asarray(f32(out)), np.asarray(reference(q, k, v))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    keep = np.broadcast_to(live[:, None, :, None], out.shape)
    np.testing.assert_allclose(out[keep], ref[keep], rtol=tol, atol=tol)
    assert not out[~keep].any(), "a row with no live key must come out exactly zero"
    if masked is not None and causal:
        assert (~live).sum() >= 130
    if lse is not None:
        scores = jnp.einsum("bhqd,bhkd->bhqk", f32(q), f32(k)) * D ** -0.5
        allowed = np.broadcast_to(valid[:, None, None, :] > 0, scores.shape)
        if causal:
            allowed = allowed & np.asarray(
                np.arange(sk)[None, :] <= np.arange(sq)[:, None] + sk - sq)
        want = jax.nn.logsumexp(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        rows = np.broadcast_to(live[:, None, :], want.shape)
        np.testing.assert_allclose(
            np.asarray(lse).reshape(want.shape)[rows], np.asarray(want)[rows],
            rtol=tol, atol=tol)

    def flash_grads():
        return jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)

    assert att.backward_plan(sq, sk, *blocks, causal)["backward"] == "fused"
    gf = flash_grads()
    if pair:
        monkeypatch.setattr(att, "FUSED_DQ_VMEM_BUDGET", 0)
        plan = att.backward_plan(sq, sk, *blocks, causal)
        assert plan["backward"] == "pair" and "budget 0" in plan["reason"]
        fused, gf = gf, flash_grads()
        # the same products of the same rounded operands, summed in float32
        # in another order (dq: key block by key block in both, but the
        # fused kernel adds each sub-tile's product into its accumulator
        # where the pair chains a block's sub-tiles first)
        for a, b, name in zip(fused, gf, "qkv"):
            a, b = np.asarray(f32(a)), np.asarray(f32(b))
            ulp = 2e-6 if dtype == jnp.float32 else 2.0 ** -7
            assert np.abs(a - b).max() <= ulp * np.abs(b).max(), (
                f"d{name}: fused and pair part by "
                f"{np.abs(a - b).max() / np.abs(b).max():.2e}"
            )
    gr = jax.grad(lambda *a: jnp.sum(reference(*a) * w), (0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        a, b = np.asarray(f32(a)), np.asarray(b)
        assert np.isfinite(a).all(), f"d{name} not finite"
        scale = np.abs(b).max()
        gtol = 1e-4 if dtype == jnp.float32 else 3e-2
        assert np.abs(a - b).max() <= gtol * scale, (
            f"d{name}: {np.abs(a - b).max() / scale:.2e} of the largest entry"
        )


def test_flash_loop_bounds_stop_at_the_diagonal():
    from deepspeed_tpu.ops.attention import (
        _key_range, _query_range, flash_tiling,
    )

    t = flash_tiling(1024, 1024, 512, 512, True, sub_q=128, sub_k=128)
    assert t["visited_share"] == 0.5625
    assert flash_tiling(1024, 1024, 512, 512, False, sub_q=128, sub_k=128)[
        "visited_share"] == 1.0
    # the cells' shape as the kernels walk it: the forward in 256 sub-tiles
    # since PR 50 (10 of 16; until then 512: 3 of 4, as the one-level
    # 512-tiles before), dkv in 128s
    t = flash_tiling(1024, 1024, 1024, 1024, True)
    assert (t["sub_q"], t["sub_k"], t["visited_share"]) == (256, 256, 0.625)
    t = flash_tiling(1024, 1024, 1024, 1024, True, key_major=True)
    assert (t["sub_q"], t["sub_k"], t["visited_share"]) == (128, 128, 0.5625)
    # on a grid of several blocks the pair's dkv walks in the large sub-tile
    t = flash_tiling(2048, 2048, 1024, 1024, True, key_major=True)
    assert (t["sub_q"], t["sub_k"]) == (512, 512)
    # the backward (PR 33): one key-major kernel for dq, dk and dv, in
    # 256-steps at one block each way (5/8 of the causal square, where the
    # pair's dq kernel walked 3/4 and its dkv 9/16); its dq accumulator
    # (float32) and output block (two buffers of bf16) cover the whole
    # query length in VMEM
    b = flash_tiling(1024, 1024, 1024, 1024, True)["backward"]
    assert b == {
        "backward": "fused", "sub_q": 256, "sub_k": 256,
        "visited_share": 0.625, "dq_vmem_bytes": 1024 * 128 * 8,
        "reason": None, "walk": "static", "bodies": 1,
        "steps": {"run": 1, "skipped": 0, "fetched": 1},
    }
    # BERT at 512: one block, 2 x 2 sub-tiles; 768: 3 x 3
    b = flash_tiling(512, 512, 512, 512, False)["backward"]
    assert (b["sub_q"], b["sub_k"], b["visited_share"]) == (256, 256, 1.0)
    b = flash_tiling(768, 768, 768, 768, True)["backward"]
    assert (b["sub_q"], b["sub_k"]) == (256, 256)
    # on a grid of several blocks the fused kernel keeps its 256-steps since
    # PR 46 (the walk is static there too: 5/8 of a diagonal block where 512
    # visits 3/4; docs/TESTING.md has the sweep), the pair's dkv the 512
    b = flash_tiling(8192, 8192, 1024, 1024, True)["backward"]
    assert (b["backward"], b["sub_q"], b["sub_k"]) == ("fused", 256, 256)
    assert b["visited_share"] == 33 / 64 and b["dq_vmem_bytes"] == 8 * 2**20
    # a block of 64 lanes takes a register's 128 all the same
    assert flash_tiling(1024, 1024, 1024, 1024, True, lanes=64)["backward"][
        "dq_vmem_bytes"] == 2**20
    # past the budget the pair runs, and the plan says why: 32k positions at
    # width 256 (64 MiB of dq) under the default, anything under none
    b = flash_tiling(32768, 32768, 1024, 1024, True, lanes=256)["backward"]
    assert b["backward"] == "pair" and b["dq_vmem_bytes"] == 0
    assert "32768 rows of 256 lanes" in b["reason"]
    assert flash_tiling(16384, 16384, 1024, 1024, True, lanes=256)[
        "backward"]["backward"] == "fused"
    b = flash_tiling(1024, 1024, 1024, 1024, True, budget=2**20 - 1)["backward"]
    assert b["backward"] == "pair" and "budget 1048575" in b["reason"]
    assert (b["sub_q"], b["sub_k"], b["visited_share"]) == (128, 128, 0.5625)
    # a block the sub-tile does not divide: the largest halving that does,
    # else the block itself
    t = flash_tiling(768, 768, 768, 768, True)
    assert (t["sub_q"], t["sub_k"]) == (256, 256)
    t = flash_tiling(1032, 1032, 8, 8, True)
    assert (t["sub_q"], t["sub_k"]) == (8, 8)

    # PR 46: how a kernel walks its GRID. One block each way: one body, one
    # step. The cells' causal grids: a step lies ON the diagonal or UNDER it
    # (two bodies, bounds as ints), the steps above it are skipped and hold
    # their neighbour's block, so nothing is fetched for them
    def walk(t):
        return t["walk"], t["bodies"], t["steps"]

    one = {"run": 1, "skipped": 0, "fetched": 1}
    t = flash_tiling(1024, 1024, 1024, 1024, True)
    assert walk(t) == walk(t["backward"]) == ("static", 1, one)
    assert walk(flash_tiling(512, 512, 512, 512, False)) == ("static", 1, one)
    for seq, run in ((8192, 36), (16384, 136), (2048, 3)):
        steps = {"run": run, "skipped": (seq // 1024) ** 2 - run, "fetched": run}
        t = flash_tiling(seq, seq, 1024, 1024, True, lanes=256)
        assert walk(t) == walk(t["backward"]) == ("static", 2, steps)
    # past the VMEM budget the pair: both its walks are static too
    t = flash_tiling(32768, 32768, 1024, 1024, True, lanes=256)
    assert t["backward"]["backward"] == "pair"
    assert walk(t)[:2] == walk(t["backward"])[:2] == ("static", 2)
    # not causal, several blocks: every step the same, one body, all fetched
    assert walk(flash_tiling(2048, 2048, 1024, 1024, False)) == (
        "static", 1, {"run": 4, "skipped": 0, "fetched": 4})
    # Laguna's band, W 512 under 1,024-blocks: 2 steps a query block, its own
    # key block and the one before; the first query block's second step skips
    t = flash_tiling(8192, 8192, 1024, 1024, True, window=512)
    assert walk(t) == walk(t["backward"]) == (
        "static", 2, {"run": 15, "skipped": 1, "fetched": 15})
    # SDAR's block-diffusion mask on 16 x 16: 80 of 256 steps run, 3 bodies
    t = flash_tiling(16384, 16384, 1024, 1024, False, block_diffusion=4)
    assert walk(t) == walk(t["backward"]) == (
        "static", 3, {"run": 80, "skipped": 176, "fetched": 80})
    # diag_offset != 0 and blocks that differ each way: the same computation
    t = flash_tiling(2048, 3072, 1024, 512, True)
    assert walk(t) == ("static", 3, {"run": 10, "skipped": 2, "fetched": 10})
    # more classes than MAX_WALK_BODIES (a Q block of two halves' worth under
    # the block-diffusion mask): the walk stays the ``fori_loop`` it was, one
    # body whose bounds follow from the grid position; the index maps still
    # hold a neighbour's block through the skipped steps
    t = flash_tiling(16384, 16384, 2048, 1024, False, block_diffusion=4)
    assert walk(t)[:2] == walk(t["backward"])[:2] == ("loop", 1)
    assert t["steps"]["fetched"] == t["steps"]["run"] < 8 * 16

    for sq, sk, bq, bk, sub_q, sub_k in [
        (1024, 1024, 512, 512, 128, 128), (768, 768, 256, 256, 128, 128),
        (256, 512, 256, 512, 128, 128), (512, 256, 512, 256, 128, 128),
        (1024, 1024, 512, 1024, 256, 128), (384, 384, 384, 384, 128, 128),
        (1024, 1024, 1024, 512, 128, 256), (264, 264, 264, 264, 264, 264),
    ]:
        off = sk - sq
        by_rows = set()
        for q_first in range(0, sq, sub_q):
            for k_block in range(0, sk, bk):
                n_full, hi = _key_range(
                    q_first, sub_q, k_block, sub_k, bk // sub_k, off
                )
                assert 0 <= n_full <= hi <= bk // sub_k
                for c in range(bk // sub_k):
                    k_first = k_block + c * sub_k
                    # some score of the sub-tile is live <=> its first key
                    # is visible to the last row
                    live = k_first <= q_first + sub_q - 1 + off
                    # every score is live <=> its last key is visible to
                    # the first row
                    whole = k_first + sub_k - 1 <= q_first + off
                    assert (c < hi) == live, "visited iff not wholly above"
                    assert (c < n_full) == whole
                    if c < hi:
                        by_rows.add((q_first, k_first, c < n_full))
        by_keys = set()
        for k_first in range(0, sk, sub_k):
            for q_block in range(0, sq, bq):
                lo, full = _query_range(
                    k_first, sub_k, q_block, sub_q, bq // sub_q, off
                )
                assert 0 <= lo <= full <= bq // sub_q
                for r in range(lo, bq // sub_q):
                    by_keys.add((q_block + r * sub_q, k_first, r >= full))
        # dkv walks the same sub-tiles as fwd and dq, masked the same way
        assert by_keys == by_rows


# One forward + backward as jaxpr text (kernel bodies, grids and index maps
# included, function addresses stripped): (entry, seq, heads, head width,
# causal, key mask, bias, the mask form's keywords, block).
WALK_PROGRAMS = {
    # one block each way, the six GPT-2 and BERT cells' calls at two heads, and
    # two stripes of one head: several chains a grid step, so since PR 50 the
    # forward walks them key-major (the text as that PR left it, sha256; the
    # backward's half of it is bd1970c's, PR 46)
    "gpt2_packed_1024": (
        "packed", 1024, 2, 64, True, False, True, {}, 1024, "98cf0ad46ea35b3a"),
    "bert_packed_512_masked": (
        "packed", 512, 2, 64, False, True, True, {}, 1024, "a7691d7c7b10c12f"),
    "bert_packed_384_masked": (
        "packed", 384, 2, 64, False, True, True, {}, 1024, "76de485be8b35fb2"),
    "split_causal_1024_d64": (
        "split", 1024, 1, 64, True, False, False, {}, 1024, "9455c11ae9b7eb28"),
    "split_noncausal_1024_d128": (
        "split", 1024, 1, 128, False, False, False, {}, 1024, "6a56c3aecdc9c21a"),
    # ONE chain a grid step (one stripe of one head): nothing to put side by
    # side, and the whole text is the parent's (sha256 of c9ea1f4's, PR 50),
    # on one block and on a 4 x 4 grid, whose classes ride behind the seed
    "split_causal_512_d128": (
        "split", 512, 1, 128, True, False, False, {}, 1024, "c3c1284b7d065495"),
    "split_masked_384_d64": (
        "split", 384, 1, 64, False, True, False, {}, 1024, "115c783b2f6e285b"),
    "packed_causal_512_d128": (
        "packed", 512, 1, 128, True, False, False, {}, 1024, "1a9635e82548e70f"),
    "split_causal_1024_blocks256": (
        "split", 1024, 1, 128, True, False, False, {}, 256, "1fed9f354f05fbb9"),
    # grids of several blocks: no loop in either kernel
    "causal_2048": ("split", 2048, 1, 128, True, False, False, {}, 1024, None),
    "causal_512_blocks128_masked": (
        "split", 512, 1, 64, True, True, False, {}, 128, None),
    "packed_causal_2048": ("packed", 2048, 1, 128, True, False, False, {}, 1024, None),
    "band_2048": (
        "split", 2048, 1, 128, True, False, False, {"window": 512}, 1024, None),
    "block_diffusion_4096": (
        "split", 4096, 1, 128, False, False, False, {"block_diffusion": 4}, 1024,
        None),
}


@pytest.mark.parametrize("case", sorted(WALK_PROGRAMS))
def test_flash_walk_is_static_and_one_block_is_the_program_it_was(case):
    import hashlib
    import re

    from deepspeed_tpu.ops.attention import flash_attention_packed

    entry, s, h, d, causal, masked, biased, form, block, pin = WALK_PROGRAMS[case]
    blocks = dict(block_q=block, block_k=block)
    if entry == "packed":
        args = [jax.ShapeDtypeStruct((1, s, 3 * h * d), jnp.bfloat16),
                jax.ShapeDtypeStruct((3 * h * d,), jnp.bfloat16)]
        nums = (0, 1) if biased else (0,)

        def loss(qkv, bias, kvm=None):
            return flash_attention_packed(
                qkv, h, bias=bias if biased else None, kv_mask=kvm,
                causal=causal, **blocks,
            ).astype(jnp.float32).sum()
    else:
        args = [jax.ShapeDtypeStruct((1, h, s, d), jnp.bfloat16)] * 3
        nums = (0, 1, 2)

        def loss(q, k, v, kvm=None):
            return flash_attention(
                q, k, v, kv_mask=kvm, causal=causal, **form, **blocks
            ).astype(jnp.float32).sum()

    if masked:
        args.append(jax.ShapeDtypeStruct((1, s), jnp.int32))
    text = re.sub(
        r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.grad(loss, nums))(*args)))
    assert text.count("pallas_call") >= 2
    assert "while" not in text and "scan" not in text
    if pin:
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == pin
    else:
        # the class of the running step comes from the table behind the seed
        assert text.count("cond[") >= 4


@contextlib.contextmanager
def _attention_debug_log():
    """The debug lines ``ops/attention.py`` logs while the block is open,
    with its once-a-shape caches emptied first."""
    import logging

    att = _attention_module()
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    level = att.logger.level
    att.logger.addHandler(handler)
    att.logger.setLevel(logging.DEBUG)
    att._log_tiling.cache_clear()
    att._log_layout.cache_clear()
    try:
        yield att, seen
    finally:
        att.logger.removeHandler(handler)
        att.logger.setLevel(level)


def test_flash_tiling_is_logged_once_per_shape():
    with _attention_debug_log() as (att, seen):
        q = jnp.zeros((1, 1, 1024, 64), jnp.float32)
        for _i in range(2):
            jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True), q)
    lines = [m for m in seen if m.startswith("flash_tiling")]
    assert len(lines) == 1
    t = att.flash_tiling(1024, 1024, 1024, 1024, True)
    assert f" sub={t['sub_q']}x{t['sub_k']} " in lines[0]
    assert f" visited_share={t['visited_share']:.4f} " in lines[0]
    b = att.flash_tiling(1024, 1024, 1024, 1024, True, lanes=64, itemsize=4)[
        "backward"]
    assert (t["order"], t["chains"]) == ("key_major", 1024 // t["sub_q"])
    assert (f" walk=static bodies=1 steps=1/0/1 order={t['order']} "
            f"chains={t['chains']} backward=fused ") in lines[0]
    assert lines[0].endswith(
        f" backward=fused bwd_sub={b['sub_q']}x{b['sub_k']} "
        f"bwd_visited_share={b['visited_share']:.4f} "
        f"bwd_walk=static bodies=1 steps=1/0/1 "
        f"dq_vmem_bytes={b['dq_vmem_bytes']}"
    )


# The flash calls of the seven cells that run the kernels, as their models make
# them: (entry, batch, seq, heads, head width, causal, key mask).
# ``zero2-dp4``'s is one chip's shard of 32 rows under ``shard_map``.
BACKWARD_CASES = {
    "gpt2-large.train-seq1024": ("packed", 8, 1024, 20, 64, True, False),
    "gpt2-large.train-accum1": ("packed", 8, 1024, 20, 64, True, False),
    "gpt2-large.zero2-dp4": ("packed", 8, 1024, 20, 64, True, False),
    "bert-large.pretrain-seq512": ("packed", 8, 512, 16, 64, False, True),
    "ouro-2.6b.train-seq8192": ("packed", 1, 8192, 16, 128, True, False),
    "nemotron3-super-120b-a12b.train-seq8192": ("split", 2, 8192, 4, 128, True, False),
    "qwen3-next-80b-a3b.train-seq16384": ("split", 2, 16384, 16, 256, True, False),
    # no cell: a sequence whose dq does not fit the budget keeps the pair
    "seq32768_width256": ("split", 1, 32768, 2, 256, True, False),
}


@pytest.mark.parametrize("cell", sorted(BACKWARD_CASES))
def test_backward_is_chosen_by_shape_and_logged(cell):
    """The ``flash_tiling`` line of each cell's call names the backward it
    gets, from the function that makes the choice (``backward_plan``)."""
    from deepspeed_tpu.ops.attention import flash_attention_packed

    entry, b, s, h, d, causal, masked = BACKWARD_CASES[cell]
    kv_mask = jnp.ones((b, s), jnp.int32) if masked else None
    with _attention_debug_log() as (att, seen):
        if entry == "packed":
            jax.eval_shape(
                lambda x: flash_attention_packed(
                    x, h, kv_mask=kv_mask, causal=causal),
                jax.ShapeDtypeStruct((b, s, 3 * h * d), jnp.bfloat16),
            )
        else:
            q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
            jax.eval_shape(
                lambda q: flash_attention(q, q, q, kv_mask=kv_mask, causal=causal),
                q,
            )
    (line,) = [m for m in seen if m.startswith("flash_tiling")]
    lanes = 128 if entry == "packed" else d
    block = min(s, 1024)
    plan = att.backward_plan(s, s, block, block, causal, lanes)
    if cell == "seq32768_width256":
        assert plan["backward"] == "pair"
        assert line.endswith(f" backward=pair bwd_sub={plan['sub_q']}x{plan['sub_k']} "
                             f"bwd_visited_share={plan['visited_share']:.4f} "
                             f"bwd_walk=static bodies=2 steps=528/496/528 "
                             f"dq_vmem_bytes=0 reason={plan['reason']!r}")
        return
    assert plan["backward"] == "fused" and plan["reason"] is None
    assert plan["dq_vmem_bytes"] == s * lanes * 8 <= att.FUSED_DQ_VMEM_BUDGET
    assert f" backward=fused bwd_sub={plan['sub_q']}x{plan['sub_k']} " in line
    assert line.endswith(f" dq_vmem_bytes={plan['dq_vmem_bytes']}")
    # the walk of both kernels: static at every cell's shape, and nothing
    # fetched for a step that skips
    run = (s // block) * (s // block + 1) // 2 if causal else (s // block) ** 2
    steps = f"steps={run}/{(s // block) ** 2 - run}/{run}"
    bodies = 2 if causal and s > block else 1
    assert line.count(f"walk=static bodies={bodies} {steps} ") == 2


# what the dispatcher sees in each cell of the benchmark (and in two shapes
# no cell runs), and the layout it must choose from that alone:
# (entry, batch, seq, heads, head_dim, kv heads, mesh (data, model),
#  layout, heads a block, a word of the reason)
LAYOUT_CASES = {
    "gpt2-large.train-seq1024": ("packed", 8, 1024, 20, 64, 20, (1, 1), "packed", 2, None),
    "gpt2-large.train-accum1": ("packed", 8, 1024, 20, 64, 20, (1, 1), "packed", 2, None),
    # dp 4: 32 rows over the data axis, the kernels run per shard
    "gpt2-large.zero2-dp4": ("packed", 32, 1024, 20, 64, 20, (4, 1), "packed", 2, None),
    # seq 128 never reaches the kernels
    "bert-large.pretrain-seq128": ("packed", 32, 128, 16, 64, 16, (1, 1), "split", 1, "FLASH_MIN_SEQ"),
    # the hybrid mixer: separate projections, one kv head for four
    "nemotron3-super-120b-a12b.train-seq8192": ("split", 2, 8192, 4, 128, 1, (1, 1), "split", 1, "separate"),
    "gpt2-xl_25_heads": ("packed", 4, 1024, 25, 64, 25, (1, 1), "split", 1, "25 heads"),
    "head_dim_128": ("packed", 2, 2048, 8, 128, 8, (1, 1), "packed", 1, None),
    "head_dim_80": ("packed", 2, 1024, 16, 80, 16, (1, 1), "split", 1, "head_dim 80"),
    "model_axis_2": ("packed", 8, 1024, 20, 64, 20, (2, 2), "split", 1, "model axis"),
}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_attention_layout_is_logged_with_its_reason(case):
    from deepspeed_tpu.parallel.mesh import build_mesh

    entry, b, s, h, d, kv, (dp, mp), layout, heads_a_block, reason = (
        LAYOUT_CASES[case]
    )
    mesh = build_mesh(
        devices=jax.devices()[:dp * mp], data_parallel_size=dp,
        model_parallel_size=mp,
    )
    with _attention_debug_log() as (att, seen):
        for _i in range(2):
            if entry == "packed":
                out = jax.eval_shape(
                    lambda x: att.attention_packed(
                        x, h, causal=True, mesh=mesh),
                    jax.ShapeDtypeStruct((b, s, 3 * h * d), jnp.bfloat16),
                )
                assert out.shape == (b, s, h * d)
            else:
                jax.eval_shape(
                    lambda q, k: att.attention(q, k, k, causal=True, mesh=mesh),
                    jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16),
                    jax.ShapeDtypeStruct((b, kv, s, d), jnp.bfloat16),
                )
    lines = [m for m in seen if m.startswith("attention_layout")]
    assert len(lines) == 1, lines
    assert f" b={b} s={s} heads={h} d={d} " in lines[0]
    assert f" layout={layout} heads_a_block={heads_a_block}" in lines[0]
    if reason is None:
        assert "reason" not in lines[0]
    else:
        assert reason in lines[0].split("reason=")[1]
    # the chooser itself says the same
    chosen = att.attention_layout(
        b, s, h, d, att._flash_gate(s, s, None, 0.0, None, True)[0], mesh)
    if entry == "packed":
        assert chosen[:2] == (layout, heads_a_block)


@pytest.mark.parametrize("variant", ["one_device", "dp2_per_shard", "lora", "return_kv"])
def test_block_on_the_packed_route_matches_the_plain_block(variant):
    """``block()`` hands the kernels the projection's bare product and its
    bias (one device, or per shard of a data-parallel mesh) and gets
    [B, S, H] back: output and every parameter's gradient against the
    hand-written block; an adapter on ``attn_qkvw`` and the KV cache's
    prefill (``return_kv``) keep working."""
    from deepspeed_tpu.ops.transformer import (
        TRANSFORMER_PARAM_LAYOUT, transformer_block_apply,
    )
    from deepspeed_tpu.parallel.mesh import build_mesh

    dp = 2 if variant == "dp2_per_shard" else 1
    mesh = build_mesh(devices=jax.devices()[:dp], data_parallel_size=dp)
    cfg = DeepSpeedTransformerConfig(
        hidden_size=128, heads=2, attn_dropout_ratio=0.0,
        hidden_dropout_ratio=0.0, lora_rank=4 if variant == "lora" else 0,
        lora_targets=("attn_qkvw",) if variant == "lora" else (),
    )
    dims = {"H": 128, "3H": 384, "I": 512}
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 32))
    p = {
        name: (1.0 if kind.startswith("ones") else 0.0)
        + 0.05 * jax.random.normal(next(keys), tuple(dims[d] for d in shape))
        for name, shape, kind in TRANSFORMER_PARAM_LAYOUT
    }
    if variant == "lora":
        p["attn_qkvw_lora_a"] = 0.1 * jax.random.normal(next(keys), (128, 4))
        p["attn_qkvw_lora_b"] = 0.1 * jax.random.normal(next(keys), (4, 384))
    x = jax.random.normal(next(keys), (2, 256, 128))

    def plain(p, x):
        if variant == "lora":
            p = dict(p, attn_qkvw=p["attn_qkvw"]
                     + p["attn_qkvw_lora_a"] @ p["attn_qkvw_lora_b"])
        return naive_layer_forward(p, x, cfg, causal=True)

    with _attention_debug_log() as (_att, seen):
        if variant == "return_kv":
            out, (k, v) = transformer_block_apply(
                cfg, p, x, causal=True, train=False, mesh=mesh, return_kv=True)
            qkv = naive_qkv(p, x, cfg)
            np.testing.assert_allclose(np.asarray(k), np.asarray(qkv[1]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(v), np.asarray(qkv[2]), atol=1e-5)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(plain(p, x)), rtol=2e-4, atol=2e-4)
        else:
            def ours(p, x):
                return transformer_block_apply(
                    cfg, p, x, causal=True, train=False, mesh=mesh)

            w = jax.random.normal(next(keys), x.shape)
            (lo, go), (lr, gr) = (
                jax.value_and_grad(lambda p, x: jnp.sum(f(p, x) * w), (0, 1))(p, x)
                for f in (ours, plain)
            )
            np.testing.assert_allclose(float(lo), float(lr), rtol=2e-4)
            for a, b in zip(jax.tree_util.tree_leaves(go),
                            jax.tree_util.tree_leaves(gr)):
                scale = float(jnp.abs(b).max()) or 1.0
                assert float(jnp.abs(a - b).max()) <= 2e-3 * scale
    lines = [m for m in seen if m.startswith("attention_layout")]
    assert lines and all("layout=packed heads_a_block=2" in m for m in lines)


def naive_qkv(params, x, cfg):
    """q, k, v [B, heads, S, hd] of the hand-written block."""
    def ln(t, w, b):
        mu, var = t.mean(-1, keepdims=True), t.var(-1, keepdims=True)
        return ((t - mu) / jnp.sqrt(var + cfg.layer_norm_eps)) * w + b

    h = ln(x, params["attn_nw"], params["attn_nb"]) if cfg.pre_layer_norm else x
    qkv = h @ params["attn_qkvw"] + params["attn_qkvb"]
    b, s, _ = x.shape
    return [
        t.reshape(b, s, cfg.heads, -1).transpose(0, 2, 1, 3)
        for t in jnp.split(qkv, 3, axis=-1)
    ]
