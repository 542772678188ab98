"""Flash attention under data/model-parallel meshes (shard_map path).

The reference's fused attention kernel runs independently on every
data-parallel GPU (csrc/transformer/ds_transformer_cuda.cpp:217-231); the
TPU analog must keep the O(S) Pallas kernel per-shard under dp/mp meshes
instead of silently degrading to the O(S^2) XLA path. These tests assert
numerical parity of the shard_map'd kernel against ``mha_reference`` on the
virtual 8-device mesh (dp=4 x mp=2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

pytestmark = pytest.mark.slow  # compile-heavy; excluded from `make test-fast`

attn_lib = importlib.import_module("deepspeed_tpu.ops.attention")
from deepspeed_tpu.ops.attention import (
    attention,
    flash_attention_sharded,
    mha_reference,
)
from deepspeed_tpu.parallel.mesh import build_mesh


def _qkv(b=8, h=4, s=256, d=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) * 0.5 for k in ks)


@pytest.fixture(scope="module")
def dp_mp_mesh():
    return build_mesh(data_parallel_size=4, model_parallel_size=2)


def test_sharded_flash_matches_reference(dp_mp_mesh):
    q, k, v = _qkv()
    out = jax.jit(
        lambda q, k, v: flash_attention_sharded(q, k, v, dp_mp_mesh)
    )(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sharded_flash_causal_matches_reference(dp_mp_mesh):
    q, k, v = _qkv(seed=1)
    out = jax.jit(
        lambda q, k, v: flash_attention_sharded(q, k, v, dp_mp_mesh, causal=True)
    )(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sharded_flash_kv_mask_matches_reference(dp_mp_mesh):
    q, k, v = _qkv(seed=2)
    b, _, s, _ = q.shape
    kv_valid = (
        jnp.arange(s)[None, :] < jnp.asarray([s, s // 2] * (b // 2))[:, None]
    ).astype(jnp.int32)
    additive = jnp.where(kv_valid[:, None, None, :] > 0, 0.0, attn_lib.NEG_INF)
    out = jax.jit(
        lambda q, k, v, m: flash_attention_sharded(
            q, k, v, dp_mp_mesh, kv_mask=m
        )
    )(q, k, v, kv_valid)
    ref = mha_reference(q, k, v, mask=additive)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sharded_flash_gradients_match_reference(dp_mp_mesh):
    q, k, v = _qkv(b=4, h=2, s=256, d=64, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_sharded(q, k, v, dp_mp_mesh) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v) ** 2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_dispatcher_routes_to_sharded_flash(dp_mp_mesh, monkeypatch):
    """attention(mesh=...) must take the shard_map path (not mha_reference)
    for a dp/mp mesh with clean tiling."""
    called = {}
    real = attn_lib.flash_attention_sharded

    def spy(*a, **kw):
        called["yes"] = True
        return real(*a, **kw)

    monkeypatch.setattr(attn_lib, "flash_attention_sharded", spy)
    q, k, v = _qkv(seed=4)
    out = attention(q, k, v, mesh=dp_mp_mesh)
    assert called.get("yes"), "dispatcher fell back off the shard_map path"
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_dispatcher_falls_back_when_heads_do_not_divide(dp_mp_mesh):
    # 3 heads % mp=2 != 0 -> must fall back to the XLA path, still correct
    q, k, v = _qkv(h=3, seed=5)
    out = attention(q, k, v, mesh=dp_mp_mesh)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pick_block_falls_back_to_dividing_block():
    from deepspeed_tpu.ops.attention import pick_block

    assert pick_block(1024, 512) == 512
    assert pick_block(768, 512) == 256   # 768 % 512 != 0 -> halve
    assert pick_block(128, 512) == 128
    assert pick_block(17, 512) == 17     # single full-dim block is tileable
    assert pick_block(1030, 512) == 0    # 2*5*103: nothing >= 8 divides


def test_resolve_remat_policy_rejects_typos():
    import pytest as _pytest

    from deepspeed_tpu.ops.transformer import resolve_remat_policy

    resolve_remat_policy("dots_with_no_batch_dims_saveable+flash_out")
    with _pytest.raises(ValueError, match="unknown remat policy part"):
        resolve_remat_policy("dots_with_no_batch_dims_savable")  # typo
