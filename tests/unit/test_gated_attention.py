"""The gated attention mixer (ops/transformer.py:attention_mixer under kind
``G``'s spec, models/hybrid.py:attention_spec) against its plain reference
(benchmark/reference/qwen3_next.py): partial rotary, zero-centred q/k norms,
the output gate, 8 query heads a kv head; the three flash kernels at head
width 256 against the XLA softmax path, values and gradients (interpret mode
on the CPU); the ``attention_layout`` log line for that shape."""

import importlib
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.hybrid import HybridLMConfig, attention_spec
from deepspeed_tpu.ops.transformer import (
    apply_rotary,
    attention_mixer,
    rotary_frequencies,
)
from deepspeed_tpu.utils.logging import logger

attn_ops = importlib.import_module("deepspeed_tpu.ops.attention")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402

DOT = ref_ops.make_dot("float32")
CFG = dict(hidden_size=48, num_attention_heads=8, num_key_value_heads=1,
           head_dim=16, partial_rotary_factor=0.25, rope_theta=10000000,
           rms_norm_eps=1e-6)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def leaves(rng, cfg=CFG):
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"wq": 0.3 * normal(rng, e, hq * 2 * d),
            "wk": 0.3 * normal(rng, e, hkv * d),
            "wv": 0.3 * normal(rng, e, hkv * d),
            "q_norm": 0.2 * normal(rng, d), "k_norm": 0.2 * normal(rng, d),
            "wo": 0.3 * normal(rng, hq * d, e)}


def ours(p, x, cfg=CFG):
    return attention_mixer(p, x, attention_spec(HybridLMConfig(
        pattern="G", hidden_size=cfg["hidden_size"],
        attn_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_lanes=int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"]), "G"))


def theirs(p, x, cfg=CFG):
    p = {{"q_norm": "q_norm.w", "k_norm": "k_norm.w"}.get(k, k): v
         for k, v in p.items()}
    return ref.gattn_mixer(p, x, cfg, DOT)


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gated_attention_matches_reference(kv_heads):
    """Output and the gradient of every leaf and of the input: 8 (and 4)
    query heads a kv head, 4 of 16 lanes rotating."""
    cfg = dict(CFG, num_key_value_heads=kv_heads)
    rng = np.random.default_rng(kv_heads)
    p, x = leaves(rng, cfg), normal(rng, 2, 24, cfg["hidden_size"])
    probe = normal(rng, *x.shape)
    np.testing.assert_allclose(
        ours(p, x, cfg), theirs(p, x, cfg), rtol=2e-4, atol=2e-5)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x, cfg) * probe), (0, 1))(p, x)
    g_theirs = jax.grad(
        lambda p, x: jnp.sum(theirs(p, x, cfg) * probe), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_theirs)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))))


def test_the_gate_closes_the_output_and_the_norm_gain_is_zero_centred():
    rng = np.random.default_rng(7)
    p, x = leaves(rng), normal(rng, 1, 8, CFG["hidden_size"])
    d = CFG["head_dim"]
    # a strongly negative gate: sigmoid -> 0, whatever the context is
    wq = p["wq"].reshape(-1, CFG["num_attention_heads"], 2 * d)
    shut = dict(p, wq=wq.at[..., d:].set(0.0).reshape(p["wq"].shape))
    half = ours(shut, x)       # gate logit 0: sigmoid = 1/2
    np.testing.assert_allclose(
        half, theirs(shut, x), rtol=2e-4, atol=2e-5)
    zero_gain = dict(p, q_norm=jnp.zeros(d), k_norm=jnp.zeros(d))
    unit_gain = dict(p, q_norm=jnp.full(d, 1.0), k_norm=jnp.zeros(d))
    # 1 + w: a gain of zero leaves the normalised query, a gain of one
    # doubles it (scores double, so the output moves)
    assert not np.allclose(ours(zero_gain, x), ours(unit_gain, x))
    np.testing.assert_allclose(
        ours(zero_gain, x), theirs(zero_gain, x), rtol=2e-4, atol=2e-5)


def test_rotary_rotates_the_first_lanes_in_half_split_pairs():
    rng = np.random.default_rng(0)
    x = normal(rng, 1, 2, 6, 16)
    out = apply_rotary(x, 8, 100.0)
    inv = rotary_frequencies(8, 100.0)
    np.testing.assert_allclose(inv, 100.0 ** (-np.arange(4) / 4.0), rtol=1e-6)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])   # the rest stays
    np.testing.assert_allclose(out[:, :, 0], x[:, :, 0], atol=1e-7)  # position 0
    t = 5
    for i in range(4):   # lane i pairs with lane i + 4
        c, s = np.cos(t * inv[i]), np.sin(t * inv[i])
        np.testing.assert_allclose(
            out[0, :, t, i], x[0, :, t, i] * c - x[0, :, t, i + 4] * s, rtol=1e-5,
            atol=1e-6)
        np.testing.assert_allclose(
            out[0, :, t, i + 4], x[0, :, t, i + 4] * c + x[0, :, t, i] * s,
            rtol=1e-5, atol=1e-6)
    # a rotation: the pair's length is kept, so q.k depends on t - s only
    np.testing.assert_allclose(
        jnp.sum(out[..., :8] ** 2, -1), jnp.sum(x[..., :8] ** 2, -1), rtol=1e-5)
    np.testing.assert_allclose(
        ref.rotary(x, inv), out, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("blocks", [(256, 256), (128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernels_at_head_width_256_match_the_softmax_path(causal, blocks):
    """Forward, dq and dkv at d = 256 on the split [B, H, S, D] layout, with
    one block each way (the static walk) and a 2 x 2 grid (the ``fori_loop``
    walk the cell's 16 x 16 grid takes), against ``mha_reference``."""
    rng = np.random.default_rng(11)
    q, k, v, probe = (normal(rng, 1, 2, 256, 256) for _ in range(4))
    q = q * 0.25

    def flash(q, k, v):
        return attn_ops.flash_attention(
            q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1])

    def softmax(q, k, v):
        return attn_ops.mha_reference(q, k, v, causal=causal)

    np.testing.assert_allclose(
        flash(q, k, v), softmax(q, k, v), rtol=2e-4, atol=2e-5)
    g_flash = jax.grad(lambda *a: jnp.sum(flash(*a) * probe), (0, 1, 2))(q, k, v)
    g_soft = jax.grad(lambda *a: jnp.sum(softmax(*a) * probe), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_soft):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)


def test_layout_line_names_the_width_256_shape_and_why_it_is_split(caplog):
    attn_ops._log_layout.cache_clear()
    logger.propagate = True
    q = jnp.zeros((1, 16, 256, 256), jnp.float32)
    kv = jnp.zeros((1, 2, 256, 256), jnp.float32)
    try:
        with caplog.at_level(logging.DEBUG, logger=logger.name):
            jax.eval_shape(
                lambda q, k, v: attn_ops.attention(q, k, v, causal=True),
                q, kv, kv)
    finally:
        logger.propagate = False
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("attention_layout")]
    assert len(lines) == 1, lines
    assert "s=256 heads=16 d=256 layout=split heads_a_block=1" in lines[0]
    assert "head_dim 256 is neither 64 nor 128" in lines[0]
    assert attn_ops.packed_refusal(16, 256) == \
        "head_dim 256 is neither 64 nor 128"
