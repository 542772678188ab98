"""The two sublayers that SDAR-30B-A3B-Chat adds (models/hybrid.py kinds
``A`` and ``S``) against the plain float32 reference
(benchmark/reference/sdar.py) on seeded weights at toy widths on the CPU:
rotary grouped-query attention with q/k norms under the block-diffusion mask
(ops/transformer.py:attention_mixer under kind ``A``'s spec,
models/hybrid.py:attention_spec), on the XLA path and through
the flash kernels in interpret mode, and causal; the experts with no shared
one (ops/moe.py:gated_moe_mixer), the learned selection and a skewed router
included; and the EIGHT shares' expert parts adding up to the uncut layer."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.hybrid import HybridLMConfig, attention_spec
from deepspeed_tpu.ops.moe import gated_moe_mixer
from deepspeed_tpu.ops.transformer import apply_rotary, attention_mixer

attn_ops = importlib.import_module("deepspeed_tpu.ops.attention")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import sdar as ref  # noqa: E402

DOT = ref_ops.make_dot("float32")
CFG = dict(hidden_size=48, num_attention_heads=8, num_key_value_heads=1,
           head_dim=16, rope_theta=1000000, rms_norm_eps=1e-6, block_length=4,
           num_experts=16, experts_routed_over=16, expert_offset=0,
           num_experts_per_tok=4, moe_intermediate_size=24)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def attn_leaves(rng, cfg=CFG):
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"wq": 0.3 * normal(rng, e, hq * d), "wk": 0.3 * normal(rng, e, hkv * d),
            "wv": 0.3 * normal(rng, e, hkv * d),
            "q_norm": 1 + 0.2 * normal(rng, d), "k_norm": 1 + 0.2 * normal(rng, d),
            "wo": 0.3 * normal(rng, hq * d, e)}


def as_reference(p):
    return {{"q_norm": "q_norm.g", "k_norm": "k_norm.g"}.get(k, k): v
            for k, v in p.items()}


def attn_spec(block, cfg=CFG):
    """Kind ``A`` out of the table; ``block`` 0: the next-token objective."""
    return attention_spec(HybridLMConfig(
        pattern="A", hidden_size=cfg["hidden_size"],
        attn_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        objective="block_diffusion" if block else "next_token",
        diffusion_block=block or cfg["block_length"]), "A")


def our_attn(p, x, block=4, cfg=CFG):
    half = x.shape[1] // 2
    return attention_mixer(
        p, x, attn_spec(block, cfg), positions=jnp.arange(2 * half) % half)


@pytest.mark.parametrize("flash", [False, True])
@pytest.mark.parametrize("block", [4, 32])
def test_attention_mixer_against_the_reference(block, flash, monkeypatch):
    """Values and every leaf's gradient, 8 query heads on one kv head, a row
    of 2 x 128 positions (32 or 4 blocks a side). ``flash``: the three
    kernels in interpret mode on a 4 x 4 grid of blocks (the dispatcher
    takes the XLA path with the dense mask at this length otherwise)."""
    if flash:
        monkeypatch.setattr(attn_ops, "FLASH_MODE", "always")
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_Q", 64)
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_K", 64)
    rng = np.random.default_rng(block)
    p, x, w = attn_leaves(rng), normal(rng, 2, 256, 48), normal(rng, 2, 256, 48)
    cfg = dict(CFG, block_length=block)

    def ours(p, x):
        return jnp.sum(our_attn(p, x, block) * w)

    def theirs(p, x):
        return jnp.sum(ref.attn(as_reference(p), x, cfg, DOT) * w)

    np.testing.assert_allclose(
        our_attn(p, x, block), ref.attn(as_reference(p), x, cfg, DOT),
        atol=2e-5, rtol=2e-5)
    got, want = jax.grad(ours, (0, 1))(p, x), jax.grad(theirs, (0, 1))(p, x)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=3e-5)


def test_mixer_is_causal_without_the_objective():
    """The same mixer under a next-token objective: positions 0..S-1, the
    causal mask; an early output does not move with a late input."""
    rng = np.random.default_rng(1)
    p, x = attn_leaves(rng), normal(rng, 1, 32, 48)
    out = attention_mixer(p, x, attn_spec(0))
    moved = attention_mixer(p, x.at[:, 20:].add(1.0), attn_spec(0))
    np.testing.assert_allclose(out[:, :20], moved[:, :20], atol=1e-6)
    assert float(jnp.max(jnp.abs(out[:, 20:] - moved[:, 20:]))) > 1e-3


def test_rotary_takes_positions():
    """Both halves of a row at the same position ids rotate alike; the
    default is 0..S-1 as before."""
    rng = np.random.default_rng(2)
    x = normal(rng, 1, 2, 8, 16)
    twice = jnp.concatenate([x, x], axis=2)
    out = apply_rotary(twice, 16, 1e6, positions=jnp.arange(16) % 8)
    np.testing.assert_allclose(out[:, :, :8], out[:, :, 8:], atol=0)
    np.testing.assert_allclose(out[:, :, :8], apply_rotary(x, 16, 1e6), atol=0)
    np.testing.assert_allclose(
        apply_rotary(x, 16, 1e6, positions=jnp.arange(8)),
        apply_rotary(x, 16, 1e6), atol=0)
    inv = ref.rotary_frequencies(dict(head_dim=16, rope_theta=1e6))
    np.testing.assert_allclose(
        out, ref.rotary(twice, jnp.arange(16) % 8, inv), atol=1e-6)


def expert_leaves(rng, cfg=CFG, skew=0.0):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["experts_routed_over"]
    router = 0.5 * normal(rng, e, routed)
    # a skewed router: the first experts' columns dominate
    router = router.at[:, :2].multiply(1.0 + skew)
    return {"router": router, "wg": 0.3 * normal(rng, held, e, f),
            "wu": 0.3 * normal(rng, held, e, f),
            "wd": 0.3 * normal(rng, held, f, e)}


def our_experts(p, x, cfg=CFG, force_level=False):
    return gated_moe_mixer(
        p, x, top_k=cfg["num_experts_per_tok"], held=cfg["num_experts"],
        offset=cfg["expert_offset"], tile=8, force_level=force_level)


@pytest.mark.parametrize("case", ["learned", "skewed", "level", "share"])
def test_experts_without_a_shared_one_against_the_reference(case):
    """The routed sum alone: no shared leaves, no ``moe_shared`` scope.
    ``learned``: the router's own selection; ``skewed``: two experts draw
    most tokens; ``level``: the forced selection; ``share``: 4 held of 16
    from expert 8."""
    rng = np.random.default_rng(len(case))
    cfg = dict(CFG, router_force_level=int(case == "level"))
    if case == "share":
        cfg.update(num_experts=4, expert_offset=8)
    p = expert_leaves(rng, cfg, skew=3.0 if case == "skewed" else 0.0)
    x, w = normal(rng, 2, 24, 48), normal(rng, 2, 24, 48)

    def ours(p, x):
        return jnp.sum(our_experts(p, x, cfg, case == "level")[0] * w)

    def theirs(p, x):
        return jnp.sum(ref.experts(p, x, cfg, DOT) * w)

    out, counters = our_experts(p, x, cfg, case == "level")
    np.testing.assert_allclose(
        out, ref.experts(p, x, cfg, DOT), atol=2e-5, rtol=2e-5)
    assert int(counters["moe/overflow"]) == 0
    if case == "skewed":
        assert int(counters["moe/max_expert_load"]) > 2 * 48 * 4 // 16
    text = jax.jit(lambda p, x: our_experts(p, x, cfg)[0]).lower(
        p, x).as_text(debug_info=True)
    assert "moe_experts" in text and "moe_shared" not in text
    got, want = jax.grad(ours, (0, 1))(p, x), jax.grad(theirs, (0, 1))(p, x)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=3e-5)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of the 8 chips of the deployment routes over all 16 experts and
    computes its own 2; nothing is computed alike on every chip (no shared
    expert), so the parts alone add up to the reference's uncut layer. Held
    and routed counts are separate arguments."""
    rng = np.random.default_rng(8)
    p, x = expert_leaves(rng), normal(rng, 2, 24, 48)
    whole = ref.experts(p, x, CFG, DOT)
    parts = []
    for chip in range(8):
        lo = 2 * chip
        share = {"router": p["router"],
                 **{k: p[k][lo:lo + 2] for k in ("wg", "wu", "wd")}}
        cfg = dict(CFG, num_experts=2, expert_offset=lo)
        out, counters = our_experts(share, x, cfg)
        np.testing.assert_allclose(
            out, ref.experts(share, x, cfg, DOT), atol=2e-5, rtol=2e-5)
        parts.append(out)
        assert int(counters["moe/overflow"]) == 0
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5, rtol=5e-5)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-2
