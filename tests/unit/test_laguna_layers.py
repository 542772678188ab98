"""The sublayers that Laguna-S-2.1 adds (models/hybrid.py kinds ``H``, ``W``
and ``U``) against the plain float32 reference (benchmark/reference/laguna.py)
on seeded weights at toy widths on the CPU: grouped-query attention gated per
head (ops/transformer.py:attention_mixer under the kinds' specs,
models/hybrid.py:attention_spec), full with YaRN on part of the lanes and
windowed with plain rotary, on the XLA path and through the flash kernels in
interpret mode (through the fused q/k pass against the XLA functions:
test_qk_prep.py, beside the other kinds); the YaRN table against its closed
form and the factor on the rotated lanes only; the experts with a routed
scaling factor and an ungated shared expert (ops/moe.py:gated_moe_mixer), the
learned selection and a skewed router included; and the THIRTY-TWO shares'
expert parts, with the shared expert counted once, adding up to the uncut
layer."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.hybrid import HybridLMConfig, attention_spec
from deepspeed_tpu.ops.moe import gated_moe_mixer
from deepspeed_tpu.ops.transformer import (
    apply_rotary,
    attention_mixer,
    rotary_frequencies,
    yarn_frequencies,
)

attn_ops = importlib.import_module("deepspeed_tpu.ops.attention")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import laguna as ref  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402

DOT = ref_ops.make_dot("float32")
CFG = dict(hidden_size=48, num_attention_heads=4, sliding_attention_heads=6,
           num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
           full_rope_theta=500000, full_rotary_lanes=8, yarn_factor=128,
           yarn_original_positions=32, yarn_beta_fast=32, yarn_beta_slow=1,
           yarn_attention_factor=1.4852030263919618, sliding_rope_theta=10000,
           sliding_window=24, num_experts=64, experts_routed_over=64,
           expert_offset=0, num_experts_per_tok=10, moe_intermediate_size=24,
           shared_expert_intermediate_size=24, moe_routed_scaling_factor=2.5)
# the published rotary numbers (rope_parameters.full_attention, head_dim 128)
PUBLISHED = dict(full_rope_theta=500000, full_rotary_lanes=64, yarn_factor=128,
                 yarn_original_positions=8192, yarn_beta_fast=32,
                 yarn_beta_slow=1)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def frequencies(cfg, kind):
    if kind == "win":
        return rotary_frequencies(cfg["head_dim"], cfg["sliding_rope_theta"])
    return yarn_frequencies(
        cfg["full_rotary_lanes"], cfg["full_rope_theta"], cfg["yarn_factor"],
        cfg["yarn_original_positions"], cfg["yarn_beta_fast"],
        cfg["yarn_beta_slow"])


def test_yarn_table_against_its_closed_form():
    """The published table: of 32 pairs, 0-9 keep ``theta^(-2i/64)``, 18-31
    are that over 128, 10-17 lie on the ramp ``(i - 9) / 9`` between them;
    the program's and the reference's agree; the attention factor is ``0.1
    ln 128 + 1``."""
    table = frequencies(PUBLISHED, "full")
    assert table.shape == (32,) and table.dtype == np.float32
    i = np.arange(32)
    plain = 500000.0 ** (-2.0 * i / 64)
    np.testing.assert_allclose(table[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(table[18:], plain[18:] / 128, rtol=1e-6)
    ramp = (i[10:18] - 9) / 9.0
    np.testing.assert_allclose(
        table[10:18], plain[10:18] * ((1 - ramp) + ramp / 128), rtol=1e-6)
    np.testing.assert_allclose(
        table, ref.yarn_inverse_frequencies(PUBLISHED), rtol=1e-6)
    np.testing.assert_allclose(
        frequencies(CFG, "full"), ref.yarn_inverse_frequencies(CFG), rtol=1e-6)
    assert abs(0.1 * np.log(128) + 1 - CFG["yarn_attention_factor"]) < 1e-12
    # factor 1 is the plain table
    np.testing.assert_allclose(
        yarn_frequencies(64, 5e5, 1.0, 8192), rotary_frequencies(64, 5e5),
        rtol=1e-6)


def test_the_factor_scales_the_rotated_lanes_and_no_other():
    rng = np.random.default_rng(2)
    x = normal(rng, 1, 2, 12, 16)
    table = frequencies(CFG, "full")
    plain = apply_rotary(x, 8, None, frequencies=table)
    scaled = apply_rotary(x, 8, None, frequencies=table, factor=1.5)
    np.testing.assert_array_equal(scaled[..., 8:], x[..., 8:])
    np.testing.assert_allclose(scaled[..., :8], 1.5 * plain[..., :8], rtol=1e-6)
    np.testing.assert_allclose(
        scaled, ref.rotary(x, table, jnp.float32(1.5)), atol=1e-6)
    # seq_axis 1, as a projection leaves it
    np.testing.assert_allclose(
        apply_rotary(x.transpose(0, 2, 1, 3), 8, None, seq_axis=1,
                     frequencies=table, factor=1.5).transpose(0, 2, 1, 3),
        scaled, atol=1e-6)


def attn_leaves(rng, kind, cfg=CFG):
    e, d, hq = cfg["hidden_size"], cfg["head_dim"], ref.heads(cfg, kind)
    kv = cfg["num_key_value_heads"] * d
    return {"wq": 0.3 * normal(rng, e, hq * d), "wk": 0.3 * normal(rng, e, kv),
            "wv": 0.3 * normal(rng, e, kv), "wg": 0.5 * normal(rng, e, hq),
            "wo": 0.3 * normal(rng, hq * d, e)}


def attn_spec(kind, cfg=CFG):
    """Kinds ``H`` (full) and ``W`` (win) out of the table."""
    return attention_spec(HybridLMConfig(
        pattern="HW", hidden_size=cfg["hidden_size"],
        attn_heads=cfg["num_attention_heads"],
        window_attn_heads=cfg["sliding_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_lanes=cfg["full_rotary_lanes"],
        rope_theta=cfg["full_rope_theta"], yarn_factor=cfg["yarn_factor"],
        yarn_original_positions=cfg["yarn_original_positions"],
        yarn_beta_fast=cfg["yarn_beta_fast"],
        yarn_beta_slow=cfg["yarn_beta_slow"],
        rotary_attention_factor=cfg["yarn_attention_factor"],
        window=cfg["sliding_window"],
        window_rope_theta=cfg["sliding_rope_theta"]),
        {"full": "H", "win": "W"}[kind])


def our_attn(p, x, kind, cfg=CFG):
    return attention_mixer(p, x, attn_spec(kind, cfg))


@pytest.mark.parametrize("flash", [False, True])
@pytest.mark.parametrize("kind", ["full", "win"])
def test_attention_mixers_against_the_reference(kind, flash, monkeypatch):
    """Values and every leaf's gradient over a row of 256 positions: 4 heads
    (full) and 6 (windowed, 24 keys) on the same 2 kv heads. ``flash``: the
    kernels in interpret mode on a 4 x 4 grid of blocks, the band's inner
    axes 2 steps (the dispatcher takes the XLA path at this length
    otherwise)."""
    if flash:
        monkeypatch.setattr(attn_ops, "FLASH_MODE", "always")
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_Q", 64)
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_K", 64)
    rng = np.random.default_rng(len(kind))
    p = attn_leaves(rng, kind)
    x, w = normal(rng, 2, 256, 48), normal(rng, 2, 256, 48)

    def ours(p, x):
        return jnp.sum(our_attn(p, x, kind) * w)

    def theirs(p, x):
        return jnp.sum(ref.attn(p, x, CFG, DOT, kind) * w)

    np.testing.assert_allclose(
        our_attn(p, x, kind), ref.attn(p, x, CFG, DOT, kind),
        atol=2e-5, rtol=2e-5)
    got, want = jax.grad(ours, (0, 1))(p, x), jax.grad(theirs, (0, 1))(p, x)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=3e-5)


def test_the_window_cuts_and_the_gate_is_one_a_head():
    """A windowed layer's output at position i does not move with inputs
    more than 23 positions back, and a full layer's does; a gate column
    driven far negative closes its head and no other."""
    rng = np.random.default_rng(7)
    x = normal(rng, 1, 64, 48)
    moved = x.at[:, :20].add(1.0)
    for kind, reaches in (("win", False), ("full", True)):
        p = attn_leaves(rng, kind)
        gap = jnp.abs(our_attn(p, x, kind) - our_attn(p, moved, kind))
        assert (float(jnp.max(gap[:, 43:])) > 1e-4) == reaches, kind
        assert float(jnp.max(gap[:, 20:43])) > 1e-4
    # the gate reads the sublayer's input: lane 0 held at 1 and one weight
    # on it drive head 2's gate to sigmoid(-1e4) = 0, the others' to 1/2
    p, d = attn_leaves(rng, "win"), CFG["head_dim"]
    x = x.at[..., 0].set(1.0)
    even = dict(p, wg=jnp.zeros_like(p["wg"]))
    closed = dict(p, wg=even["wg"].at[0, 2].set(-1e4))
    without = dict(closed, wo=p["wo"].at[2 * d:3 * d].set(0.0))
    np.testing.assert_allclose(
        our_attn(closed, x, "win"), our_attn(without, x, "win"), atol=1e-6)
    np.testing.assert_allclose(
        our_attn(closed, x, "win"),
        our_attn(dict(even, wo=without["wo"]), x, "win"), atol=1e-6)
    assert float(jnp.max(jnp.abs(
        our_attn(closed, x, "win") - our_attn(even, x, "win")))) > 1e-3


def expert_leaves(rng, cfg=CFG, skew=0.0):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["experts_routed_over"]
    router = 0.5 * normal(rng, e, routed)
    # a skewed router: the first experts' columns dominate
    router = router.at[:, :2].multiply(1.0 + skew)
    return {"router": router, "wg": 0.3 * normal(rng, held, e, f),
            "wu": 0.3 * normal(rng, held, e, f),
            "wd": 0.3 * normal(rng, held, f, e),
            "shared_wg": 0.3 * normal(rng, e, fs),
            "shared_wu": 0.3 * normal(rng, e, fs),
            "shared_wd": 0.3 * normal(rng, fs, e)}


def our_experts(p, x, cfg=CFG, force_level=False):
    return gated_moe_mixer(
        p, x, top_k=cfg["num_experts_per_tok"], held=cfg["num_experts"],
        offset=cfg["expert_offset"], tile=8, force_level=force_level,
        scale=cfg["moe_routed_scaling_factor"])


@pytest.mark.parametrize("case", ["learned", "skewed", "level", "share"])
def test_scaled_experts_with_an_ungated_shared_one_against_the_reference(case):
    """The routed sum times 2.5 plus the shared expert as it is: no
    ``shared_gate`` leaf. ``learned``: the router's own selection;
    ``skewed``: two experts draw most tokens; ``level``: the forced
    selection; ``share``: 2 held of 64 from expert 8."""
    rng = np.random.default_rng(len(case))
    cfg = dict(CFG, router_force_level=int(case == "level"))
    if case == "share":
        cfg.update(num_experts=2, expert_offset=8)
    p = expert_leaves(rng, cfg, skew=3.0 if case == "skewed" else 0.0)
    x, w = normal(rng, 2, 24, 48), normal(rng, 2, 24, 48)

    def ours(p, x):
        return jnp.sum(our_experts(p, x, cfg, case == "level")[0] * w)

    def theirs(p, x):
        return jnp.sum(ref.experts(p, x, cfg, DOT) * w)

    out, counters = our_experts(p, x, cfg, case == "level")
    np.testing.assert_allclose(
        out, ref.experts(p, x, cfg, DOT), atol=3e-5, rtol=3e-5)
    assert int(counters["moe/overflow"]) == 0
    if case == "skewed":
        assert int(counters["moe/max_expert_load"]) > 2 * 48 * 10 // 64
    text = jax.jit(lambda p, x: our_experts(p, x, cfg)[0]).lower(
        p, x).as_text(debug_info=True)
    assert "moe_experts" in text and "moe_shared" in text
    got, want = jax.grad(ours, (0, 1))(p, x), jax.grad(theirs, (0, 1))(p, x)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=3e-5)


def test_thirty_two_shares_add_up_to_the_uncut_layer():
    """Each of the 32 chips of the deployment routes over all 64 experts of
    this toy layer (top-10) and computes its own 2, and every chip computes
    the shared expert alike: the routed parts (each chip's layer less the
    shared expert) and the shared expert ONCE add up to the reference's
    uncut layer. Held and routed counts are separate arguments."""
    rng = np.random.default_rng(8)
    p, x = expert_leaves(rng), normal(rng, 2, 24, 48)
    whole = ref.experts(p, x, CFG, DOT)
    shared = ref.gated_ffn(
        x, p["shared_wg"], p["shared_wu"], p["shared_wd"], DOT)
    parts = []
    for chip in range(32):
        lo = 2 * chip
        share = {**p, **{k: p[k][lo:lo + 2] for k in ("wg", "wu", "wd")}}
        cfg = dict(CFG, num_experts=2, expert_offset=lo)
        out, counters = our_experts(share, x, cfg)
        if chip % 8 == 0:
            np.testing.assert_allclose(
                out, ref.experts(share, x, cfg, DOT), atol=3e-5, rtol=3e-5)
        parts.append(out - shared)
        assert int(counters["moe/overflow"]) == 0
    np.testing.assert_allclose(
        sum(parts) + shared, whole, atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(parts[0] + shared - whole))) > 1e-2
    # every token's ten choices lie on some chip: the parts' weights add up
    assert sum(int(our_experts(
        {**p, **{k: p[k][2 * c:2 * c + 2] for k in ("wg", "wu", "wd")}}, x,
        dict(CFG, num_experts=2, expert_offset=2 * c))[1][
            "moe/local_assignments"]) for c in range(32)) == 2 * 24 * 10
