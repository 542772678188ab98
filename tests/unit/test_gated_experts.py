"""The mixture of SiLU-gated experts (ops/moe.py:gated_moe_mixer: softmax
top-k routing renormalised over the chosen, three-matrix experts at the
model's own width through the grouped loop, a shared expert behind a scalar
sigmoid gate) against a dense loop over all experts
(benchmark/reference/qwen3_next.py:experts), at toy size on the CPU: values
and every leaf's gradient, a skewed router, a token that finds no held
expert, the forced level selection, and the sum of the shares."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402

DOT = ref_ops.make_dot("float32")
E, F, FS = 32, 24, 40
CFG = dict(num_experts=4, experts_routed_over=16, expert_offset=4,
           num_experts_per_tok=3)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def leaves(rng, held, routed):
    return {"router": normal(rng, E, routed),
            "wg": 0.3 * normal(rng, held, E, F),
            "wu": 0.3 * normal(rng, held, E, F),
            "wd": 0.3 * normal(rng, held, F, E),
            "shared_wg": 0.3 * normal(rng, E, FS),
            "shared_wu": 0.3 * normal(rng, E, FS),
            "shared_wd": 0.3 * normal(rng, FS, E),
            "shared_gate": normal(rng, E, 1)}


def ours(p, x, cfg=CFG, tile=8, **kw):
    return moe_ops.gated_moe_mixer(
        p, x, top_k=cfg["num_experts_per_tok"], held=cfg["num_experts"],
        offset=cfg["expert_offset"], tile=tile, **kw)


@pytest.mark.parametrize("tile", [4, 8, 64])
def test_gated_experts_match_the_dense_loop(tile):
    """Output, counters and the gradient of every leaf and of the input,
    with tiles smaller and larger than any expert's run."""
    rng = np.random.default_rng(tile)
    p, x = leaves(rng, 4, 16), normal(rng, 2, 20, E)
    probe = normal(rng, *x.shape)
    out, counters = ours(p, x, tile=tile)
    np.testing.assert_allclose(
        out, ref.experts(p, x, CFG, DOT), rtol=2e-4, atol=2e-5)
    assert int(counters["moe/overflow"]) == 0
    chosen, weights = moe_ops.route_softmax_topk(
        x.reshape(-1, E), p["router"], 3)
    held = (chosen >= 4) & (chosen < 8)
    assert int(counters["moe/local_assignments"]) == int(held.sum())
    # the weights of ALL the chosen add up to one, held here or not
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x, tile=tile)[0] * probe),
                      (0, 1))(p, x)
    g_theirs = jax.grad(
        lambda p, x: jnp.sum(ref.experts(p, x, CFG, DOT) * probe), (0, 1))(p, x)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_ours),
            jax.tree_util.tree_leaves(g_theirs)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))),
            err_msg=str(path))


def test_skewed_router_and_a_token_without_a_held_expert():
    """The router sends most tokens to ONE held expert (a column of its
    matrix along the tokens' common direction), a few tokens choose no held
    expert at all and get the gated shared expert only; no overflow, and the
    result still equals the dense loop."""
    rng = np.random.default_rng(5)
    p = leaves(rng, 4, 16)
    x = normal(rng, 2, 32, E) + 2.0            # a common direction
    x = x.at[0, :3].set(-x[0, :3])             # three tokens point away
    # the held experts 4-7 like that direction, expert 5 most of all; a
    # token that points away scores them lowest
    p["router"] = p["router"].at[:, 4:8].set(0.3).at[:, 5].set(1.0)
    out, counters = ours(p, x)
    tokens = 64
    assert int(counters["moe/max_expert_load"]) >= tokens - 3 \
        > 4 * tokens * 3 / 16
    assert int(counters["moe/overflow"]) == 0
    assert int(counters["moe/tokens_without_held_expert"]) == 3
    np.testing.assert_allclose(
        out, ref.experts(p, x, CFG, DOT), rtol=2e-4, atol=2e-5)
    # such a token's output is the gated shared expert alone
    chosen, _ = moe_ops.route_softmax_topk(x.reshape(-1, E), p["router"], 3)
    alone = np.flatnonzero(~np.any((chosen >= 4) & (chosen < 8), axis=-1))
    xt = x.reshape(-1, E)[alone]
    shared = jax.nn.sigmoid(xt @ p["shared_gate"]) * ref.gated_ffn(
        xt, p["shared_wg"], p["shared_wu"], p["shared_wd"], DOT)
    np.testing.assert_allclose(
        out.reshape(-1, E)[alone], shared, rtol=2e-4, atol=2e-5)


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of a toy deployment (16 experts,
    4 to a chip) plus the shared expert counted ONCE equal the uncut
    reference's output for the whole layer; each share equals the reference
    given the same share."""
    rng = np.random.default_rng(9)
    whole = leaves(rng, 16, 16)
    x = normal(rng, 2, 24, E)
    uncut = ref.experts(
        whole, x, dict(CFG, num_experts=16, expert_offset=0), DOT)
    shared = jax.nn.sigmoid(DOT(x, whole["shared_gate"], ref_ops.X_W)) \
        * ref.gated_ffn(x, whole["shared_wg"], whole["shared_wu"],
                        whole["shared_wd"], DOT)
    total = shared
    for offset in range(0, 16, 4):
        cfg = dict(CFG, expert_offset=offset)
        share = {k: (v[offset:offset + 4] if k in ("wg", "wu", "wd") else v)
                 for k, v in whole.items()}
        out, counters = ours(share, x, cfg)
        assert int(counters["moe/overflow"]) == 0
        total = total + (out - shared)
        np.testing.assert_allclose(
            out, ref.experts(share, x, cfg, DOT), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-5)


def test_forced_level_selection_ignores_the_weights_and_matches_reference():
    cfg = dict(CFG, experts_routed_over=32, expert_offset=0,
               router_force_level=1)
    rng = np.random.default_rng(13)
    p, x = leaves(rng, 4, 32), normal(rng, 2, 96, E)
    out, counters = ours(p, x, cfg, force_level=True)
    np.testing.assert_allclose(
        out, ref.experts(p, x, cfg, DOT), rtol=2e-4, atol=2e-5)
    _, again = ours(dict(p, router=-3.0 * p["router"]), 2.0 * x, cfg,
                    force_level=True)
    assert {k: int(v) for k, v in again.items()} == {
        k: int(v) for k, v in counters.items()}
    level = 2 * 96 * 3 * 4 / 32
    assert 0.7 * level < int(counters["moe/local_assignments"]) < 1.3 * level


def test_expert_forms_backward_matches_autodiff():
    """The hand-written backward of each expert form of the grouped loop."""
    rng = np.random.default_rng(1)
    x, dy = normal(rng, 8, E), normal(rng, 8, E)
    for form, mats in {
            "relu2": (normal(rng, E, F), normal(rng, F, E)),
            "swiglu": (normal(rng, E, F), normal(rng, E, F),
                       normal(rng, F, E))}.items():
        fwd, bwd = moe_ops.EXPERT_FORMS[form]
        y, saved = fwd(x, mats)
        dx, dmats = bwd(x, mats, saved, dy)
        want = jax.grad(
            lambda x, mats: jnp.sum(fwd(x, mats)[0] * dy), (0, 1))(x, mats)
        for a, b in zip(jax.tree_util.tree_leaves((dx, dmats)),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=form)
