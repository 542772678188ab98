"""The mixture of SiLU-gated experts (ops/moe.py:gated_moe_mixer: softmax
top-k routing renormalised over the chosen, three-matrix experts at the
model's own width through the grouped kernels, a shared expert behind a scalar
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
    """The hand-written backward of each form's hidden activation, which
    the kernels apply to a block of a tile's rows."""
    rng = np.random.default_rng(1)
    a, b, dh = normal(rng, 8, F), normal(rng, 8, F), normal(rng, 8, F)
    for form, pre in {"relu2": (a,), "swiglu": (a, b)}.items():
        act, act_bwd = moe_ops.EXPERT_FORMS[form]
        want = jax.grad(lambda *pre: jnp.sum(act(*pre) * dh),
                        tuple(range(len(pre))))(*pre)
        for got, ref_ in zip(act_bwd(*pre, dh), want):
            np.testing.assert_allclose(got, ref_, rtol=1e-5, atol=1e-6,
                                       err_msg=form)


# ----------------------------------------------------------------------
# the grouped products themselves (``grouped_expert_ffn``: the kernels
# ``moe_ffn_fwd`` and ``moe_ffn_bwd`` in interpret mode, the chunks' gathers
# and combines around them) against autodiff of a dense loop over the held
# experts, under routers that fill one chunk, many, or none
# ----------------------------------------------------------------------
TOKENS, K, ROUTED, HELD, OFFSET = 48, 3, 12, 4, 2


def dense_experts(form, u, mats, weights_t):
    """sum_e weights_t[e] * expert_e(u), every expert over every token."""
    act = moe_ops.EXPERT_FORMS[form][0]
    out = 0.0
    for e in range(weights_t.shape[0]):
        *w_in, w_out = (m[e] for m in mats)
        out = out + weights_t[e][:, None] * (act(*(u @ w for w in w_in)) @ w_out)
    return out


def routed_to(router, rng):
    """[TOKENS, K] distinct experts a token under the named router."""
    def pick(allowed, n=K):
        return rng.permutation(np.asarray(allowed))[:n]

    everyone = np.arange(ROUTED)
    held = np.arange(OFFSET, OFFSET + HELD)
    elsewhere = np.setdiff1d(everyone, held)
    if router == "level":
        rows = [pick(everyone) for _ in range(TOKENS)]
    elif router == "one_expert_takes_every_token":
        rows = [np.append(pick(np.setdiff1d(everyone, [OFFSET + 1]), K - 1),
                          OFFSET + 1) for _ in range(TOKENS)]
    elif router == "tokens_without_a_held_expert":
        rows = [pick(elsewhere if t % 3 == 0 else everyone)
                for t in range(TOKENS)]
    elif router == "an_expert_without_a_row":
        rows = [pick(np.setdiff1d(everyone, [OFFSET + 2]))
                for _ in range(TOKENS)]
    else:
        assert router == "no_held_assignment"
        rows = [pick(elsewhere) for _ in range(TOKENS)]
    return np.stack(rows).astype(np.int32)


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
@pytest.mark.parametrize("router,tile", [
    ("level", 4), ("level", 8), ("level", 64),
    ("one_expert_takes_every_token", 4),
    ("tokens_without_a_held_expert", 8),
    ("an_expert_without_a_row", 8),
    ("no_held_assignment", 8)])
def test_grouped_products_match_the_dense_loop(form, router, tile):
    """Output, du, every matrix's gradient and the routing weights'
    gradient; no assignment without a row; the tiles in use and the chunks
    they take as the shapes say."""
    rng = np.random.default_rng(len(router) + tile)
    chosen = routed_to(router, rng)
    weights = np.zeros((TOKENS, ROUTED), np.float32)
    np.put_along_axis(weights, chosen, rng.uniform(
        0.2, 1.0, chosen.shape).astype(np.float32), axis=1)
    u, probe = normal(rng, TOKENS, E), normal(rng, TOKENS, E)
    n_in = {"relu2": 1, "swiglu": 2}[form]
    mats = tuple([0.3 * normal(rng, HELD, E, F) for _ in range(n_in)]
                 + [0.3 * normal(rng, HELD, F, E)])

    def ours(u, mats, weights):
        weights_t, plan, chunk_tiles, counters = moe_ops._route_and_plan(
            u, TOKENS, lambda _x, _level: (jnp.asarray(chosen), weights),
            HELD, OFFSET, tile, False)
        return moe_ops.grouped_expert_ffn(
            u, mats, weights_t, plan, tile, form, chunk_tiles), counters

    def theirs(u, mats, weights):
        return dense_experts(form, u, mats, weights[:, OFFSET:OFFSET + HELD].T)

    out, counters = ours(u, mats, jnp.asarray(weights))
    np.testing.assert_allclose(
        out, theirs(u, mats, jnp.asarray(weights)), rtol=2e-4, atol=2e-5)
    local = chosen - OFFSET
    sizes = np.bincount(local[(local >= 0) & (local < HELD)], minlength=HELD)
    tiles = int(np.sum(-(-sizes // tile)))
    chunk_tiles = HELD - (-TOKENS * K * HELD // (ROUTED * tile))
    assert {k: int(v) for k, v in counters.items()} == {
        "moe/local_assignments": int(sizes.sum()),
        "moe/tokens_without_held_expert": int(np.sum(
            ~np.any((local >= 0) & (local < HELD), axis=1))),
        "moe/max_expert_load": int(sizes.max()), "moe/overflow": 0,
        "moe/tiles": tiles, "moe/chunks": -(-tiles // chunk_tiles)}
    assert {"one_expert_takes_every_token": int(counters["moe/chunks"]) > 1,
            "no_held_assignment": tiles == 0,
            "an_expert_without_a_row": sizes[2] == 0,
            "tokens_without_a_held_expert":
                int(counters["moe/tokens_without_held_expert"]) >= TOKENS // 3,
            }.get(router, int(counters["moe/chunks"]) == 1)
    got = jax.grad(lambda *a: jnp.sum(ours(*a)[0] * probe), (0, 1, 2))(
        u, mats, jnp.asarray(weights))
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) * probe), (0, 1, 2))(
        u, mats, jnp.asarray(weights))
    # a weight that chose nothing is not an input of ours: no gradient there
    want = (want[0], want[1], jnp.where(weights != 0, want[2], 0.0))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=2e-5 * (1 + float(jnp.max(jnp.abs(b)))),
            err_msg=str(path))


def test_the_chip_refuses_widths_that_fill_no_lane_block(monkeypatch):
    """Off the chip any width goes (interpret mode); on it an expert's
    matrices come in whole 128-lane blocks or not at all, and the refusal
    names the shape: no second, silent path."""
    from deepspeed_tpu.utils import device

    rng = np.random.default_rng(3)
    chosen = jnp.asarray(routed_to("level", rng))
    weights = jnp.ones((TOKENS, ROUTED), jnp.float32)
    mats = (normal(rng, HELD, E, F), normal(rng, HELD, F, E))
    weights_t, plan, chunk_tiles, _ = moe_ops._route_and_plan(
        normal(rng, TOKENS, E), TOKENS, lambda _x, _level: (chosen, weights),
        HELD, OFFSET, 8, False)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match=r"128-lane blocks, not \(32, 24\)"):
        moe_ops.grouped_expert_ffn(
            normal(rng, TOKENS, E), mats, weights_t, plan, 8, "relu2",
            chunk_tiles)
