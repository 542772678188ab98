"""The sublayers that JoyAI-LLM-Flash adds (models/hybrid.py kinds ``L`` and
``B``) against the plain float32 reference (benchmark/reference/joyai.py) on
seeded weights at toy widths on the CPU: multi-head latent attention
(ops/transformer.py:attention_mixer under the kind's spec, models/hybrid.py:
attention_spec) on the XLA path and through the flash kernels at unequal
widths in interpret mode; what the mechanism is made of (the rotated lanes are
the LAST ones, the key's rotated part is one vector for all heads, v has its
own width); the experts chosen by sigmoid scores plus a selection bias
(ops/moe.py:gated_moe_mixer under ``route="sigmoid"``), a bias that changes
the choice and not the weights, a skewed router with the level selection off;
and the SIXTEEN shares' expert parts, with the shared expert counted once,
adding up to the uncut layer."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.hybrid import HybridLMConfig, attention_spec
from deepspeed_tpu.ops.moe import gated_moe_mixer
from deepspeed_tpu.ops.transformer import AttentionSpec, attention_mixer

attn_ops = importlib.import_module("deepspeed_tpu.ops.attention")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import joyai as ref  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402

DOT = ref_ops.make_dot("float32")
CFG = dict(hidden_size=48, num_attention_heads=4, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=12, rms_norm_eps=1e-6, rope_theta=32000000,
           n_routed_experts=64, experts_routed_over=64, expert_offset=0,
           num_experts_per_tok=8, moe_intermediate_size=24, n_shared_experts=1,
           routed_scaling_factor=2.5)
# float32 through and through, the sums in another order: a few ulp of numbers
# of size 1. bf16 in any product would read 1e-2
TIGHT = dict(atol=3e-5, rtol=3e-5)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def mla_leaves(rng, cfg=CFG):
    """The reference's names; ``ours`` renames the gains."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return {"wqa": 0.3 * normal(rng, e, rq), "q_norm.g": 1 + 0.1 * normal(rng, rq),
            "wqb": 0.3 * normal(rng, rq, h * (nope + rope)),
            "wkva": 0.3 * normal(rng, e, rkv + rope),
            "kv_norm.g": 1 + 0.1 * normal(rng, rkv),
            "wkvb": 0.3 * normal(rng, rkv, h * (nope + dv)),
            "wo": 0.3 * normal(rng, h * dv, e)}


def ours(p):
    return {k.removesuffix(".g"): v for k, v in p.items()}


def mla_spec(cfg=CFG):
    """Kind ``L`` out of the table."""
    return attention_spec(HybridLMConfig(
        pattern="L", hidden_size=cfg["hidden_size"],
        attn_heads=cfg["num_attention_heads"], mla_q_rank=cfg["q_lora_rank"],
        mla_kv_rank=cfg["kv_lora_rank"], mla_nope_dim=cfg["qk_nope_head_dim"],
        mla_rope_dim=cfg["qk_rope_head_dim"], mla_v_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"]), "L")


def our_mla(p, x, cfg=CFG):
    return attention_mixer(ours(p), x, mla_spec(cfg))


def test_the_spec_of_the_latent_kind():
    spec = mla_spec()
    assert (spec.heads, spec.kv_heads, spec.head_dim, spec.lanes, spec.v_dim,
            spec.q_rank, spec.kv_rank, spec.scope) == (
                4, 4, 24, 8, 12, 24, 16, "attn_mla")
    assert len(spec.frequencies) == 4
    with pytest.raises(ValueError, match="latent"):
        AttentionSpec(heads=4, kv_heads=2, head_dim=24, kv_rank=16)
    with pytest.raises(ValueError, match="latent"):
        AttentionSpec(heads=4, kv_heads=4, head_dim=24, kv_rank=16, window=8)


@pytest.mark.parametrize("flash", [False, True])
def test_latent_attention_against_the_reference(flash, monkeypatch):
    """Values and every leaf's gradient over rows of 256 positions, 4 heads of
    16 + 8 q/k lanes on 12 v lanes. ``flash``: the kernels in interpret mode
    on a 4 x 4 grid of blocks (the dispatcher takes the XLA path at this
    length otherwise), q and k 24 lanes wide and v 12."""
    if flash:
        monkeypatch.setattr(attn_ops, "FLASH_MODE", "always")
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_Q", 64)
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_K", 64)
    rng = np.random.default_rng(5)
    p = mla_leaves(rng)
    x, w = normal(rng, 2, 256, 48), normal(rng, 2, 256, 48)
    text = jax.jit(lambda p, x: our_mla(p, x)).lower(p, x).as_text(
        debug_info=True)
    assert "attn_mixer/attn_mla" in text
    assert ("flash_fwd" in text) == flash
    np.testing.assert_allclose(
        our_mla(p, x), ref.mla(p, x, CFG, DOT), **TIGHT)
    got = jax.grad(lambda p, x: jnp.sum(our_mla(p, x) * w), (0, 1))(p, x)
    want = jax.grad(
        lambda p, x: jnp.sum(ref.mla(p, x, CFG, DOT) * w), (0, 1))(p, x)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=3e-5)


def test_what_the_latent_mixer_is_made_of():
    """By hand over dense arrays in float64: the last 8 of a head's 24 q/k
    lanes rotate (pairing lane i with lane i + 4), the key's rotated part is
    ONE vector a position for the four heads, the scores divide by sqrt(24),
    the context is 12 lanes a head. And each piece got wrong moves the
    output by far more than the tolerance."""
    rng = np.random.default_rng(6)
    p = {k: np.asarray(v, np.float64) for k, v in mla_leaves(rng).items()}
    x = np.asarray(normal(rng, 1, 40, 48), np.float64)
    s, h, nope, rope, dv, rkv = 40, 4, 16, 8, 12, 16

    def rms(t, g):
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True) + 1e-6) * g

    def rot(t, back=4):
        """t [S, ..., 8]; ``back``: the partner's distance."""
        inv = 32000000.0 ** (-np.arange(0, rope, 2) / rope)
        ang = np.arange(s)[:, None] * inv[None, :]
        cos, sin = np.cos(ang), np.sin(ang)
        while cos.ndim < t.ndim:
            cos, sin = cos[:, None], sin[:, None]
        a, b = t[..., :back], t[..., back:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def by_hand(first_lanes=False, per_head_key=False, scale=24.0):
        q = (rms(x[0] @ p["wqa"], p["q_norm.g"]) @ p["wqb"]).reshape(s, h, 24)
        latent = x[0] @ p["wkva"]
        kv = (rms(latent[:, :rkv], p["kv_norm.g"]) @ p["wkvb"]).reshape(
            s, h, nope + dv)
        k_r = rot(latent[:, rkv:])                          # [S, 8]
        if first_lanes:     # the WRONG lanes of q rotate
            q = np.concatenate([rot(q[..., :rope]), q[..., rope:]], -1)
        else:
            q = np.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
        out = np.zeros((s, h, dv))
        for head in range(h):
            key_part = k_r * (1 + head) if per_head_key else k_r
            k = np.concatenate([kv[:, head, :nope], key_part], -1)
            scores = q[:, head] @ k.T / np.sqrt(scale)
            scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
            probs = np.exp(scores - scores.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            out[:, head] = probs @ kv[:, head, nope:]
        return out.reshape(s, h * dv) @ p["wo"]

    got = np.asarray(our_mla({k: jnp.asarray(v, jnp.float32)
                              for k, v in p.items()}, jnp.asarray(x, jnp.float32)))
    np.testing.assert_allclose(got[0], by_hand(), atol=5e-5, rtol=5e-5)
    for wrong in (dict(first_lanes=True), dict(per_head_key=True),
                  dict(scale=16.0)):
        assert np.max(np.abs(got[0] - by_hand(**wrong))) > 1e-2, wrong


def expert_leaves(rng, cfg=CFG, skew=0.0):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["n_routed_experts"], cfg["experts_routed_over"]
    router = 0.5 * normal(rng, e, routed)
    # a skewed router: the first experts' columns dominate
    router = router.at[:, :2].multiply(1.0 + skew)
    return {"router": router, "router_bias": jnp.zeros((routed,), jnp.float32),
            "wg": 0.3 * normal(rng, held, e, f),
            "wu": 0.3 * normal(rng, held, e, f),
            "wd": 0.3 * normal(rng, held, f, e),
            "shared_wg": 0.3 * normal(rng, e, f),
            "shared_wu": 0.3 * normal(rng, e, f),
            "shared_wd": 0.3 * normal(rng, f, e)}


def our_experts(p, x, cfg=CFG, force_level=False):
    return gated_moe_mixer(
        p, x, top_k=cfg["num_experts_per_tok"], held=cfg["n_routed_experts"],
        offset=cfg["expert_offset"], tile=8, force_level=force_level,
        scale=cfg["routed_scaling_factor"], route="sigmoid")


@pytest.mark.parametrize(
    "case", ["learned", "skewed", "level", "share", "biased"])
def test_sigmoid_routed_experts_against_the_reference(case):
    """Sigmoid scores over all 64, the top-8 of score + bias, the chosen
    scores over their sum times 2.5, the shared expert as it is. ``learned``:
    the router's own selection; ``skewed``: two experts draw most tokens (the
    level selection off); ``level``: the forced selection; ``share``: 4 held
    of 64 from expert 8; ``biased``: a seeded bias joins the choice."""
    rng = np.random.default_rng(len(case))
    cfg = dict(CFG, router_force_level=int(case == "level"))
    if case == "share":
        cfg.update(n_routed_experts=4, expert_offset=8)
    p = expert_leaves(rng, cfg, skew=3.0 if case == "skewed" else 0.0)
    if case == "biased":
        p["router_bias"] = 0.3 * normal(rng, 64)
    x, w = normal(rng, 2, 24, 48), normal(rng, 2, 24, 48)

    def loss(p, x):
        return jnp.sum(our_experts(p, x, cfg, case == "level")[0] * w)

    def theirs(p, x):
        return jnp.sum(ref.experts(p, x, cfg, DOT) * w)

    out, counters = our_experts(p, x, cfg, case == "level")
    np.testing.assert_allclose(out, ref.experts(p, x, cfg, DOT), **TIGHT)
    assert int(counters["moe/overflow"]) == 0
    if case == "skewed":
        assert int(counters["moe/max_expert_load"]) > 2 * 48 * 8 // 64
    text = jax.jit(lambda p, x: our_experts(p, x, cfg)[0]).lower(
        p, x).as_text(debug_info=True)
    assert all(scope in text
               for scope in ("moe_route", "moe_experts", "moe_shared"))
    got, want = jax.grad(loss, (0, 1))(p, x), jax.grad(theirs, (0, 1))(p, x)
    # the bias chooses and takes no gradient
    assert float(jnp.max(jnp.abs(got[0]["router_bias"]))) == 0.0
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-30)
        np.testing.assert_allclose(g / scale, r / scale, atol=3e-5,
                                   err_msg=str(path))


def test_a_bias_changes_the_choice_and_not_the_weights():
    """A bias that lifts expert 63 over every score puts it among every
    token's eight; the weights stay the router's own scores: a chosen
    expert's weight is 2.5 s_e over the chosen scores' sum, bias or none."""
    from deepspeed_tpu.ops.moe import route_sigmoid_topk

    rng = np.random.default_rng(3)
    x, router = normal(rng, 48, 48), 0.5 * normal(rng, 48, 64)
    bias = jnp.zeros((64,)).at[63].set(5.0)
    plain, w0 = route_sigmoid_topk(x, router, jnp.zeros((64,)), 8, 2.5)
    chosen, w1 = route_sigmoid_topk(x, router, bias, 8, 2.5)
    assert bool(jnp.all(jnp.any(chosen == 63, -1)))
    assert not bool(jnp.all(jnp.any(plain == 63, -1)))
    scores = jax.nn.sigmoid(x @ router)
    mask = jnp.any(chosen[:, :, None] == jnp.arange(64)[None, None], 1)
    np.testing.assert_allclose(
        w1, 2.5 * scores * mask / jnp.sum(scores * mask, -1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w1, -1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w0, -1), 2.5, rtol=1e-6)


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Each of the 16 chips of the deployment routes over all 64 experts of
    this toy layer (top-8 of sigmoid scores plus a seeded bias) and computes
    its own 4, and every chip computes the shared expert alike: the routed
    parts (each chip's layer less the shared expert) and the shared expert
    ONCE add up to the reference's uncut layer. Held and routed counts are
    separate arguments."""
    rng = np.random.default_rng(8)
    p, x = expert_leaves(rng), normal(rng, 2, 24, 48)
    p["router_bias"] = 0.3 * normal(rng, 64)
    whole = ref.experts(p, x, CFG, DOT)
    shared = ref.gated_ffn(
        x, p["shared_wg"], p["shared_wu"], p["shared_wd"], DOT)

    def share_of(chip):
        lo = 4 * chip
        return ({**p, **{k: p[k][lo:lo + 4] for k in ("wg", "wu", "wd")}},
                dict(CFG, n_routed_experts=4, expert_offset=lo))

    parts, assignments = [], 0
    for chip in range(16):
        share, cfg = share_of(chip)
        out, counters = our_experts(share, x, cfg)
        if chip % 4 == 0:
            np.testing.assert_allclose(
                out, ref.experts(share, x, cfg, DOT), **TIGHT)
        parts.append(out - shared)
        assert int(counters["moe/overflow"]) == 0
        assignments += int(counters["moe/local_assignments"])
    np.testing.assert_allclose(
        sum(parts) + shared, whole, atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(parts[0] + shared - whole))) > 1e-2
    # every token's eight choices lie on some chip: the parts' weights add up
    assert assignments == 2 * 24 * 8
