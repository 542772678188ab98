"""Compiled-Mosaic kernel numerics on REAL TPU hardware.

Everything in tests/unit runs the Pallas kernels in interpret mode on the
CPU mesh; the compiled TPU lowering — and the in-kernel hardware-PRNG
dropout, which interpret mode cannot execute at all — had no correctness
evidence before this tier (the analog of the reference's on-device kernel
suites, tests/unit/test_cuda_forward.py / test_cuda_backward.py:1-40).

The dropout backward regenerates its keep-mask by reseeding the TPU PRNG
per (batch*head, q granule, k granule) of 128 x 128 scores, whatever slab a
kernel computes in (ops/attention.py:_keep_mask); a
fwd/bwd mask mismatch silently corrupts gradients. The directional-
derivative test here is the direct check: with a FIXED seed the dropout
net is deterministic, so a central finite difference along a random
direction must match <grad, direction> — any mask disagreement between the
forward and either backward kernel breaks that identity by O(1).

The decode kernels (ops/decode_attention.py) are held to two references:
an exact host computation (what the kernel should produce) and the XLA
gather path they replace (ops/transformer.py:_attend_gathered — which on
the chip rounds its probabilities to the storage dtype and runs its
einsums at the MXU's default precision, so it is the looser of the two).

Run on the chip and record counts and date in docs/TESTING.md (the chip
is reached through the builder's tool, docs/TESTING.md "Running on the
chip"):

    python -m pytest tests_tpu/ -q
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import (
    flash_attention, flash_attention_packed, mha_reference,
)

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(
        jax.devices()[0].platform != "tpu",
        reason="needs real TPU hardware",
    ),
]

B, H, S, D = 2, 4, 256, 64


def _qkv(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(B, H, S, D)).astype(np.float32), dtype
    )
    return mk(), mk(), mk()


def _kv_mask(valid=192):
    m = np.zeros((B, S), np.int32)
    m[:, :valid] = 1
    return jnp.asarray(m)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-2), (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_matches_reference_compiled(dtype, tol, causal):
    q, k, v = _qkv(dtype)
    out = jax.jit(
        functools.partial(flash_attention, causal=causal)
    )(q, k, v)
    ref = mha_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal,
    )
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
    assert float(err) < tol, f"max err {float(err):.2e}"


def test_flash_fwd_with_kv_mask_compiled():
    q, k, v = _qkv()
    kvm = _kv_mask()
    out = jax.jit(flash_attention)(q, k, v, kv_mask=kvm)
    # additive-mask reference
    add = jnp.where(kvm[:, None, None, :] > 0, 0.0, -1e30)
    ref = mha_reference(q, k, v, mask=add)
    err = jnp.max(jnp.abs(out - ref))
    assert float(err) < 2e-2, f"max err {float(err):.2e}"


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference_compiled(causal):
    q, k, v = _qkv()
    w = jnp.asarray(
        np.random.default_rng(9).normal(size=(B, H, S, D)).astype(np.float32)
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * w)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * w)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        denom = float(jnp.max(jnp.abs(b))) or 1.0
        rel = float(jnp.max(jnp.abs(a - b))) / denom
        assert rel < 5e-2, f"d{name} rel err {rel:.2e}"


# (entry, [b, h, s], q/k width, v width, the mask form's keywords): the GPT-2
# cells' one block each way, and the 8 x 8 grids of 1,024-blocks of the cells
# whose kernels lower one body a class of step (PR 46), at two heads so that
# the float32 reference's [b, h, s, s] scores fit beside them
CELL_SHAPES = {
    "gpt2_one_block": ("split", (8, 20, 1024), 64, 64, {"causal": True}),
    "ouro_packed_8x8": ("packed", (1, 2, 8192), 128, 128, {"causal": True}),
    "joyai_192_128_8x8": ("split", (1, 2, 8192), 192, 128, {"causal": True}),
    "sdar_mask_8x8": ("split", (1, 2, 8192), 128, 128, {"block_diffusion": 4}),
    "laguna_band_8x8": (
        "split", (1, 2, 8192), 128, 128, {"causal": True, "window": 512}),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_flash_matches_reference_at_the_cells_shape(cell):
    """Forward and gradients against the float32 reference, bf16, no key
    mask, dropout off: at the shape both GPT-2 cells of the benchmark run
    (one block each way) and on the 8 x 8 grids of the several-block cells
    (Ouro's packed operands, JoyAI's q/k of 192 lanes on v of 128, SDAR's
    block-diffusion mask, Laguna's band), where the walk is static too."""
    import importlib

    att = importlib.import_module("deepspeed_tpu.ops.attention")
    entry, (b, h, s), d, dv, form = CELL_SHAPES[cell]
    ks = jax.random.split(jax.random.PRNGKey(25), 4)
    q, k, v, w = (
        jax.random.normal(kk, (b, h, s, width), jnp.float32).astype(jnp.bfloat16)
        for kk, width in zip(ks, (d, d, dv, dv))
    )
    block = min(s, 1024)
    tiling = att.flash_tiling(
        s, s, block, block, form.get("causal", False),
        block_diffusion=form.get("block_diffusion", 0),
        window=form.get("window", 0))
    for walk in (tiling, tiling["backward"]):
        assert walk["walk"] == "static"
        assert walk["steps"]["fetched"] == walk["steps"]["run"]

    def f32(x):
        return x.astype(jnp.float32)

    def flash(q, k, v):
        if entry == "packed":
            qkv = jnp.concatenate([_merge(q), _merge(k), _merge(v)], -1)
            out = flash_attention_packed(qkv, h, **form)
            return f32(out.reshape(b, s, h, dv).transpose(0, 2, 1, 3))
        return f32(flash_attention(q, k, v, **form))

    def reference(q, k, v):
        mask = None
        if form.get("block_diffusion"):
            mask = att.block_diffusion_mask(s, form["block_diffusion"])
        return mha_reference(
            f32(q), f32(k), f32(v), mask=mask, causal=form.get("causal", False),
            window=form.get("window", 0))

    def out_and_grads(attn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v) * f32(w)), argnums=(0, 1, 2)
        ))(q, k, v)[1], jax.jit(attn)(q, k, v)

    gf, out = out_and_grads(flash)
    gr, ref = out_and_grads(reference)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 4e-2, f"max err {err:.2e}"
    for a, r, name in zip(gf, gr, "qkv"):
        rel = float(jnp.max(jnp.abs(f32(a) - f32(r))) / jnp.max(jnp.abs(f32(r))))
        assert rel < 5e-2, f"d{name} rel err {rel:.2e}"


# (entry, [b, h, s], head width, the call's keywords): compiled, the forward's
# two orders of one grid step (PR 50) give the same bits
ORDER_SHAPES = {
    "gpt2_one_block_two_heads": (
        "packed", (2, 4, 1024), 64, {"causal": True, "bias": True}),
    "bert_key_mask_two_heads": ("packed", (2, 4, 512), 64, {"kv_mask": True}),
    "ouro_packed_4x4": ("packed", (1, 2, 4096), 128, {"causal": True}),
    "sdar_mask_4x4": ("split", (1, 2, 4096), 128, {"block_diffusion": 4}),
    "laguna_band_4x2": ("split", (1, 2, 4096), 128, {"causal": True, "window": 512}),
    "dropout_2x2": ("split", (1, 2, 2048), 128, {
        "causal": True, "dropout_rate": 0.3, "dropout_seed": 5}),
    "dropout_two_heads_a_block": ("packed", (2, 4, 1024), 64, {
        "causal": True, "dropout_rate": 0.3, "dropout_seed": 5}),
    "latent_4x4": ("latent", (1, 4, 4096), 128, {}),
}


@pytest.mark.parametrize("shape", sorted(ORDER_SHAPES))
def test_flash_forward_key_major_is_query_major_bit_for_bit(shape, monkeypatch):
    """``flash_fwd`` with the key sub-tile outermost (every chain that sees it
    advancing side by side) against the old order (one chain after another),
    both compiled at the sub-tiles ``pick_subtiles`` gives: each chain meets
    the same sub-tiles in the same order, so the context is the same bits,
    dropout's (by global head and granule) among them."""
    import importlib

    att = importlib.import_module("deepspeed_tpu.ops.attention")
    entry, (b, h, s), d, form = ORDER_SHAPES[shape]
    form = dict(form)
    ks = jax.random.split(jax.random.PRNGKey(50), 5)

    def normal(key, *dims):
        return jax.random.normal(key, dims, jnp.float32).astype(jnp.bfloat16)

    if form.pop("kv_mask", False):
        form["kv_mask"] = (
            jnp.arange(s)[None, :] < s - 37 * jnp.arange(b)[:, None]
        ).astype(jnp.int32)
    if entry == "latent":
        operands = (normal(ks[0], b, s, h * d), normal(ks[1], b, s, h * 64),
                    normal(ks[2], b, s, h * 2 * d), normal(ks[3], b, s, 64))

        def forward():
            return att.flash_attention_latent(*operands, h)
    elif entry == "packed":
        qkv = normal(ks[0], b, s, 3 * h * d)
        if form.pop("bias", False):
            form["bias"] = normal(ks[1], 3 * h * d)

        def forward():
            return flash_attention_packed(qkv, h, **form)
    else:
        q, k, v = (normal(kk, b, h, s, d) for kk in ks[:3])

        def forward():
            return flash_attention(q, k, v, **form)

    seen, real = [], att._forward_order

    def order(*a):
        seen.append(real(*a))
        return seen[-1]

    monkeypatch.setattr(att, "_forward_order", order)
    # (a new function each time: jit's cache is keyed by it)
    new = np.asarray(jax.jit(lambda: forward())().astype(jnp.float32))
    assert seen and all(
        o["order"] == "key_major" and o["chains"] > 1 for o in seen), seen
    monkeypatch.setattr(
        att, "_forward_order", lambda *a: {"order": "query_major", "chains": 1})
    old = np.asarray(jax.jit(lambda: forward())().astype(jnp.float32))
    assert np.isfinite(new).all() and new.any()
    np.testing.assert_array_equal(new, old)


def test_latent_layout_matches_the_split_path_at_the_published_widths():
    """The LATENT layout (ops/attention.py: q_nope, q_r, kv and the shared k_r
    as a latent mixer's projections write them) against the split path over
    the same numbers assembled ``[B, H, S, .]``, both compiled, bf16: JoyAI's
    128 + 64 / 128 lanes at S 8,192 (an 8 x 8 grid of 1,024-blocks), four
    heads (two programs a row): the context, dq's two parts, both halves of
    d kv, and dk_r summed over the heads."""
    import importlib

    att = importlib.import_module("deepspeed_tpu.ops.attention")
    b, h, s, nope, rope, dv = 1, 4, 8192, 128, 64, 128
    q_nope, q_r, kv, k_r, w = (
        jax.random.normal(kk, (b, s, width), jnp.float32).astype(jnp.bfloat16)
        for kk, width in zip(
            jax.random.split(jax.random.PRNGKey(47), 5),
            (h * nope, h * rope, h * (nope + dv), rope, h * dv)))
    assert att.latent_layout(b, s, h, nope, rope, dv)[0] == "latent"

    def f32(x):
        return x.astype(jnp.float32)

    def latent(*operands):
        return f32(att.flash_attention_latent(*operands, h))

    def split(q_nope, q_r, kv, k_r):
        def heads(t):
            return t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

        kv = heads(kv)
        q = jnp.concatenate([heads(q_nope), heads(q_r)], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (b, h, s, rope))],
            -1)
        out = flash_attention(q, k, kv[..., nope:], causal=True)
        return f32(out.transpose(0, 2, 1, 3).reshape(b, s, h * dv))

    def out_and_grads(attn):
        return jax.jit(jax.value_and_grad(
            lambda *o: jnp.sum(attn(*o) * f32(w)), argnums=(0, 1, 2, 3)
        ))(q_nope, q_r, kv, k_r)[1], jax.jit(attn)(q_nope, q_r, kv, k_r)

    got, out = out_and_grads(latent)
    want, ref = out_and_grads(split)
    # the same products in the same order but for the score's sum of two
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 2e-2, f"max err {err:.2e}"
    for a, r, name in zip(got, want, ("q_nope", "q_r", "kv", "k_r")):
        rel = float(jnp.max(jnp.abs(f32(a) - f32(r))) / jnp.max(jnp.abs(f32(r))))
        assert rel < 2e-2, f"d{name} rel err {rel:.2e}"


def test_flash_dropout_deterministic_per_seed():
    q, k, v = _qkv()
    f = jax.jit(
        functools.partial(flash_attention, dropout_rate=0.3)
    )
    a = f(q, k, v, dropout_seed=7)
    b = f(q, k, v, dropout_seed=7)
    c = f(q, k, v, dropout_seed=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.max(jnp.abs(a - c))) > 1e-3, "seed does not change mask"
    nodrop = jax.jit(flash_attention)(q, k, v)
    assert float(jnp.max(jnp.abs(a - nodrop))) > 1e-3, "dropout is a no-op"


@pytest.mark.parametrize("wrt", [0, 1, 2])
def test_flash_dropout_fwd_bwd_mask_consistency(wrt):
    """Central finite difference == autodiff directional derivative.

    The keep-mask depends only on (seed, tile indices) — never on the
    inputs — so with a fixed seed both f(x+h d) and f(x-h d) see the SAME
    mask and the identity is exact up to float noise. If any of the three
    kernels (fwd, dq, dkv) regenerated a different mask, backward would
    differentiate a different function and the mismatch would be O(1)."""
    q, k, v = _qkv()
    w = jnp.asarray(
        np.random.default_rng(3).normal(size=(B, H, S, D)).astype(np.float32)
    )

    def loss(*args):
        return jnp.sum(
            flash_attention(*args, dropout_rate=0.3, dropout_seed=11) * w
        )

    args = [q, k, v]
    g = jax.jit(jax.grad(loss, argnums=wrt))(*args)
    d = jnp.asarray(
        np.random.default_rng(4).normal(size=(B, H, S, D)).astype(np.float32)
    )
    h = 2e-2
    jl = jax.jit(loss)
    plus = list(args)
    plus[wrt] = args[wrt] + h * d
    minus = list(args)
    minus[wrt] = args[wrt] - h * d
    fd = (float(jl(*plus)) - float(jl(*minus))) / (2 * h)
    ad = float(jnp.sum(g * d))
    scale = max(abs(fd), abs(ad), 1.0)
    assert abs(fd - ad) / scale < 0.15, (
        f"directional derivative mismatch wrt {'qkv'[wrt]}: fd={fd:.4f} "
        f"ad={ad:.4f} — fwd/bwd dropout masks disagree"
    )


# ---------------------------------------------------------------------------
# the packed entry (PR 29): the kernels read heads out of the fused qkv
# projection's [B, S, 3*H*D] result and write [B, S, H*D]
# ---------------------------------------------------------------------------
def _merge(t):
    b, h, s, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _f32(x):
    return x.astype(jnp.float32)


def _projection(shape, seed, dtype=jnp.bfloat16):
    """q, k, v [B, H, S, D], the same numbers side by side as the
    projection lays them [B, S, 3*H*D], and a weight for the loss."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (
        jax.random.normal(kk, shape, jnp.float32).astype(dtype) for kk in ks
    )
    qkv = jnp.concatenate([_merge(q), _merge(k), _merge(v)], -1)
    return (q, k, v), qkv, _f32(_merge(w))


def _one_step_apart(a, b, what):
    """bf16 roundings of float32 sums that differ in their last bits: a few
    elements land one bf16 step apart, none further."""
    a, b = np.asarray(_f32(a)), np.asarray(_f32(b))
    off = np.abs(a - b)
    assert off.max() <= 2.0 ** -7 * np.abs(a).max(), f"{what}: {off.max():.3e}"
    assert (off > 0).mean() < 0.02, f"{what}: {(off > 0).mean():.4f} differ"


@pytest.mark.parametrize("dropout", [0.0, 0.3], ids=["plain", "dropout"])
@pytest.mark.parametrize(
    "shape", [(8, 20, 1024, 64), (2, 8, 2048, 128)],
    ids=["cells_two_heads_a_block", "d128_grid_of_blocks"],
)
def test_packed_matches_split_bit_for_bit(shape, dropout):
    """Same kernels, same products, same dropout bits by global position
    and head: the context is the SAME BITS whichever layout the operands
    arrive in. The gradients differ only through delta (a 0/1 product in
    place of a float32 reduction over a head's 64 or 128 terms)."""
    h = shape[1]
    (q, k, v), qkv, w = _projection(shape, 29)
    kw = dict(causal=True, dropout_rate=dropout, dropout_seed=5)

    def split(q, k, v):
        return _merge(flash_attention(q, k, v, **kw))

    def packed(qkv):
        return flash_attention_packed(qkv, h, **kw)

    np.testing.assert_array_equal(
        np.asarray(_f32(jax.jit(split)(q, k, v))),
        np.asarray(_f32(jax.jit(packed)(qkv))),
    )
    gs = jax.jit(jax.grad(
        lambda *a: jnp.sum(_f32(split(*a)) * w), argnums=(0, 1, 2)
    ))(q, k, v)
    gp = jax.jit(jax.grad(lambda a: jnp.sum(_f32(packed(a)) * w)))(qkv)
    _one_step_apart(jnp.concatenate([_merge(g) for g in gs], -1), gp, "dqkv")


@pytest.mark.parametrize("dropout", [0.0, 0.3], ids=["plain", "dropout"])
@pytest.mark.parametrize(
    "shape", [(8, 20, 1024, 64), (1, 16, 8192, 128)],
    ids=["gpt2_cells_one_block", "ouro_cell_8x8_blocks"],
)
def test_fused_backward_matches_the_pair(shape, dropout, monkeypatch):
    """The one key-major backward kernel (PR 33) against ``flash_bwd_dq`` +
    ``flash_bwd_dkv`` at two cells' packed shapes: the same products of the
    same rounded operands and, with dropout on, the same bits (they are a
    function of seed, head and position, not of the walk), so dq, dk and dv
    differ only by the order of their float32 sums."""
    import importlib

    # ``deepspeed_tpu.ops.attention`` the attribute is the dispatcher
    att = importlib.import_module("deepspeed_tpu.ops.attention")
    h = shape[1]
    _, qkv, w = _projection(shape, 33)
    kw = dict(causal=True, dropout_rate=dropout, dropout_seed=5)

    def grad():
        # a new function each time: nothing of the other backward's trace
        return jax.jit(jax.grad(
            lambda a: jnp.sum(_f32(flash_attention_packed(a, h, **kw)) * w)
        ))(qkv)

    s = shape[2]
    block = min(s, 1024)
    assert att.backward_plan(s, s, block, block, True)["backward"] == "fused"
    fused = grad()
    monkeypatch.setattr(att, "FUSED_DQ_VMEM_BUDGET", 0)
    assert att.backward_plan(s, s, block, block, True)["backward"] == "pair"
    _one_step_apart(fused, grad(), "dqkv")


def test_packed_bias_is_the_projections_own_sum():
    """The kernels add the projection's bias as they load: the same bf16
    sum XLA writes out, so context and gradients are the same bits as for
    the biased operand; the bias's gradient is a float32 sum over rows."""
    shape = (8, 20, 1024, 64)
    _, qkv, w = _projection(shape, 30)
    bias = jax.random.normal(
        jax.random.PRNGKey(31), (qkv.shape[-1],), jnp.float32
    ).astype(jnp.bfloat16)

    def inside(qkv, bias):
        return flash_attention_packed(qkv, shape[1], bias=bias, causal=True)

    def outside(qkv, bias):
        return flash_attention_packed(qkv + bias, shape[1], causal=True)

    def both(f):
        return jax.jit(jax.value_and_grad(
            lambda a, b: jnp.sum(_f32(f(a, b)) * w), argnums=(0, 1)
        ))(qkv, bias)

    (li, (gi, bi)), (lo, (go, bo)) = both(inside), both(outside)
    assert float(li) == float(lo)
    np.testing.assert_array_equal(np.asarray(_f32(gi)), np.asarray(_f32(go)))
    _one_step_apart(bo, bi, "dbias")


def test_packed_dropout_fwd_bwd_mask_consistency():
    """The directional derivative of the packed entry with dropout on, as
    ``test_flash_dropout_fwd_bwd_mask_consistency`` for the split one: a
    head's bits are drawn by its global index from a program that holds
    two heads, in the forward and in both backward kernels."""
    shape = (B, H, S, D)
    _, qkv, w = _projection(shape, 32, jnp.float32)

    def loss(x):
        return jnp.sum(flash_attention_packed(
            x, H, causal=True, dropout_rate=0.3, dropout_seed=11) * w)

    g = jax.jit(jax.grad(loss))(qkv)
    d = jax.random.normal(jax.random.PRNGKey(33), qkv.shape, jnp.float32)
    h, jl = 2e-2, jax.jit(loss)
    fd = (float(jl(qkv + h * d)) - float(jl(qkv - h * d))) / (2 * h)
    ad = float(jnp.sum(g * d))
    assert abs(fd - ad) / max(abs(fd), abs(ad), 1.0) < 0.15, (
        f"directional derivative mismatch: fd={fd:.4f} ad={ad:.4f}"
    )


def test_train_with_attention_dropout_converges():
    """Statistical tier: a small causal LM trained THROUGH the flash
    dropout path (rate 0.1) must reduce loss with finite grads — the
    end-to-end form of the mask-consistency evidence."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(
        vocab_size=256, n_positions=S, n_embd=128, n_layer=2, n_head=4,
        dropout=0.1,  # feeds BOTH attn_dropout_ratio and hidden_dropout
    )
    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    # learnable structure: next token = current token + 1 (mod vocab)
    base = rng.integers(0, 256, (8, S + 1)).astype(np.int32)
    seq = np.cumsum(np.ones_like(base), axis=1) % 7 + (base[:, :1] % 13)
    ids = (seq[:, :-1] % 256).astype(np.int32)
    tgt = (seq[:, 1:] % 256).astype(np.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        jnp.asarray(ids[:2]), jnp.asarray(tgt[:2]),
    )["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
        },
    )
    losses = []
    for _ in range(30):
        loss = engine(ids, tgt)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert engine.skipped_steps == 0
    assert losses[-1] < 0.7 * losses[0], losses


def test_pallas_lamb_matches_xla_lamb_compiled():
    from deepspeed_tpu.ops.optimizers import Lamb
    from deepspeed_tpu.ops.pallas import FusedLamb

    rng = np.random.default_rng(0)
    params = {
        "w": jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(128,)).astype(np.float32)),
    }
    grads = {
        "w": jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(128,)).astype(np.float32)),
    }
    xla = Lamb(weight_decay=0.01)
    fused = FusedLamb(weight_decay=0.01)
    lr = jnp.float32(1e-2)
    p1, s1, a1 = jax.jit(xla.apply)(params, grads, xla.init(params), lr)
    p2, s2, a2 = jax.jit(fused.apply)(params, grads, fused.init(params), lr)
    for key in params:
        np.testing.assert_allclose(
            np.asarray(p1[key]), np.asarray(p2[key]), rtol=1e-5, atol=1e-6
        )
    for c1, c2 in zip(a1["lamb_coeffs"], a2["lamb_coeffs"]):
        np.testing.assert_allclose(
            float(c1), float(c2), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# decode kernels (ops/decode_attention.py), compiled; tests/unit/
# test_chip_compile.py holds the no-chip compile rehearsal of both
# ---------------------------------------------------------------------------
def _decode_case(dtype, seed=11):
    """4 slots over a 16-token-page pool at GPT-2 large heads (20 x 64):
    slot 0 three full pages and a partly filled fourth, slot 1 DEAD (all
    null pages), slot 2 one token into its first page, slot 3 every page
    of its table, last token of the last page."""
    rng = np.random.default_rng(seed)
    slots, heads, hd, bs, mb, pages = 4, 20, 64, 16, 8, 40
    q = rng.normal(size=(slots, heads, hd)).astype(np.float32)
    kp = rng.normal(size=(pages, bs, heads, hd)).astype(np.float32)
    vp = rng.normal(size=(pages, bs, heads, hd)).astype(np.float32)
    tables = np.zeros((slots, mb), np.int32)
    tables[0, :4] = [5, 9, 2, 17]
    tables[2, :1] = [30]
    tables[3] = [1, 3, 4, 6, 7, 8, 10, 11]
    positions = np.asarray([3 * bs + 6, 0, 0, mb * bs - 1], np.int32)
    cast = lambda a: jnp.asarray(a, dtype)
    return cast(q), cast(kp), cast(vp), tables, positions


def _decode_exact(q, kp, vp, tables, positions):
    q, kp, vp = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (q, kp, vp))
    slots, heads, hd = q.shape
    bs = kp.shape[1]
    out = np.zeros((slots, heads, hd))
    for b in range(slots):
        if tables[b, 0] == 0:
            continue  # dead slot: exact zeros
        k = kp[tables[b]].reshape(-1, heads, hd)[: positions[b] + 1]
        v = vp[tables[b]].reshape(-1, heads, hd)[: positions[b] + 1]
        s = np.einsum("hd,khd->hk", q[b], k) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hk,khd->hd", p, v)
    return out


@pytest.mark.parametrize(
    "shape",
    [(36, 1280, 5120), (6, 1600, 6400), (6, 6400, 1600), (4, 1600, 1600),
     (12576, 1280), (50304, 1600)],
    ids=lambda s: "x".join(map(str, s)),
)
def test_adam_leaf_update_matches_plain_at_the_cells_shape(shape):
    """The one-pass kernel of the reduced-state Adam update (ops/pallas.py:
    adam_leaf_update, PR 27) compiled by Mosaic, against the plain XLA
    update over the same ``adam_core``, on GPT-2 large's largest leaf
    [36, 1280, 5120] under the cells' recipe (bf16 parameters + int8
    compensation, int8 first moment in runs of 1,280, bf16 second moment),
    from a state with one step behind it: masters to one compensation code,
    scales to float32 rounding, codes to one rounding tie; then a closed
    gate rewrites every stored byte unchanged. Then the shapes whose rows
    are no multiple of 128 (a ragged last tile): GPT-2 1.5B's 1,600-row
    stacks, the 12,576 rows a chip of GPT-2 large's token table under
    dp 4; and 1.5B's 1,600-WIDE stacks and token table, which the chip
    stores rows-minor and the kernel takes transposed."""
    from deepspeed_tpu.ops import pallas as kernels
    from deepspeed_tpu.ops import quant
    from deepspeed_tpu.ops.optimizers import Adam

    draw = lambda seed, scale: {"w": (
        jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * scale
    ).astype(jnp.bfloat16)}
    opt = Adam(state_dtype="int8", master_compensation=True)
    kw = dict(grad_scale=jnp.float32(0.5), mom=jnp.float32(0.9))

    def step(kernel):
        return jax.jit(lambda p, g, s, gate: opt.apply(
            p, g, s, jnp.float32(1e-3), gate=gate, kernel=kernel, **kw
        )[:2])

    params = draw(0, 0.02)
    state = opt.init(params)
    assert kernels.adam_kernel_run(
        params["w"], state["mu"]["w"], state["nu"]["w"]
    ) == quant.run_length(shape[-1])
    params, state = step(False)(params, draw(1, 1e-2), state, jnp.bool_(True))
    grads = draw(2, 1e-2)
    p1, s1 = step(True)(params, grads, state, jnp.bool_(True))
    p2, s2 = step(False)(params, grads, state, jnp.bool_(True))

    @jax.jit
    def gaps(p0, s0, p1, s1, p2, s2):
        m0 = quant.decode_master(p0["w"], s0["comp"]["w"])
        m1 = quant.decode_master(p1["w"], s1["comp"]["w"])
        m2 = quant.decode_master(p2["w"], s2["comp"]["w"])
        codes = jnp.abs(
            s1["mu"]["w"]["q"].astype(jnp.int32)
            - s2["mu"]["w"]["q"].astype(jnp.int32)
        )
        scale = s2["mu"]["w"]["scale"]
        nu1 = s1["nu"]["w"].astype(jnp.float32)
        nu2 = s2["nu"]["w"].astype(jnp.float32)
        return {
            # beyond one compensation code (2^-8 / 127 of the master)
            "master": jnp.max(jnp.abs(m1 - m2) - 4e-5 * jnp.abs(m2)),
            "moved": jnp.mean(jnp.abs(m2 - m0) > 0),
            "codes_max": jnp.max(codes),
            "codes_share": jnp.mean(codes > 0),
            "scale": jnp.max(
                jnp.abs(s1["mu"]["w"]["scale"] - scale) / (scale + 1e-30)
            ),
            "nu": jnp.max(jnp.abs(nu1 - nu2) / (nu2 + 1e-30)),
        }

    got = {
        k: float(v)
        for k, v in gaps(params, state, p1, s1, p2, s2).items()
    }
    assert got["moved"] > 0.99, got  # the step is no no-op
    assert got["master"] <= 1e-8, got  # float32 noise of a 1e-3 step
    assert got["codes_max"] <= 1 and got["codes_share"] < 1e-3, got
    assert got["scale"] <= 1e-5, got
    assert got["nu"] <= 2 ** -7, got  # one bf16 step at a rounding tie

    p3, s3 = step(True)(p1, grads, s1, jnp.bool_(False))
    same = jax.jit(lambda a, b: jax.tree_util.tree_reduce(
        jnp.logical_and,
        jax.tree_util.tree_map(lambda x, y: jnp.all(x == y), a, b),
    ))
    assert bool(same((p1, s1), (p3, s3)))


@pytest.mark.parametrize(
    "dtype,exact_tol,xla_tol",
    [(jnp.float32, 1e-4, 4e-2), (jnp.bfloat16, 2e-2, 4e-2)],
)
def test_paged_flash_decode_compiled(dtype, exact_tol, xla_tol):
    from deepspeed_tpu.ops.decode_attention import paged_flash_decode
    from deepspeed_tpu.ops.transformer import _attend_gathered

    q, kp, vp, tables, positions = _decode_case(dtype)
    t, pos = jnp.asarray(tables), jnp.asarray(positions)
    out = jax.jit(paged_flash_decode)(q, kp, vp, t, pos)
    assert out.dtype == dtype
    out = np.asarray(out.astype(jnp.float32))
    assert np.all(out[1] == 0.0), "dead slot must emit exact zeros"
    exact = _decode_exact(q, kp, vp, tables, positions)
    err = np.max(np.abs(out - exact))
    assert err < exact_tol, f"vs exact: max err {err:.2e}"

    def gather_path(q, kp, vp, t, pos):  # the XLA path the kernel replaces
        b, mb = t.shape
        full = lambda pool: pool[t].reshape(
            b, mb * pool.shape[1], pool.shape[2], pool.shape[3]
        ).transpose(0, 2, 1, 3)
        return _attend_gathered(
            q, full(kp), full(vp), pos, live=t[:, 0] != 0
        )

    ref = np.asarray(
        jax.jit(gather_path)(q, kp, vp, t, pos).astype(jnp.float32)
    )
    assert np.all(ref[1] == 0.0)
    err = np.max(np.abs(out - ref))
    assert err < xla_tol, f"vs XLA gather path: max err {err:.2e}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_sgmv_compiled(dtype, tol=1e-2):
    """Mixed adapter ids including 0 (the all-zeros identity row) at the
    qkv projection's shape, rank 8; vs the XLA gather-einsum the kernel
    replaces and an exact host product. The tolerance is relative to the
    largest output (~11 here): both dots feed the MXU, which multiplies
    f32 operands at bf16 precision by default, exactly as the XLA path
    does (first chip run: 4.9e-2 absolute on f32 inputs)."""
    from deepspeed_tpu.ops.decode_attention import lora_sgmv

    rng = np.random.default_rng(5)
    b, din, r, dout, n = 8, 1280, 8, 3840, 4
    a_pool = rng.normal(size=(n + 1, din, r)).astype(np.float32) / np.sqrt(din)
    b_pool = rng.normal(size=(n + 1, r, dout)).astype(np.float32)
    a_pool[0] = 0.0
    b_pool[0] = 0.0
    x = rng.normal(size=(b, din)).astype(np.float32)
    ids = np.asarray([2, 0, 4, 1, 0, 3, 3, 2], np.int32)
    xd, ad, bd = (jnp.asarray(v, dtype) for v in (x, a_pool, b_pool))
    out = np.asarray(jax.jit(lora_sgmv)(xd, ad, bd, jnp.asarray(ids)))
    assert out.dtype == np.float32 and out.shape == (b, dout)
    assert np.all(out[[1, 4]] == 0.0), "identity row must contribute exact 0"

    f64 = lambda v: np.asarray(v.astype(jnp.float32), np.float64)
    t = np.einsum("bi,bir->br", f64(xd), f64(ad)[ids])
    exact = np.einsum("br,bro->bo", t, f64(bd)[ids])
    scale = np.abs(exact).max()
    err = np.max(np.abs(out - exact)) / scale
    assert err < tol, f"vs exact: max relative err {err:.2e}"

    def gather_einsum(x, a, bp, ids):
        t = jnp.einsum("bi,bir->br", x, a[ids])
        return jnp.einsum("br,bro->bo", t, bp[ids]).astype(jnp.float32)

    ref = np.asarray(jax.jit(gather_einsum)(xd, ad, bd, jnp.asarray(ids)))
    err = np.max(np.abs(out - ref)) / scale
    assert err < 2 * tol, f"vs XLA gather-einsum: max relative err {err:.2e}"


def test_device_sync_fence_orders_behind_compute():
    """utils/timers._device_sync enqueues a trivial program per local
    device and blocks on it; it is a fence only if the device runs its
    programs in order. Dispatch ~a quarter second of matmuls, fence, and
    the result must already be there."""
    from deepspeed_tpu.utils.timers import _device_sync

    @jax.jit
    def busy(x):
        return jax.lax.fori_loop(
            0, 48, lambda _, a: (a @ a) * jnp.bfloat16(1e-2), x
        )

    x = jnp.ones((8192, 8192), jnp.bfloat16)
    busy(x).block_until_ready()  # compile outside the check
    out = busy(x)
    _device_sync()
    assert out.is_ready(), "the fence returned before the work it follows"


def test_gated_delta_rule_kernels_match_the_recurrence_at_the_cells_shape():
    """``gdn_fwd`` and ``gdn_bwd`` compiled, at the shape of the benchmark's
    Qwen3-Next cell (one row of 16,384 positions, 16 key heads serving 32
    value heads of 128, chunks of 64, bf16 operands and float32 gates),
    against the float32 per-position recurrence: the output and the gradient
    of q, k, v, the log decay, beta and the state before the first
    position."""
    from deepspeed_tpu.ops import linear_attention as la

    s, hk, hv, d, chunk = 16384, 16, 32, 128, 64
    assert la.gdn_path(1, s, hk, hv, d, d, chunk)["path"] == "kernel"
    ks = jax.random.split(jax.random.PRNGKey(31), 7)
    q = (la.l2_normalise(jax.random.normal(ks[0], (1, s, hk, d))) * d ** -0.5
         ).astype(jnp.bfloat16)
    k = la.l2_normalise(jax.random.normal(ks[1], (1, s, hk, d))).astype(
        jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, hv, d)).astype(jnp.bfloat16)
    # the cell's decays: a time step of 1e-3..1e-1 times a rate of 1..16
    g = -jnp.exp(jax.random.uniform(ks[3], (1, s, hv), minval=-7.0, maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, s, hv)))
    s0 = 0.1 * jax.random.normal(ks[5], (1, hk, hv // hk, d, d))
    w = jax.random.normal(ks[6], (1, s, hv, d))

    def f32(x):
        return x.astype(jnp.float32)

    def kernels(q, k, v, g, beta, s0):
        return f32(la.gated_delta_rule_chunked(
            q, k, v, g, beta, chunk, initial_state=s0))

    def recurrence(q, k, v, g, beta, s0):
        r = hv // hk

        def step(state, inp):
            q_t, k_t, v_t, g_t, b_t = inp
            state = state * jnp.exp(g_t)[..., None, None]
            u = b_t[..., None] * (
                v_t - jnp.sum(state * k_t[..., :, None], axis=-2))
            state = state + k_t[..., :, None] * u[..., None, :]
            return state, jnp.sum(state * q_t[..., :, None], axis=-2)

        seq = tuple(jnp.moveaxis(t, 1, 0) for t in (
            jnp.repeat(f32(q), r, 2), jnp.repeat(f32(k), r, 2), f32(v), g,
            beta))
        seq = tuple(t.reshape((s // 128, 128) + t.shape[1:]) for t in seq)
        _, o = jax.lax.scan(
            jax.checkpoint(lambda st, inp: jax.lax.scan(step, st, inp)),
            s0.reshape(1, hv, d, d), seq)
        return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)

    def out_and_grads(rule):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(rule(*a) * w), argnums=range(6)
        ))(q, k, v, g, beta, s0), jax.jit(rule)(q, k, v, g, beta, s0)

    gk, out = out_and_grads(kernels)
    gr, ref = out_and_grads(recurrence)
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert err < 2e-2, f"max err {err:.2e} of the largest output"
    for a, r, name in zip(gk, gr, "q k v g beta state".split()):
        rel = float(jnp.max(jnp.abs(f32(a) - f32(r))) / jnp.max(jnp.abs(f32(r))))
        assert rel < 5e-2, f"d{name} rel err {rel:.2e}"


@pytest.mark.parametrize("form,held,top_k,latent,inter,tile", [
    ("relu2", 16, 22, 1024, 2688, 384),     # the Nemotron cell's experts
    ("swiglu", 32, 10, 2048, 512, 352)])    # the Qwen3-Next cell's
def test_grouped_expert_kernels_match_the_float32_loop_at_the_cells_widths(
        form, held, top_k, latent, inter, tile):
    """``moe_ffn_fwd`` and ``moe_ffn_bwd`` compiled, an expert's matrices
    whole in VMEM at the cells' widths and tile sizes, bf16 rows and
    weights under the forced-level selection over 512 experts (8,192
    tokens: one chunk) and under a router that sends every token to ONE
    held expert too (more tiles than a chunk holds): output, du, every
    matrix's gradient and the routing weights' gradient against a float32
    loop over the held experts at the highest precision."""
    from deepspeed_tpu.ops import moe

    tokens, routed = 8192, 512
    ks = jax.random.split(jax.random.PRNGKey(37), 8)
    n_in = {"relu2": 1, "swiglu": 2}[form]
    mats = tuple(
        [0.03 * jax.random.normal(ks[i], (held, latent, inter))
         for i in range(n_in)]
        + [0.03 * jax.random.normal(ks[2], (held, inter, latent))])
    u = jax.random.normal(ks[3], (tokens, latent))
    probe = jax.random.normal(ks[4], (tokens, latent))
    scores = jax.nn.sigmoid(jax.random.normal(ks[5], (tokens, routed)))
    act = moe.EXPERT_FORMS[form][0]

    def kernels(chosen, u, mats, weights):
        weights_t, plan, chunk_tiles, counters = moe._route_and_plan(
            u, tokens, lambda _x, _level: (chosen, weights), held, 0, tile,
            False)
        return moe.grouped_expert_ffn(
            u.astype(jnp.bfloat16),
            tuple(m.astype(jnp.bfloat16) for m in mats), weights_t, plan,
            tile, form, chunk_tiles), counters

    def loop(u, mats, weights):
        dot = functools.partial(jnp.dot, precision="highest")
        out = 0.0
        for e in range(held):
            *w_in, w_out = (m[e] for m in mats)
            out = out + weights[:, e, None] * dot(
                act(*(dot(u, w) for w in w_in)), w_out)
        return out

    for skew in (False, True):
        selection = moe.level_selection_scores(jnp.arange(tokens), routed)
        if skew:
            selection = selection.at[:, 3].set(2.0 ** 25)
        chosen, picked = moe._top_k_of(selection, scores, top_k)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
        out, counters = jax.jit(kernels)(chosen, u, mats, weights)
        assert int(counters["moe/overflow"]) == 0
        assert (int(counters["moe/chunks"]) > 1) == skew, counters
        ref = jax.jit(loop)(u, mats, weights)
        err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        assert err < 2e-2, f"skew={skew}: max err {err:.2e} of the largest"
        got = jax.jit(jax.grad(lambda *a: jnp.sum(
            kernels(chosen, *a)[0] * probe), (0, 1, 2)))(u, mats, weights)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(
            loop(*a) * probe), (0, 1, 2)))(u, mats, weights)
        want = (want[0], want[1], jnp.where(weights != 0, want[2], 0.0))
        for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want)):
            rel = float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))
            assert rel < 5e-2, f"skew={skew} {path}: rel err {rel:.2e}"


@pytest.mark.parametrize("cell", ["sdar", "ouro", "qwen"])
def test_qk_prep_kernels_match_the_xla_functions_at_the_cells_shapes(cell):
    """``qk_prep_fwd`` and ``qk_prep_bwd`` compiled, at the three cells'
    shapes (SDAR: 32 heads of 128 over 2 x 16,384 rows whose two halves
    repeat the position ids, plain gains; Ouro: q | k of a packed 16-head
    product rotated in place, v's lanes bit-equal; Qwen3-Next: 16 heads of
    256, zero-centred gains, 64 lanes rotated), bf16, against ``apply_rotary(rms_norm(..))`` compiled by XLA:
    the result, the projection's cotangent and the gain's gradient, the
    largest gap in steps of bfloat16 at the largest magnitude of the head's
    row. The kernels round once where the XLA functions round after the norm
    and after the rotation, so a step or two either way is what two correct
    programs differ by."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "qk_prep_alone", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "qk_prep_alone.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    c = tool.cells[cell]
    width = (3 if c["mixer"] == "R" else 1) * c["H"] * c["D"]
    ks = jax.random.split(jax.random.PRNGKey(39), 3)
    x = jax.random.normal(ks[0], (c["B"], c["S"], width)).astype(jnp.bfloat16)
    gain = (1.0 - c["zc"] + 0.1 * jax.random.normal(ks[1], (c["D"],))).astype(
        jnp.bfloat16)
    got = {}
    for path in ("fused", "xla"):
        f = tool.pass_of(c, path)
        out = jax.jit(f)(x, gain)
        probe = jax.random.normal(ks[2], out.shape).astype(jnp.bfloat16)
        got[path] = (out, *jax.jit(jax.grad(
            lambda x, g: jnp.sum((f(x, g) * probe).astype(jnp.float32)),
            (0, 1)))(x, gain))
    gaps = {name: tool.steps(a, b) for name, a, b in zip(
        ("out", "dx", "dgain"), got["fused"], got["xla"])}
    print(cell, gaps)
    assert gaps["out"] <= 2 and gaps["dx"] <= 4, gaps
    if c["mixer"] == "R":
        v = 2 * c["H"] * c["D"]
        assert jnp.array_equal(got["fused"][0][..., v:], x[..., v:])
        assert jnp.array_equal(got["fused"][1][..., v:], got["xla"][1][..., v:])
    else:
        assert gaps["dgain"] <= 4, gaps


def test_gdn_glue_kernels_match_the_xla_functions_at_the_cells_shape():
    """``gdn_in_fwd`` / ``gdn_in_bwd`` and ``gdn_out_fwd`` / ``gdn_out_bwd``
    compiled, at the Qwen3-Next cell's shape (one row of 16,384 positions, 16
    key heads on 32 value heads of 128, 4 taps, bf16), against
    ``gdn_inputs`` and ``gated_head_rms_norm`` compiled by XLA: q, k, v, the
    projection's cotangent, ``d conv_w``, the gated output, ``d o``, ``d z``
    and the gain's gradient, the largest gap in steps of bfloat16 at the
    largest magnitude of the head's row. The kernels round once: against the
    XLA functions over the same operands in float32 they are within half a
    step (a tie rounds either way); against them in bf16, which round after
    every tap of the convolution, after SiLU and after the norm, within a few
    steps (my chip run, PR 41: 1 for q, k, v, d o, d z and d conv_w, 5 for d
    mixed, 0 for the gated output and the gain's gradient)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "gdn_glue_alone", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "gdn_glue_alone.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    c = tool.CELL
    from deepspeed_tpu.ops import gdn_glue

    assert gdn_glue.gdn_glue_path(
        1, c["S"], c["Hk"], c["Hv"], c["dk"], c["dv"], c["K"])[0] == "fused"
    gaps = tool.gaps(c)
    print(gaps)
    assert max(gaps["xla_float32"].values()) <= 0.51, gaps
    assert max(gaps["xla_bf16"].values()) <= 8, gaps
