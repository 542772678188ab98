"""The flash kernels at UNEQUAL head widths (ops/attention.py: q and k of Dqk
lanes, v, the context, dO and dv of Dv), in interpret mode on the CPU against
``mha_reference``: the forward, ``lse``, and dq / dk / dv of the fused backward
and of the pair, at a latent mixer's 192 / 128 and at one other pair of widths,
on one block and on a grid of blocks; the dispatcher and the sharded route take
them; and a caller with equal widths gets the program it got before."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

att = importlib.import_module("deepspeed_tpu.ops.attention")


def operands(seed, b, h, s, dqk, dv, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(
        jax.random.normal(k, (b, h, s, d), jnp.float32).astype(dtype)
        for k, d in zip(keys, (dqk, dqk, dv, dv)))


# float32 operands through float32 accumulation: what is left is the order of
# the sums (blocks of keys against one softmax row), a few ulp of numbers of
# size 1; bf16 products where float32 is stated would read 1e-2
TIGHT = dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [1024, 128, 64])
@pytest.mark.parametrize("dqk,dv", [(192, 128), (48, 16), (64, 128)])
def test_forward_and_gradients_against_the_reference(dqk, dv, block):
    """``block`` 1024: one block each way; 128 and 64: grids of 2 x 2 and
    4 x 4 (a body ON the diagonal and one UNDER it, the causal skip of whole
    blocks); the walk static on all three."""
    q, k, v, g = operands(dqk + dv, 2, 2, 256, dqk, dv)
    tiling = att.flash_tiling(
        256, 256, min(block, 256), min(block, 256), True, lanes=dqk, itemsize=4)
    assert tiling["walk"] == tiling["backward"]["walk"] == "static"

    def ours(q, k, v):
        return jnp.sum(att.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block) * g)

    def theirs(q, k, v):
        return jnp.sum(att.mha_reference(q, k, v, causal=True) * g)

    out = att.flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    assert out.shape == (2, 2, 256, dv)
    np.testing.assert_allclose(
        out, att.mha_reference(q, k, v, causal=True), **TIGHT)
    got = jax.grad(ours, (0, 1, 2))(q, k, v)
    want = jax.grad(theirs, (0, 1, 2))(q, k, v)
    for a, r, like in zip(got, want, (q, k, v)):
        assert a.shape == like.shape
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(a / scale, r / scale, atol=2e-5)


@pytest.mark.parametrize("block", [128, 64, 32])
def test_lse_and_residuals_follow_the_value_width(block):
    """``lse`` is the scores' log-sum-exp at 1 / sqrt(Dqk) (one float32 a
    query row); the named residual ``flash_out`` has v's width. On one block
    and on grids of 2 x 2 and 4 x 4."""
    q, k, v, _ = operands(3, 1, 2, 128, 192, 128)
    out, residuals = att._flash_fwd(
        q, k, v, None, jnp.int32(0), True, 192 ** -0.5, 0.0, block, block)
    lse = residuals[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 192 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), scores, -1e30)
    np.testing.assert_allclose(
        lse.reshape(1, 2, 128), jax.nn.logsumexp(scores, -1), **TIGHT)
    assert residuals[-2].shape == out.shape == (1, 2, 128, 128)


@pytest.mark.parametrize("sq,sk", [(256, 256), (512, 256)])
def test_the_pair_of_backward_kernels_takes_them_too(sq, sk, monkeypatch):
    """A budget of 0 forces ``flash_bwd_dq`` + ``flash_bwd_dkv``; on a 2 x 2
    grid, and on 4 x 2 with the diagonal two blocks down."""
    q, _, _, g = operands(5, 1, 2, sq, 192, 128)
    _, k, v, _ = operands(6, 1, 2, sk, 192, 128)

    def loss(q, k, v):
        return jnp.sum(att.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128) * g)

    fused = jax.grad(loss, (0, 1, 2))(q, k, v)
    monkeypatch.setattr(att, "FUSED_DQ_VMEM_BUDGET", 0)
    pair = jax.grad(loss, (0, 1, 2))(q, k, v)
    for a, b in zip(fused, pair):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_key_mask_and_bf16_operands():
    """A padding mask beside the unequal widths, and bf16 operands: the
    kernels round p and ds to bf16 before their products, which reads 2e-2 of
    the largest entry (the equal-width kernels' own tolerance in
    test_flash_attention.py)."""
    q, k, v, g = operands(7, 2, 2, 256, 192, 128, jnp.bfloat16)
    valid = (jnp.arange(256)[None, :] < jnp.array([[256], [200]])).astype(
        jnp.int32)
    bias = jnp.where(valid > 0, 0.0, att.NEG_INF)[:, None, None, :]

    def f32(t):
        return t.astype(jnp.float32)

    def ours(q, k, v):
        return jnp.sum(f32(att.flash_attention(
            q, k, v, kv_mask=valid, block_q=128, block_k=128)) * f32(g))

    def theirs(q, k, v):
        return jnp.sum(f32(att.mha_reference(q, k, v, mask=bias)) * f32(g))

    got = jax.grad(ours, (0, 1, 2))(q, k, v)
    want = jax.grad(theirs, (0, 1, 2))(q, k, v)
    for a, r in zip(got, want):
        scale = float(jnp.max(jnp.abs(f32(r))))
        np.testing.assert_allclose(f32(a) / scale, f32(r) / scale, atol=2e-2)


def test_the_dispatcher_and_the_sharded_route_take_unequal_widths(monkeypatch):
    from deepspeed_tpu.parallel.mesh import build_mesh

    q, k, v, _ = operands(9, 2, 2, 256, 48, 16)
    want = att.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        att.attention(q, k, v, causal=True), want, **TIGHT)
    mesh = build_mesh(devices=jax.devices()[:2])
    monkeypatch.setattr(att, "FLASH_MODE", "always")
    text = jax.jit(lambda q, k, v: att.attention(
        q, k, v, causal=True, mesh=mesh)).lower(q, k, v).as_text()
    assert "shard_map" in text or "sdy.manual_computation" in text
    np.testing.assert_allclose(
        att.attention(q, k, v, causal=True, mesh=mesh), want, **TIGHT)


def test_equal_widths_lower_to_the_program_they_had():
    """With v as wide as q the operands carry no second width and every
    BlockSpec, scratch and result shape is the one the kernels had: the
    lowered text of a forward + backward at 128 / 128 holds no shape of 192
    (tests/unit/test_looped_stack.py pins the five hybrid configurations'
    whole programs on the parent's code)."""
    q, k, v, g = operands(11, 1, 2, 256, 128, 128)
    ops = att._split_operands(q, v)
    assert ops.v_width == ops.head_dim == ops.v_lanes == ops.block_lanes == 128
    plain = att._Operands(1, 2, 128, packed=False)
    assert (plain.v_width, plain.v_lanes) == (128, 128)
    for made in (ops, plain):
        assert made.result(256, jnp.float32, v=True) == made.result(
            256, jnp.float32)

    def step(q, k, v, g):
        return jax.grad(lambda q, k, v: jnp.sum(att.flash_attention(
            q, k, v, causal=True) * g), (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(q, k, v, g).as_text()
    assert "x192" not in text and "192x" not in text
    unequal = att._split_operands(*operands(11, 1, 2, 256, 192, 128)[1:3])
    assert (unequal.head_dim, unequal.v_width) == (192, 128)
    assert unequal.result(256, jnp.float32, v=True).shape == (2, 256, 128)
    assert unequal.result(256, jnp.float32).shape == (2, 256, 192)


# ---------------------------------------------------------------------------
# the LATENT layout (PR 47): the kernels read a latent mixer's operands where
# its projections wrote them
# ---------------------------------------------------------------------------
def latent_operands(seed, b, h, s, nope=128, rope=64, v=128, dtype=jnp.float32):
    """``(q_nope, q_r, kv, k_r, g)`` as a latent mixer's projections leave
    them (``g``: a cotangent of the context's shape)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q_nope, q_r, kv, k_r, g = (
        jax.random.normal(k, (b, s, width), jnp.float32).astype(dtype)
        for k, width in zip(
            keys, (h * nope, h * rope, h * (nope + v), rope, h * v)))
    return q_nope, q_r, kv, k_r, g


def assembled(q_nope, q_r, kv, k_r, h):
    """The same numbers as ``mha_reference``'s ``[B, H, S, .]`` q, k and v."""
    b, s, rope = k_r.shape
    nope = q_nope.shape[-1] // h

    def heads(t):
        return t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

    kv = heads(kv)
    q = jnp.concatenate([heads(q_nope), heads(q_r)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (b, h, s, rope))], -1)
    return q, k, kv[..., nope:]


@pytest.mark.parametrize("block", [1024, 128, 64])
@pytest.mark.parametrize("h", [2, 4])
def test_latent_layout_against_the_reference(h, block):
    """The context, ``lse``, ``dq_nope``, ``dq_r``, ``d kv`` (both halves) and
    ``dk_r`` of the LATENT layout at 128 + 64 / 128 against ``mha_reference``
    on the assembled operands: one block each way, and grids of 2 x 2 and
    4 x 4 (a body ON the diagonal, one UNDER it, a skipped step)."""
    s = 256
    q_nope, q_r, kv, k_r, g = latent_operands(h + block, 1, h, s)
    tiling = att.flash_tiling(
        s, s, min(block, s), min(block, s), True, lanes=2 * 192, itemsize=4)
    assert tiling["walk"] == tiling["backward"]["walk"] == "static"
    assert tiling["backward"]["backward"] == "fused"
    if block < s:
        assert tiling["steps"]["skipped"] and tiling["bodies"] == 2

    def ours(*operands):
        return att.flash_attention_latent(
            *operands, h, block_q=block, block_k=block)

    def theirs(*operands):
        q, k, v = assembled(*operands, h)
        out = att.mha_reference(q, k, v, causal=True)   # 1 / sqrt(192)
        return out.transpose(0, 2, 1, 3).reshape(1, s, h * 128)

    out, residuals = att._flash_latent_fwd(
        q_nope, q_r, kv, k_r, h, 192 ** -0.5, min(block, s), min(block, s))
    np.testing.assert_allclose(out, ours(q_nope, q_r, kv, k_r), atol=0, rtol=0)
    np.testing.assert_allclose(out, theirs(q_nope, q_r, kv, k_r), **TIGHT)
    q, k, _ = assembled(q_nope, q_r, kv, k_r, h)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 192 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    np.testing.assert_allclose(
        residuals[-1].reshape(1, h, s), jax.nn.logsumexp(scores, -1), **TIGHT)

    got = jax.grad(lambda *o: jnp.sum(ours(*o) * g), (0, 1, 2, 3))(
        q_nope, q_r, kv, k_r)
    want = jax.grad(lambda *o: jnp.sum(theirs(*o) * g), (0, 1, 2, 3))(
        q_nope, q_r, kv, k_r)
    dkv, dkv_want = (t.reshape(1, s, h, 256) for t in (got[2], want[2]))
    pairs = dict(
        dq_nope=(got[0], want[0]), dq_r=(got[1], want[1]),
        dk_nope=(dkv[..., :128], dkv_want[..., :128]),
        dv=(dkv[..., 128:], dkv_want[..., 128:]), dk_r=(got[3], want[3]))
    for name, (a, r) in pairs.items():
        assert a.shape == r.shape, name
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(
            a / scale, r / scale, atol=2e-5, err_msg=name)


def latent_mixer(seed, heads, nope=128, rope=64, v=128, s=256, b=1,
                 dtype=jnp.bfloat16):
    """``(spec, leaves, x, w)`` of a latent mixer at the published head
    widths behind toy ranks: hidden 64, q rank 48, kv rank 32."""
    tr = importlib.import_module("deepspeed_tpu.ops.transformer")
    spec = tr.AttentionSpec(
        heads=heads, kv_heads=heads, head_dim=nope + rope, eps=1e-6,
        lanes=rope, frequencies=tr.rotary_frequencies(rope, 10000.0),
        scope="attn_mla", q_rank=48, kv_rank=32, v_dim=v)
    shapes = dict(
        wqa=(64, 48), q_norm=(48,), wqb=(48, heads * (nope + rope)),
        wkva=(64, 32 + rope), kv_norm=(32,), wkvb=(32, heads * (nope + v)),
        wo=(heads * v, 64))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 2)
    p = {
        name: ((1.0 if name.endswith("norm") else 0.0) + (
            0.05 if name.endswith("norm") else shape[0] ** -0.5
        ) * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        for k, (name, shape) in zip(keys, shapes.items())}
    x, w = (jax.random.normal(k, (b, s, 64), jnp.float32).astype(dtype)
            for k in keys[-2:])
    return tr, spec, p, x, w


def test_the_latent_mixer_by_the_new_path_equals_itself_by_todays(monkeypatch):
    """``_latent_attention`` at 128 + 64 / 128 lanes, bf16, on one device:
    the LATENT layout (two products for q, ``kv`` as the product wrote it,
    ``k_r`` shared) against the same mixer by today's path (q and k built
    ``[B, H, S, 192]``, the rotated key part repeated over the heads, through
    ``attention``), both through the kernels on a 2 x 2 grid: the output and
    the gradients of all seven leaves and of the input."""
    tr, spec, p, x, w = latent_mixer(3, heads=2)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(att, "DEFAULT_BLOCK_Q", 128)
    monkeypatch.setattr(att, "DEFAULT_BLOCK_K", 128)

    def f32(t):
        return t.astype(jnp.float32)

    def run():
        text = jax.jit(lambda p, x: tr.attention_mixer(p, x, spec)).lower(
            p, x).as_text(debug_info=True)
        out, grads = jax.value_and_grad(
            lambda p, x: jnp.sum(f32(tr.attention_mixer(p, x, spec)) * f32(w)),
            (0, 1))(p, x)
        return text, out, grads

    text, out, grads = run()
    assert att.latent_layout(1, 256, 2, 128, 64, 128) == ("latent", 2, None)
    head_major = "tensor<1x2x256x192xbf16>"   # q and k as today's path builds them
    assert "flash_fwd" in text and head_major not in text
    monkeypatch.setattr(
        tr, "latent_layout", lambda *a, **k: ("split", 1, "today's path"))
    todays_text, todays_out, todays = run()
    assert "flash_fwd" in todays_text and head_major in todays_text
    np.testing.assert_allclose(out, todays_out, rtol=2e-2)
    assert sorted(grads[0]) == sorted(p) and len(p) == 7
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(todays)):
        scale = float(jnp.max(jnp.abs(f32(r))))
        np.testing.assert_allclose(f32(g) / scale, f32(r) / scale, atol=2e-2)


@pytest.mark.parametrize("case,words", [
    ("toy widths", "16 unrotated lanes on 16 of v"),
    ("v narrower", "128 unrotated lanes on 64 of v"),
    ("rotated part", "32 rotated lanes"),
    ("odd head count", "3 heads do not pair"),
    ("several devices and no mesh", "8 devices and no mesh"),
    ("a mesh of several devices", "a mesh of several devices"),
    ("short row", "under FLASH_MIN_SEQ"),
])
def test_latent_refusal_names_the_shape_and_todays_program_runs(
        case, words, monkeypatch):
    """Each refused shape gets its reason, and the mixer then lowers to the
    program it lowered to before the layout existed (the one a forced
    ``split`` gives), with no kernel of the layout's in it."""
    from deepspeed_tpu.parallel.mesh import build_mesh

    b, heads, nope, rope, v, s, mesh = 1, 2, 128, 64, 128, 256, None
    if case == "toy widths":
        nope, rope, v = 16, 8, 16
    elif case == "v narrower":
        v = 64
    elif case == "rotated part":
        rope = 32
    elif case == "odd head count":
        heads = 3
    elif case == "a mesh of several devices":
        b, mesh = 2, build_mesh(devices=jax.devices()[:2])
    elif case == "short row":
        s = 128
    if "devices" not in case:
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    why = att.latent_refusal(b, s, heads, nope, rope, v, mesh)
    assert why and words in why, why
    assert att.latent_layout(b, s, heads, nope, rope, v, mesh) == (
        "split", 1, why)
    tr, spec, p, x, _ = latent_mixer(5, heads, nope, rope, v, s, b)

    def lowered():
        return jax.jit(lambda p, x: tr.attention_mixer(
            p, x, spec, mesh=mesh)).lower(p, x).as_text()

    refused = lowered()
    monkeypatch.setattr(
        tr, "flash_attention_latent", lambda *a, **k: pytest.fail("latent"))
    monkeypatch.setattr(
        tr, "latent_layout", lambda *a, **k: ("split", 1, "today's path"))
    assert refused == lowered()


def test_the_layout_line_says_latent_or_why_not(caplog, monkeypatch):
    """``attention_layout`` (debug, once a shape at trace time): ``layout=latent
    heads_a_block=2`` where the kernels take the projections' buffers, else
    ``layout=split`` with ``latent_refusal``'s words."""
    import logging

    from deepspeed_tpu.utils.logging import logger

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    att._log_layout.cache_clear()
    logger.propagate = True
    try:
        with caplog.at_level(logging.DEBUG, logger=logger.name):
            for heads in (2, 3):
                tr, spec, p, x, _ = latent_mixer(7, heads)
                jax.eval_shape(
                    lambda p, x: tr.attention_mixer(p, x, spec), p, x)
    finally:
        logger.propagate = False
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("attention_layout")]
    assert len(lines) == 2, lines
    assert "s=256 heads=2 d=192 layout=latent heads_a_block=2" in lines[0]
    assert "s=256 heads=3 d=192 layout=split heads_a_block=1" in lines[1]
    assert "3 heads do not pair into 128-lane blocks" in lines[1]
