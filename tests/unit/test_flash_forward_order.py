"""``flash_fwd`` walks a grid step key-major (PR 50): the key sub-tile
outermost, every softmax chain (a head's query stripe) that sees it advancing
by one link side by side. Each chain still meets its key sub-tiles in the order
the query-major walk gave it, so at an unchanged sub-tile shape ``out`` and
``lse`` are the old order's BIT FOR BIT: every mask form, every operand layout,
``diag_offset != 0`` and dropout. And the report: ``flash_tiling``'s ``order``
and ``chains`` at the cells' shapes."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

att = importlib.import_module("deepspeed_tpu.ops.attention")

# (entry, sq, sk, block, the call's keywords); every case runs at sub-tiles of
# 128 under blocks of 256, so a grid step has two stripes a head
ORDER_CASES = {
    "causal_2x2": ("split", 512, 512, dict(causal=True)),
    "causal_4x4": ("split", 1024, 1024, dict(causal=True)),
    "noncausal_2x2": ("split", 512, 512, {}),
    "band": ("split", 1024, 1024, dict(causal=True, window=200)),
    "band_narrower_than_a_subtile": (
        "split", 1024, 1024, dict(causal=True, window=72)),
    "block_diffusion": ("split", 1024, 1024, dict(block_diffusion=4)),
    # the diagonal starts in the second key block: the first steps of a row
    # are wholly allowed, and with sq > sk the first rows see no key at all
    "diag_offset_positive": ("split", 256, 512, dict(causal=True)),
    "diag_offset_negative": ("split", 512, 256, dict(causal=True)),
    "key_mask": ("split", 512, 512, dict(causal=True, masked=True)),
    # two 64-lane heads a block, the projection's bias added in the kernel
    "packed_d64_key_mask_bias": (
        "packed", 512, 512, dict(masked=True, biased=True, d=64)),
    "packed_d64_causal_bias": (
        "packed", 512, 512, dict(causal=True, biased=True, d=64)),
    "packed_d128_causal": ("packed", 512, 512, dict(causal=True, d=128)),
    # two heads a program, the score as two products
    "latent": ("latent", 512, 512, {}),
    # a stand-in for the chip's PRNG (the CPU has none): bits by global head
    # and granule, as ``_keep_mask`` draws them
    "dropout": ("split", 512, 512, dict(causal=True, dropout=0.3)),
    "dropout_packed_d64": (
        "packed", 512, 512, dict(causal=True, dropout=0.3, d=64)),
}
BLOCK, SUB = 256, 128


def _keep_by_head_and_granule(seed_ref, bh, q_first, k_first, shape, gran, rate):
    """``_keep_mask`` without the chip's PRNG: every ``gran`` granule's bits
    from (seed, batch*head, q granule, k granule) alone."""
    gran_k, gran_q = gran
    rows = []
    for a in range(shape[0] // gran_k):
        row = []
        for b in range(shape[1] // gran_q):
            key = jax.random.PRNGKey(0)
            for part in (seed_ref[0], bh, q_first // gran_q + b,
                         k_first // gran_k + a):
                key = jax.random.fold_in(key, part)
            row.append(jax.random.uniform(key, (gran_k, gran_q)) >= rate)
        rows.append(jnp.concatenate(row, axis=1))
    return jnp.concatenate(rows, axis=0)


def _forward(case, dtype):
    """``() -> (out, lse)`` of the case's forward call."""
    entry, sq, sk, kw = ORDER_CASES[case]
    kw = dict(kw)
    rng = np.random.default_rng(50)
    b, d = 2, kw.pop("d", 64)
    causal, dropout = kw.pop("causal", False), kw.pop("dropout", 0.0)
    kv_mask = bias = None
    if kw.pop("masked", False):
        valid = np.ones((b, sk), np.int32)
        valid[0, :130] = 0          # row 0: a whole leading sub-tile and more
        valid[1, 300:] = 0
        kv_mask = jnp.asarray(valid)
    seed = jnp.asarray(11, jnp.int32)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    if entry == "latent":
        heads, nope, rope = 2, 128, 64
        q_nope, q_r = normal(b, sq, heads * nope), normal(b, sq, heads * rope)
        kv, k_r = normal(b, sk, heads * 2 * nope), normal(b, sk, rope)
        return lambda: att._flash_latent_fwd(
            q_nope, q_r, kv, k_r, heads, (nope + rope) ** -0.5, BLOCK, BLOCK
        )[1][-2:]
    if entry == "packed":
        heads = 2
        qkv = normal(b, sq, 3 * heads * d)
        if kw.pop("biased", False):
            bias = normal(3 * heads * d)
        return lambda: att._flash_packed_fwd(
            qkv, bias, kv_mask, seed, heads, causal, d ** -0.5, dropout, BLOCK,
            BLOCK)[1][-2:]
    q, k, v = normal(b, 2, sq, d), normal(b, 2, sk, d), normal(b, 2, sk, d)
    blocks = att._pick_blocks(sq, sk, BLOCK, BLOCK, kw.get("block_diffusion", 0))
    return lambda: att._flash_fwd(
        q, k, v, kv_mask, seed, causal, d ** -0.5, dropout, *blocks,
        kw.get("block_diffusion", 0), kw.get("window", 0))[1][-2:]


@pytest.mark.parametrize("dtype", ["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_key_major_forward_is_the_query_major_one_bit_for_bit(
        case, dtype, monkeypatch):
    real = att.pick_subtiles
    monkeypatch.setattr(
        att, "pick_subtiles",
        lambda bq, bk, nq, nk, key_major, *more, **kw: (
            real(bq, bk, nq, nk, key_major, *more, **kw) if key_major
            else (min(bq, SUB), min(bk, SUB))))
    monkeypatch.setattr(att, "_keep_mask", _keep_by_head_and_granule)
    forward = _forward(case, jnp.bfloat16 if dtype == "bf16" else jnp.float32)

    orders = []
    real_order = att._forward_order

    def seen(*a):
        orders.append(real_order(*a))
        return orders[-1]

    monkeypatch.setattr(att, "_forward_order", seen)
    out, lse = forward()
    entry, _, _, kw = ORDER_CASES[case]
    heads_a_block = {"split": 1, "latent": 2}.get(entry, 128 // kw.get("d", 64))
    assert orders and all(
        o == {"order": "key_major", "chains": 2 * heads_a_block} for o in orders)

    # the old order: one chain after another
    monkeypatch.setattr(
        att, "_forward_order",
        lambda *a: {"order": "query_major", "chains": 1})
    old_out, old_lse = forward()
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.asarray(out, np.float32).any()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(old_out))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(old_lse))


def test_spans_seen_from_the_keys_keep_each_stripes_order():
    """``_Tiles.by_key`` is the transposed view of a class's spans: every
    (stripe, key sub-tile, crossed or not) of them once, the key sub-tiles
    ascending, which is the order each stripe's own walk has."""
    for form in (dict(causal=True), dict(causal=True, window=300),
                 dict(causal=False, block_diffusion=4), dict(causal=False)):
        causal = form.pop("causal")
        bodies, _, _ = att._walk_classes(
            False, 128, 128, 512, 512, 4, 4, causal, 0,
            form.get("block_diffusion", 0), form.get("window", 0))
        assert bodies
        for spans in bodies:
            walk = att._Tiles.by_key(spans)
            assert [c for c, _ in walk] == sorted({c for c, _ in walk})
            for r, runs in enumerate(spans):
                own = [(c, diagonal) for lo, hi, diagonal in runs
                       for c in range(lo, hi)]
                assert own == [(c, diagonal) for c, links in walk
                               for rr, diagonal in links if rr == r]


# the forward's entry of ``flash_tiling`` at the cells' shapes: (sq, block,
# causal, the mask form, heads a block) -> (grid steps run / all, bodies,
# order, chains)
CELL_REPORTS = {
    "sdar_16x16_block_diffusion": (
        16384, 1024, False, dict(block_diffusion=4), 1, (80, 256), 3),
    "joyai_latent_two_head_programs": (8192, 1024, True, {}, 2, (36, 64), 2),
    "gpt2_one_block_two_heads": (1024, 1024, True, {}, 2, (1, 1), 1),
    "ouro_8x8": (8192, 1024, True, {}, 1, (36, 64), 2),
    "qwen3next_16x16": (16384, 1024, True, {}, 1, (136, 256), 2),
    "laguna_band": (8192, 1024, True, dict(window=512), 1, (15, 16), 2),
    "bert_512_two_heads": (512, 512, False, {}, 2, (1, 1), 1),
    "bert_384_two_heads": (384, 384, False, {}, 2, (1, 1), 1),
}


@pytest.mark.parametrize("cell", sorted(CELL_REPORTS))
def test_flash_tiling_reports_the_forwards_order_and_chains(cell):
    s, block, causal, form, heads, (run, steps), bodies = CELL_REPORTS[cell]
    t = att.flash_tiling(
        s, s, block, block, causal, heads_a_block=heads, **form)
    assert (t["walk"], t["bodies"]) == ("static", bodies)
    assert t["steps"]["run"] == run
    assert t["steps"]["run"] + t["steps"]["skipped"] == steps
    stripes = block // t["sub_q"]
    assert t["chains"] == stripes * heads
    assert t["order"] == ("key_major" if stripes * heads > 1 else "query_major")
    # the key-major kernels' report has no forward's order
    assert "order" not in t["backward"] and "chains" not in t["backward"]
    assert "order" not in att.flash_tiling(
        s, s, block, block, causal, key_major=True, **form)


def test_one_stripe_of_one_head_and_the_loop_walk_keep_the_old_order(monkeypatch):
    # a block of one head no longer than a sub-tile: nothing to put side by side
    t = att.flash_tiling(256, 256, 256, 256, True)
    assert (t["sub_q"], t["order"], t["chains"]) == (256, "query_major", 1)
    t = att.flash_tiling(2048, 2048, 512, 512, True, sub_q=512, sub_k=512)
    assert (t["walk"], t["order"], t["chains"]) == ("static", "query_major", 1)
    # more classes of step than bodies: a ``fori_loop`` a stripe, one chain
    monkeypatch.setattr(att, "MAX_WALK_BODIES", 0)
    t = att.flash_tiling(2048, 2048, 1024, 1024, True, heads_a_block=2)
    assert (t["walk"], t["order"], t["chains"]) == ("loop", "query_major", 1)
