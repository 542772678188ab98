"""The q/k norm-and-rotary kernels (ops/qk_prep.py: ``qk_prep_fwd``,
``qk_prep_bwd``; interpret mode on the CPU) against ``apply_rotary(
rms_norm(..))`` and against ``jax.grad`` of it, for the three mixers'
parameter sets; the packed in-place form; ``qk_prep_path``'s choices and its
log line; the one mixer under each rotating kind's spec through the kernels
against the same through the XLA functions."""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.hybrid import HybridLMConfig, attention_spec
from deepspeed_tpu.ops import qk_prep as qp
from deepspeed_tpu.ops import transformer as T
from deepspeed_tpu.utils.logging import logger
from tests.unit import test_laguna_layers as laguna

# (heads, head width, rotated lanes, zero-centred gains, gains at all,
# position ids repeat): SDAR's q and k (plain gains, every lane, the two
# halves of a row share ids), Qwen3-Next's (zero-centred, 64 of 256 lanes), a
# rotation alone, and 192 of 256 lanes (the rotated lanes end inside the
# second 128-lane block).
SETS = {
    "sdar": (4, 128, 128, False, True, True),
    "qwen": (2, 256, 64, True, True, False),
    "no_norm": (4, 128, 128, False, False, False),
    "lanes_192_of_256": (2, 256, 192, False, True, False),
}
B, S, THETA, EPS = 2, 64, 1e4, 1e-6


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """The suite runs on eight virtual devices, where ``qk_prep_path`` says
    ``xla``; the mixers are tested as the one-chip cells run them."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def operands(name, dtype, seed=0):
    heads, d, lanes, zero_centered, normed, repeat = SETS[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (B, S, heads * d)).astype(dtype)
    gain = None
    if normed:
        gain = (1.0 - zero_centered + 0.2 * jax.random.normal(ks[1], (d,))
                ).astype(dtype)
    positions = jnp.concatenate([jnp.arange(S // 2)] * 2) if repeat else None
    probe = jax.random.normal(ks[2], (B, heads, S, d))
    return x, gain, positions, probe


def through_xla(name, x, gain, positions):
    heads, d, lanes, zero_centered, _, _ = SETS[name]
    t = x.reshape(B, S, heads, d)
    if gain is not None:
        t = T.rms_norm(t, gain, EPS, zero_centered)
    return T.apply_rotary(
        t, lanes, THETA, seq_axis=1, positions=positions).transpose(0, 2, 1, 3)


def through_kernels(name, x, gain, positions):
    _, d, lanes, zero_centered, _, _ = SETS[name]
    return qp.qk_prep(
        x, gain, T.rotary_angles(S, lanes, THETA, positions), head_dim=d,
        eps=EPS, zero_centered=zero_centered)


def bf16_steps(a, b):
    """Largest |a - b| in steps of bfloat16 at the largest magnitude of the
    row of D lanes it lies in."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = np.maximum(np.abs(b).max(axis=-1, keepdims=True), 1e-30)
    return float((np.abs(a - b) / np.exp2(np.floor(np.log2(scale)) - 7)).max())


@pytest.mark.parametrize("name", sorted(SETS))
def test_float32_matches_the_xla_functions_and_their_gradient(name):
    x, gain, positions, probe = operands(name, jnp.float32)
    want = through_xla(name, x, gain, positions)
    got = through_kernels(name, x, gain, positions)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    wrt = (0, 1) if gain is not None else (0,)
    g_want = jax.grad(lambda *a: jnp.sum(
        through_xla(name, *a, positions) * probe), wrt)(x, gain)
    g_got = jax.grad(lambda *a: jnp.sum(
        through_kernels(name, *a, positions) * probe), wrt)(x, gain)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("name", sorted(SETS))
def test_bfloat16_rounds_once_and_stays_within_a_step_or_two(name):
    """The kernels round to bf16 once, the XLA functions after the norm and
    after the rotation: the kernels' result is the closer of the two to the
    float32 one, and the two differ by bf16 steps, not by more."""
    x, gain, positions, probe = operands(name, jnp.bfloat16)
    want = through_xla(name, x, gain, positions)
    got = through_kernels(name, x, gain, positions)
    assert got.dtype == jnp.bfloat16
    exact = through_xla(
        name, x.astype(jnp.float32),
        None if gain is None else gain.astype(jnp.float32), positions)
    assert bf16_steps(got, want) <= 2
    assert bf16_steps(got, exact) <= 0.5 + 1e-3
    assert bf16_steps(got, exact) <= bf16_steps(want, exact)
    probe = probe.astype(jnp.bfloat16)
    wrt = (0, 1) if gain is not None else (0,)
    g_want = jax.grad(lambda *a: jnp.sum(
        (through_xla(name, *a, positions) * probe).astype(jnp.float32)),
        wrt)(x, gain)
    g_got = jax.grad(lambda *a: jnp.sum(
        (through_kernels(name, *a, positions) * probe).astype(jnp.float32)),
        wrt)(x, gain)
    for a, b in zip(g_got, g_want):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
    assert bf16_steps(g_got[0], g_want[0]) <= 3
    if gain is not None:
        assert bf16_steps(g_got[1][None], g_want[1][None]) <= 3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_packed_product_is_rotated_in_place_and_v_is_bit_equal(dtype):
    """Ouro's form: q | k of a packed ``[B, S, 3 H D]`` product rotated, v's
    lanes as they were, values and gradient."""
    heads, d = 2, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (B, S, 3 * heads * d)).astype(dtype)
    probe = jax.random.normal(ks[1], x.shape).astype(dtype)

    def through_xla(x):
        t = x.reshape(B, S, 3 * heads, d)
        qk = T.apply_rotary(t[:, :, :2 * heads], d, THETA, seq_axis=1)
        return jnp.concatenate([qk, t[:, :, 2 * heads:]], axis=2).reshape(
            x.shape)

    def through_kernels(x):
        return qp.qk_prep_in_place(
            x, T.rotary_angles(S, d, THETA), heads=2 * heads, head_dim=d)

    def gradient(f):
        return jax.grad(lambda x: jnp.sum(
            (f(x) * probe).astype(jnp.float32)))(x)

    got, want = jax.jit(through_kernels)(x), through_xla(x)
    v = 2 * heads * d
    assert jnp.array_equal(got[..., v:], x[..., v:])
    g_got, g_want = gradient(through_kernels), gradient(through_xla)
    assert jnp.array_equal(g_got[..., v:], g_want[..., v:])
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g_got, g_want, rtol=1e-5, atol=1e-6)
    else:
        assert bf16_steps(got, want) <= 1 and bf16_steps(g_got, g_want) <= 1


def debug_lines(caplog, calls):
    logger.propagate = True
    try:
        with caplog.at_level(logging.DEBUG, logger=logger.name):
            results = [call() for call in calls]
    finally:
        logger.propagate = False
    return results, [r.getMessage() for r in caplog.records
                     if r.getMessage().startswith("qk_prep ")]


def test_the_path_is_chosen_from_shapes_and_mesh_and_logged_once(caplog):
    """``qk_prep_path`` is the function that decides and its debug line is
    the kernels' engagement counter: ``fused`` at the three cells' shapes,
    ``xla`` with its reason at a toy width, for an odd number of rotated
    lanes, on several devices without a mesh, and under a mesh whose model
    axis shards the projection's lanes."""
    from jax.sharding import Mesh

    from deepspeed_tpu.config.constants import DATA_AXIS, MODEL_AXIS

    qp._log_path.cache_clear()
    sdar = (2, 16384, 32, 128, 128)
    results, lines = debug_lines(caplog, [
        lambda: qp.qk_prep_path(*sdar), lambda: qp.qk_prep_path(*sdar),
        lambda: qp.qk_prep_path(1, 8192, 16, 128, 128),
        lambda: qp.qk_prep_path(2, 16384, 16, 256, 64),
        lambda: qp.qk_prep_path(2, 64, 8, 16, 4),
        lambda: qp.qk_prep_path(2, 64, 8, 128, 63)])
    assert [r[0] for r in results] == ["fused"] * 4 + ["xla"] * 2
    assert len(lines) == 5, lines
    assert lines[0] == \
        "qk_prep b=2 s=16384 heads=32 d=128 rotary_lanes=128 path=fused"
    assert lines[3] == ("qk_prep b=2 s=64 heads=8 d=16 rotary_lanes=4 "
                        "path=xla reason='head_dim 16 does not fill 128-lane "
                        "blocks'")
    assert "path=xla reason='63 rotary lanes of 128'" in lines[4]
    devices = np.array(jax.devices()[:4])
    meshes = {
        "lanes": Mesh(devices.reshape(2, 2), (DATA_AXIS, MODEL_AXIS)),
        "rows": Mesh(devices.reshape(4, 1), (DATA_AXIS, MODEL_AXIS)),
        "one": Mesh(devices[:1].reshape(1, 1), (DATA_AXIS, MODEL_AXIS)),
    }
    jax.device_count = lambda: 4        # the fixture puts it back
    results, lines = debug_lines(caplog, [
        lambda: qp.qk_prep_path(8, *sdar[1:]),
        lambda: qp.qk_prep_path(8, *sdar[1:], mesh=meshes["lanes"]),
        lambda: qp.qk_prep_path(8, *sdar[1:], mesh=meshes["rows"]),
        lambda: qp.qk_prep_path(8, *sdar[1:], mesh=meshes["one"])])
    assert [r[0] for r in results] == ["xla"] * 3 + ["fused"]
    assert len(lines) == 9 and "reason='4 devices and no mesh" in lines[5]
    assert results[1][1] == "the model axis shards the projection's lanes"
    assert results[2][1] == "a kernel is not partitioned over devices"


# HybridLMConfig's fields for each attention kind at a head width the
# kernels take; H and W are tests/unit/test_laguna_layers.py's at width 128
FIELDS = {
    "A": dict(attn_heads=4, kv_heads=2, head_dim=128,
              objective="block_diffusion", diffusion_block=4),
    "G": dict(attn_heads=2, kv_heads=1, head_dim=256, rotary_lanes=64),
    "R": dict(attn_heads=2, kv_heads=2, head_dim=128),
}
LAGUNA = dict(laguna.CFG, head_dim=128, full_rotary_lanes=64,
              yarn_original_positions=64, sliding_window=24)


def _mixer(kind, dtype):
    """(mixer over (p, x), leaves, x, the plain reference or None) at a width
    the kernels take, the spec out of ``attention_spec``'s table."""
    rng = np.random.default_rng(5)
    e, s = 64, 64

    def leaf(*shape, scale=0.3):
        return jnp.asarray(scale * rng.normal(size=shape), dtype)

    if kind in "HW":
        name = {"H": "full", "W": "win"}[kind]
        return (
            lambda p, x: laguna.our_attn(p, x, name, LAGUNA),
            laguna.attn_leaves(rng, name, LAGUNA), laguna.normal(rng, 2, s, 48),
            lambda p, x: laguna.ref.attn(p, x, LAGUNA, laguna.DOT, name))
    spec = attention_spec(HybridLMConfig(
        pattern=kind, hidden_size=e, rope_theta=THETA, norm_eps=EPS,
        **FIELDS[kind]), kind)
    heads, kv, d = spec.heads, spec.kv_heads, spec.head_dim
    positions = None
    if kind == "A":
        p = {"wq": leaf(e, heads * d), "q_norm": 1 + leaf(d, scale=0.2),
             "k_norm": 1 + leaf(d, scale=0.2)}
        positions = jnp.concatenate([jnp.arange(s // 2)] * 2)
    elif kind == "G":
        p = {"wq": leaf(e, heads * 2 * d), "q_norm": leaf(d, scale=0.2),
             "k_norm": leaf(d, scale=0.2)}
    else:
        p = {"wq": leaf(e, heads * d)}
    p.update(wk=leaf(e, kv * d), wv=leaf(e, kv * d), wo=leaf(heads * d, e))
    return (lambda p, x: T.attention_mixer(p, x, spec, positions=positions),
            p, leaf(2, s, e, scale=1.0), None)


@pytest.mark.parametrize("kind", ["A", "G", "R", "H", "W"])
def test_mixers_through_the_kernels_match_the_mixers_through_xla(
        kind, monkeypatch):
    """The one mixer under each rotating kind's spec at a head width the
    kernels take (float32: the two paths then differ by summation order
    only), output and the gradient of every leaf and of the input; H and W
    (no norm, rotary on 64 of 128 lanes with the factor, or on all 128 under
    the window) against the plain reference too. The kernels lower under
    ``attn_mixer`` by name, and under the kind's own scope inside it where
    it opens one."""
    mixer, p, x, reference = _mixer(kind, jnp.float32)
    probe = jnp.asarray(
        np.random.default_rng(6).normal(size=x.shape), jnp.float32)

    def run():
        out = mixer(p, x)
        grads = jax.grad(
            lambda p, x: jnp.sum(mixer(p, x) * probe), (0, 1))(p, x)
        return out, grads

    assert qp.qk_prep_path(2, 64, 2, 128, 128)[0] == "fused"
    assert qp.qk_prep_path(2, 64, 4, 128, 64)[0] == "fused"
    # interpret mode lowers a kernel to operations that carry its name where
    # the chip has one custom call
    compiled = jax.jit(jax.grad(
        lambda p, x: jnp.sum(mixer(p, x)))).lower(p, x).compile().as_text()
    scope = {"H": "attn_full/", "W": "attn_window/"}.get(kind, "")
    assert re.search(rf"attn_mixer\)*/{scope}qk_prep_fwd/", compiled)
    assert re.search(rf"attn_mixer\)*/{scope}qk_prep_bwd/", compiled)
    for other in {"attn_full/", "attn_window/"} - {scope}:
        assert "/" + other not in compiled
    fused = run()
    monkeypatch.setattr(
        T, "qk_prep_path", lambda *a, **k: ("xla", "held by the test"))
    xla = run()
    assert "qk_prep" not in jax.jit(mixer).lower(p, x).as_text()
    if reference:
        np.testing.assert_allclose(
            xla[0], reference(p, x), atol=2e-5, rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(fused),
                    jax.tree_util.tree_leaves(xla)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(b))))
