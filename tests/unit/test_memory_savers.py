"""Memory-saver features that put GPT-2 1.5B on one chip: blocked LM-head
cross-entropy (ops/cross_entropy.py) and reduced-precision optimizer-moment
storage (ops/quant.py via Adam/Lamb state_dtype)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.bert import cross_entropy_ignore_index
from deepspeed_tpu.ops.cross_entropy import blocked_lm_head_loss
from deepspeed_tpu.ops.optimizers import Adam, Lamb
from deepspeed_tpu.ops import quant

pytestmark = pytest.mark.slow  # compile-heavy; excluded from `make test-fast`


# ------------------------------------------------------------ blocked CE
@pytest.mark.parametrize("block_rows", [32, 100, 256])
def test_blocked_ce_matches_naive_forward(block_rows):
    rng = np.random.default_rng(0)
    B, S, H, V = 2, 33, 16, 257
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(V, H)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    labels = labels.at[0, :5].set(-1)  # ignore some positions
    naive = cross_entropy_ignore_index(x @ W.T, labels)
    blocked = blocked_lm_head_loss(x, W, labels, block_rows=block_rows)
    np.testing.assert_allclose(
        np.asarray(blocked), np.asarray(naive), rtol=1e-5, atol=1e-5
    )


def test_blocked_ce_matches_naive_gradients():
    rng = np.random.default_rng(1)
    B, S, H, V = 2, 17, 16, 130
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(V, H)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)

    def loss_naive(x, W):
        return cross_entropy_ignore_index(x @ W.T, labels)

    def loss_blocked(x, W):
        return blocked_lm_head_loss(x, W, labels, block_rows=64)

    gx1, gW1 = jax.grad(loss_naive, argnums=(0, 1))(x, W)
    gx2, gW2 = jax.grad(loss_blocked, argnums=(0, 1))(x, W)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gW1), np.asarray(gW2), rtol=2e-5, atol=2e-5)


def test_blocked_ce_empty_ignore_values_counts_all_labels():
    """ignore_values=() with a non-dividing T: pad positions are masked by
    index, so label-0 padding is never counted and the empty tuple doesn't
    crash (round-3 advisor finding)."""
    rng = np.random.default_rng(4)
    B, S, H, V = 2, 13, 16, 64  # 13 % block_rows(8) != 0 -> padded
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(V, H)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    labels = labels.at[:, :3].set(0)  # real label-0 targets must count
    naive = cross_entropy_ignore_index(x @ W.T, labels, ignore_values=())
    blocked = blocked_lm_head_loss(
        x, W, labels, block_rows=8, ignore_values=()
    )
    np.testing.assert_allclose(
        np.asarray(blocked), np.asarray(naive), rtol=1e-5, atol=1e-5
    )


def test_blocked_ce_all_ignored_is_zero():
    x = jnp.zeros((1, 4, 8), jnp.float32)
    W = jnp.zeros((32, 8), jnp.float32)
    labels = jnp.full((1, 4), -1, jnp.int32)
    out = blocked_lm_head_loss(x, W, labels, block_rows=4)
    assert float(out) == 0.0


# ------------------------------------------------------ quantized moments
def test_quant_roundtrip_accuracy():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 3000)) * 0.01, jnp.float32)
    q = quant.quantize(x)
    assert q["scale"].shape == (2, 3)  # two runs of 1,500 a row, rows minor
    back = quant.dequantize(q)
    # absmax int8 per run: worst-case error is absmax/254 of the run
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    assert err <= float(jnp.max(jnp.abs(x))) / 127.0


def test_quant_zero_block_decodes_zero():
    x = jnp.zeros((2, 4096), jnp.float32)
    q = quant.quantize(x)
    assert np.asarray(quant.dequantize(q)).max() == 0.0


def _quad_problem():
    rng = np.random.default_rng(3)
    target = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    params = {"w": jnp.zeros((64, 32), jnp.float32),
              "b": jnp.zeros((64,), jnp.float32)}

    def loss(p):
        return jnp.mean((p["w"] - target) ** 2) + jnp.mean(p["b"] ** 2)

    return params, loss


@pytest.mark.parametrize("state_dtype", ["bf16", "int8"])
def test_adam_reduced_state_converges(state_dtype):
    params, loss = _quad_problem()
    ref_opt, red_opt = Adam(), Adam(state_dtype=state_dtype)
    ref_state, red_state = ref_opt.init(params), red_opt.init(params)
    ref_p, red_p = params, params
    lr = jnp.float32(0.05)
    for _ in range(60):
        g_ref = jax.grad(loss)(ref_p)
        ref_p, ref_state, _ = ref_opt.apply(ref_p, g_ref, ref_state, lr)
        g_red = jax.grad(loss)(red_p)
        red_p, red_state, _ = red_opt.apply(red_p, g_red, red_state, lr)
    assert float(loss(red_p)) < 0.05 * float(loss(params))
    # trajectories stay close to fp32-state Adam (int8 mu wobbles a bit
    # more than bf16; both must track, not diverge)
    np.testing.assert_allclose(
        np.asarray(red_p["w"]), np.asarray(ref_p["w"]), atol=0.2
    )


def test_adam_state_dtype_memory_layout():
    params = {"w": jnp.zeros((4096, 8), jnp.float32)}
    s8 = Adam(state_dtype="int8").init(params)
    params = {"w": jnp.zeros((8, 4096), jnp.float32)}
    s8 = Adam(state_dtype="int8").init(params)
    assert s8["mu"]["w"]["q"].dtype == jnp.int8
    assert s8["mu"]["w"]["q"].shape == (8, 4096)  # the parameter's own
    assert s8["mu"]["w"]["scale"].shape == (4096 // quant.BLOCK, 8)
    assert s8["nu"]["w"].dtype == jnp.bfloat16
    sb = Adam(state_dtype="bf16").init(params)
    assert sb["nu"]["w"].dtype == jnp.bfloat16


def test_lamb_reduced_state_converges():
    params, loss = _quad_problem()
    opt = Lamb(state_dtype="bf16")
    state = opt.init(params)
    p = params
    for _ in range(90):
        p, state, aux = opt.apply(p, jax.grad(loss)(p), state, jnp.float32(0.05))
    assert float(loss(p)) < 0.1 * float(loss(params))
    assert aux["lamb_coeffs"]


# The chunked-loop and flat-domain updates these lines used to hold tests
# for went in PR 27; their 17 cases (state format x compensated x layout,
# the closed gate) are tests/unit/test_adam_update.py's, against the one-pass
# kernel that replaced both.


# ------------------------------------------------- compensated masters
def test_master_compensation_roundtrip_bound():
    rng = np.random.default_rng(1)
    m = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    p, code = quant.encode_master(m, jnp.bfloat16)
    assert p.dtype == jnp.bfloat16 and code.dtype == jnp.int8
    back = np.asarray(quant.decode_master(p, code))
    err = np.abs(back - np.asarray(m))
    ulp = np.abs(np.asarray(m)) * 2**-8
    # residual error after compensation <= ulp/254 (one code step / 2)
    assert (err / np.maximum(ulp, 1e-30)).max() < 1.0 / 200


def test_compensated_adam_tracks_fp32_master_trajectory():
    """bf16 params + int8 Kahan codes must reproduce the fp32-master
    update (same bf16 forward) — the property that lets GPT-2 1.5B drop
    the fp32 param bytes without giving up master precision."""
    rng = np.random.default_rng(0)
    target = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)

    def loss(p):
        return jnp.mean((p["w"].astype(jnp.float32) - target) ** 2)

    master = {"w": jnp.zeros((256, 64), jnp.float32)}
    o32 = Adam()
    s32 = o32.init(master)
    pbf = {"w": jnp.zeros((256, 64), jnp.bfloat16)}
    oc = Adam(master_compensation=True)
    sc = oc.init(pbf)
    assert sc["comp"]["w"].dtype == jnp.int8
    lr = jnp.float32(1e-3)  # updates below one bf16 ulp exercise the carry
    for _ in range(300):
        gm = jax.grad(loss)({"w": master["w"].astype(jnp.bfloat16)})
        master, s32, _ = o32.apply(master, gm, s32, lr)
        gb = jax.grad(loss)(pbf)
        pbf, sc, _ = oc.apply(pbf, gb, sc, lr)
    lm, lc = float(loss(master)), float(loss(pbf))
    assert abs(lc - lm) / max(lm, 1e-9) < 0.01, (lm, lc)
    # plain bf16 (no compensation) must be measurably worse
    ppl = {"w": jnp.zeros((256, 64), jnp.bfloat16)}
    opl = Adam()
    spl = opl.init(ppl)
    for _ in range(300):
        ppl, spl, _ = opl.apply(ppl, jax.grad(loss)(ppl), spl, lr)
    assert abs(float(loss(ppl)) - lm) > 10 * abs(lc - lm)


def test_compensation_survives_jit():
    """Regression: computing the rounding residue via an astype roundtrip
    is FOLDED AWAY by XLA's excess-precision simplification under jit —
    codes silently stay zero and compensation becomes a no-op exactly in
    production (compiled) steps. encode_master must round via
    lax.reduce_precision instead; jit and eager must agree."""
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    p_e, c_e = quant.encode_master(m, jnp.bfloat16)
    p_j, c_j = jax.jit(lambda x: quant.encode_master(x, jnp.bfloat16))(m)
    assert int(np.count_nonzero(np.asarray(c_j))) > 3000
    np.testing.assert_array_equal(np.asarray(c_e), np.asarray(c_j))
    np.testing.assert_array_equal(
        np.asarray(p_e, np.float32), np.asarray(p_j, np.float32)
    )
    back = jax.jit(quant.decode_master)(p_j, c_j)
    err = np.abs(np.asarray(back) - np.asarray(m))
    ulp = np.abs(np.asarray(m)) * 2**-8
    assert (err / np.maximum(ulp, 1e-30)).max() < 1.0 / 200


def test_compensated_engine_codes_become_nonzero():
    """End-to-end through the engine's COMPILED update: after a few steps
    the int8 Kahan codes must be populated (zero codes = the jit elision
    regression)."""
    import flax.linen as nn

    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, y, train=True):
            h = nn.relu(nn.Dense(32)(x))
            logp = jax.nn.log_softmax(nn.Dense(4)(h))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 8)).astype(np.float32)
    Y = (X[:, 0] > 0).astype(np.int32)
    model = M()
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.asarray(X), jnp.asarray(Y)
    )["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(data_parallel_size=8),
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "data_types": {"master_dtype": "compensated"},
            "steps_per_print": 10_000,
        },
    )
    for _ in range(6):
        loss = engine(X, Y)
        engine.backward(loss)
        engine.step()
    nonzero = sum(
        int(np.count_nonzero(np.asarray(l)))
        for l in jax.tree_util.tree_leaves(engine.optimizer_state["comp"])
    )
    total = sum(
        l.size for l in jax.tree_util.tree_leaves(engine.optimizer_state["comp"])
    )
    assert nonzero > 0.3 * total, (nonzero, total)


def test_compensated_engine_end_to_end(tmp_path):
    import flax.linen as nn

    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, y, train=True):
            h = nn.relu(nn.Dense(32)(x))
            logp = jax.nn.log_softmax(nn.Dense(4)(h))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 8)).astype(np.float32)
    Y = (X[:, 0] > 0).astype(np.int32) + 2 * (X[:, 1] > 0).astype(np.int32)
    model = M()
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.asarray(X), jnp.asarray(Y)
    )["params"]

    def engine(seed=0):
        e, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            mesh=build_mesh(data_parallel_size=8),
            config_params={
                "train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2},
                "data_types": {"master_dtype": "compensated",
                               "optimizer_state_dtype": "int8"},
                "steps_per_print": 10_000,
            },
            rng_seed=seed,
        )
        return e

    e = engine()
    assert e.compensated_master and not e.master_in_opt
    for leaf in jax.tree_util.tree_leaves(e.params):
        assert leaf.dtype == e.compute_dtype  # no fp32 storage
    assert "comp" in e.optimizer_state

    losses = []
    for _ in range(12):
        loss = e(X, Y)
        e.backward(loss)
        e.step()
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses

    # exact same-mode checkpoint resume (comp codes ride the opt state)
    e.save_checkpoint(str(tmp_path), tag="t")
    cont = []
    for _ in range(6):
        loss = e(X, Y)
        e.backward(loss)
        e.step()
        cont.append(float(loss))
    fresh = engine(seed=7)
    fresh.load_checkpoint(str(tmp_path), tag="t")
    resumed = []
    for _ in range(6):
        loss = fresh(X, Y)
        fresh.backward(loss)
        fresh.step()
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, cont, rtol=1e-5)


# ------------------------------------------------------- engine plumbing
def _wide_problem():
    """A classifier whose first matrix is [1024, 256]: its int8 moment is
    quantized (runs of 256) and its rows split eight ways leave 128 to a
    shard, so it takes the one-pass kernel on one device and under ZeRO;
    the [256, 4] head keeps a bf16 moment."""
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, y, train=True):
            h = nn.relu(nn.Dense(256)(x))
            logp = jax.nn.log_softmax(nn.Dense(4)(h))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 1024)).astype(np.float32)
    Y = (X[:, 0] > 0).astype(np.int32)
    model = M()
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.asarray(X), jnp.asarray(Y)
    )["params"]
    return model, params, X, Y


def test_engine_optimizer_state_dtype_config():
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    model, params, X, Y = _wide_problem()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(data_parallel_size=8),
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "data_types": {"optimizer_state_dtype": "int8"},
            "steps_per_print": 10_000,
        },
    )
    mu = engine.optimizer_state["mu"]
    leaves = jax.tree_util.tree_leaves(mu)
    assert any(l.dtype == jnp.int8 for l in leaves)
    # one training window works end to end
    loss0 = engine(X, Y)
    engine.backward(loss0)
    engine.step()
    loss1 = engine(X, Y)
    engine.backward(loss1)
    engine.step()
    assert float(loss1) <= float(loss0)


def _quantized_leaves(engine):
    from deepspeed_tpu.ops.quant import is_quantized

    inner = (
        engine.optimizer_state["inner"]
        if engine.master_in_opt else engine.optimizer_state
    )
    return [
        leaf for leaf in jax.tree_util.tree_leaves(
            inner["mu"], is_leaf=is_quantized
        ) if is_quantized(leaf)
    ]


def test_engine_int8_moments_shard_under_zero():
    """int8 moment storage and ZeRO sharding COMPOSE (round-3 verdict #4):
    under stage>=1 with dp>1 the quantized {'q','scale'} leaves keep int8
    storage in the PARAMETER'S shape AND shard over the data axis the way
    the second moment does, the scales along their rows — per-chip moment
    bytes ~ total/dp on top of the 4x dtype saving. Training through the
    sharded quantized state (the kernel per shard, under shard_map) must
    work."""
    import deepspeed_tpu
    from deepspeed_tpu.config.constants import DATA_AXIS
    from deepspeed_tpu.parallel.mesh import build_mesh
    from jax.sharding import NamedSharding, PartitionSpec

    model, params, X, Y = _wide_problem()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(data_parallel_size=8),
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
            "data_types": {"optimizer_state_dtype": "int8"},
            "steps_per_print": 10_000,
        },
    )
    leaves = _quantized_leaves(engine)
    assert len(leaves) == 1
    for leaf in leaves:
        assert leaf["q"].dtype == jnp.int8
        assert leaf["q"].shape == (1024, 256)  # nothing flattened or padded
        assert leaf["scale"].shape == (1, 1024)
        mesh = leaf["q"].sharding.mesh
        assert leaf["q"].sharding.is_equivalent_to(
            NamedSharding(mesh, PartitionSpec(DATA_AXIS, None)), 2
        )
        assert leaf["scale"].sharding.is_equivalent_to(
            NamedSharding(mesh, PartitionSpec(None, DATA_AXIS)), 2
        )
        # each chip holds an eighth of the codes: 128 whole rows
        assert leaf["q"].addressable_shards[0].data.shape == (128, 256)
    # training through the sharded quantized state converges
    losses = []
    for _ in range(12):
        loss = engine(X, Y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], losses


def test_engine_rejects_reduced_state_for_fused_lamb():
    import flax.linen as nn

    import deepspeed_tpu
    from deepspeed_tpu.config.config import DeepSpeedConfigError

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return jnp.mean(nn.Dense(4)(x) ** 2)

    model = M()
    X = jnp.zeros((8, 4), jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0)}, X)["params"]
    with pytest.raises(DeepSpeedConfigError, match="FusedLamb"):
        deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "FusedLamb", "params": {"lr": 1e-2}},
                "data_types": {"optimizer_state_dtype": "bf16"},
            },
        )


def test_engine_rejects_state_dtype_for_unsupported_optimizer():
    import flax.linen as nn

    import deepspeed_tpu
    from deepspeed_tpu.config.config import DeepSpeedConfigError

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return jnp.mean(nn.Dense(4)(x) ** 2)

    model = M()
    X = jnp.zeros((8, 4), jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0)}, X)["params"]
    with pytest.raises(DeepSpeedConfigError):
        deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "SGD", "params": {"lr": 1e-2}},
                "data_types": {"optimizer_state_dtype": "bf16"},
            },
        )


def _int8_engine(model, params, stage, dp, mp=1):
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(
            devices=jax.devices()[:dp * mp], data_parallel_size=dp,
            model_parallel_size=mp,
        ),
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage},
            "data_types": {"optimizer_state_dtype": "int8",
                           "master_dtype": "compensated"},
            "steps_per_print": 10_000,
        },
        rng_seed=0,
    )
    return engine


def _train(engine, X, Y, steps):
    for _ in range(steps):
        loss = engine(X, Y)
        engine.backward(loss)
        engine.step()
    return float(loss)


def _eval_loss(engine, X, Y):
    engine.eval()
    loss = float(engine(X, Y))
    engine.train()
    return loss


def test_int8_zero_state_elastic_dp_resume(tmp_path):
    """Quantized ZeRO state must survive an elastic dp-resize resume: the
    stored shapes are the parameters' own, whatever mesh saved them, so a
    dp4-saved checkpoint deserializes bit-for-bit into a dp8 engine's
    template (round-4 review finding: padding the flat format of the time
    to dp itself baked the saving mesh into the stored shapes)."""
    model, params, X, Y = _wide_problem()
    saver = _int8_engine(model, params, stage=2, dp=4, mp=2)
    _train(saver, X, Y, 6)
    saver.save_checkpoint(str(tmp_path), tag="el")
    fp = _eval_loss(saver, X, Y)

    loader = _int8_engine(model, params, stage=2, dp=8)
    loader.load_checkpoint(str(tmp_path), tag="el")
    assert loader.global_steps == 6
    for a, b in zip(_quantized_leaves(saver), _quantized_leaves(loader)):
        for k in ("q", "scale"):
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    np.testing.assert_allclose(_eval_loss(loader, X, Y), fp, rtol=1e-5)
    # resumed training keeps working on the new layout
    assert np.isfinite(_train(loader, X, Y, 1))


def test_int8_checkpoint_crosses_layouts(tmp_path):
    """A checkpoint saved on one device at stage 0 loads into an engine
    whose state is sharded eight ways for ZeRO, and back: no layout leaves
    a trace in what is stored (this test used to cross the block-padding
    policies of the flat format, which PR 27 retired)."""
    model, params, X, Y = _wide_problem()
    saver = _int8_engine(model, params, stage=0, dp=1)
    _train(saver, X, Y, 5)
    saver.save_checkpoint(str(tmp_path), tag="one")
    fp = _eval_loss(saver, X, Y)

    loader = _int8_engine(model, params, stage=1, dp=8)
    loader.load_checkpoint(str(tmp_path), tag="one")
    assert loader.global_steps == 5
    np.testing.assert_allclose(_eval_loss(loader, X, Y), fp, rtol=1e-5)
    assert np.isfinite(_train(loader, X, Y, 1))

    loader.save_checkpoint(str(tmp_path), tag="eight")
    fp2 = _eval_loss(loader, X, Y)
    back = _int8_engine(model, params, stage=0, dp=1)
    back.load_checkpoint(str(tmp_path), tag="eight")
    assert back.global_steps == 6
    np.testing.assert_allclose(_eval_loss(back, X, Y), fp2, rtol=1e-5)


@pytest.mark.parametrize("pad_blocks", [1, 256])
def test_int8_checkpoint_in_the_flat_format_loads(tmp_path, pad_blocks):
    """A checkpoint written before PR 27 holds EVERY first moment flat:
    int8[nb * 2048] codes and f32[nb] scales over the flattened parameter,
    the block count padded to a multiple. Loading decodes each pair and
    encodes it again in the engine's format (runtime/checkpointing.py:
    _moments_to_template), to within one code of either format."""
    from deepspeed_tpu.ops import quant as Q

    def flat(value):
        value = np.asarray(value, np.float32).reshape(-1)
        nb = -(-value.size // 2048)
        nb = -(-nb // pad_blocks) * pad_blocks
        blocks = np.pad(value, (0, nb * 2048 - value.size)).reshape(nb, 2048)
        scale = np.abs(blocks).max(1) / 127.0
        inv = np.where(scale > 0, 1.0 / np.where(scale > 0, scale, 1.0), 0.0)
        q = np.clip(np.round(blocks * inv[:, None]), -127, 127)
        return {"q": jnp.asarray(q.reshape(-1), jnp.int8),
                "scale": jnp.asarray(scale, jnp.float32)}

    model, params, X, Y = _wide_problem()
    saver = _int8_engine(model, params, stage=0, dp=1)
    _train(saver, X, Y, 5)
    state = saver.optimizer_state
    mu = jax.tree_util.tree_map(
        Q.decode_moment, state["mu"], is_leaf=Q.moment_is_leaf
    )
    saver.optimizer_state = {
        **state, "mu": jax.tree_util.tree_map(flat, mu)
    }
    saver.save_checkpoint(str(tmp_path), tag="flat")

    loader = _int8_engine(model, params, stage=1, dp=8)
    loader.load_checkpoint(str(tmp_path), tag="flat")
    assert loader.global_steps == 5
    got = jax.tree_util.tree_map(
        Q.decode_moment, loader.optimizer_state["mu"],
        is_leaf=Q.moment_is_leaf,
    )
    for want, have, stored in zip(
        jax.tree_util.tree_leaves(mu), jax.tree_util.tree_leaves(got),
        jax.tree_util.tree_leaves(
            loader.optimizer_state["mu"], is_leaf=Q.moment_is_leaf
        ),
    ):
        assert Q.is_quantized(stored) == (Q.quantized_run(want.shape) is not None)
        # half a code of the flat block, then half a code of the run
        bound = np.abs(np.asarray(want)).max() / 127.0
        np.testing.assert_allclose(
            np.asarray(have), np.asarray(want), atol=bound
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(state["nu"]),
        jax.tree_util.tree_leaves(loader.optimizer_state["nu"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(_train(loader, X, Y, 1))
