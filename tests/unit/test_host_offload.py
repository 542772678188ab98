"""ZeRO-Offload analog: fp32 master + moments on the host cpu device
(`zero_optimization.offload_optimizer: {"device": "cpu"}`).

It trades step time for HBM (docs/memory.md sets it beside compensated
masters); the SEMANTICS pinned here: state
placement on the cpu device, numerics identical to the on-accelerator
master path, exact checkpoint resume, overflow-skip intact.
"""

import flax.linen as nn
import pytest

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.config.config import DeepSpeedConfigError
from deepspeed_tpu.parallel.mesh import build_mesh

pytestmark = pytest.mark.slow  # compile-heavy; excluded from `make test-fast`


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, y, train=True):
        h = nn.relu(nn.Dense(32)(x))
        logp = jax.nn.log_softmax(nn.Dense(4)(h))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def _data(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(16, 8)).astype(np.float32)
    Y = (X[:, 0] > 0).astype(np.int32) + 2 * (X[:, 1] > 0).astype(np.int32)
    return X, Y


def _engine(offload, seed=0, dp=8):
    X, Y = _data()
    model = MLP()
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, jnp.asarray(X), jnp.asarray(Y)
    )["params"]
    zero = {"stage": 2}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu"}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(data_parallel_size=dp),
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "zero_optimization": zero,
            "steps_per_print": 10_000,
        },
        rng_seed=0,
    )
    return engine


def _train(engine, steps=10):
    X, Y = _data()
    out = []
    for _ in range(steps):
        loss = engine(X, Y)
        engine.backward(loss)
        engine.step()
        out.append(float(loss))
    return np.asarray(out)


def test_offload_state_lives_on_host():
    engine = _engine(offload=True)
    assert engine.host_offload and engine.master_in_opt
    cpu = jax.devices("cpu")[0]
    for leaf in jax.tree_util.tree_leaves(engine.optimizer_state):
        assert leaf.devices() == {cpu}, leaf.devices()
    masters = jax.tree_util.tree_leaves(engine.optimizer_state["master"])
    assert all(m.dtype == jnp.float32 for m in masters)
    # accelerator-side params stay in the compute dtype
    for leaf in jax.tree_util.tree_leaves(engine.params):
        assert leaf.dtype == engine.compute_dtype


def test_offload_matches_on_device_master_numerics():
    """Moving the master to the host must not change a single step (same
    fp32 math, same bf16 publish) — the ZeRO master placement contract."""
    on_dev = _train(_engine(offload=False))
    off = _train(_engine(offload=True))
    np.testing.assert_array_equal(on_dev, off)
    assert off[-1] < 0.5 * off[0]


def test_offload_train_batch_path():
    engine = _engine(offload=True)
    X, Y = _data()
    accum = engine.gradient_accumulation_steps()
    losses = [
        float(engine.train_batch(iter([(X, Y)] * accum))) for _ in range(8)
    ]
    assert losses[-1] < losses[0]
    assert engine.global_steps == 8


def test_offload_checkpoint_resume_exact(tmp_path):
    engine = _engine(offload=True)
    _train(engine, steps=6)
    engine.save_checkpoint(str(tmp_path), tag="t")
    cont = _train(engine, steps=6)

    fresh = _engine(offload=True, seed=7)
    fresh.load_checkpoint(str(tmp_path), tag="t")
    # restored state must land back on the host
    cpu = jax.devices("cpu")[0]
    for leaf in jax.tree_util.tree_leaves(fresh.optimizer_state):
        assert leaf.devices() == {cpu}
    resumed = _train(fresh, steps=6)
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)


def test_offload_rejects_compensated_combo():
    X, Y = _data()
    model = MLP()
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.asarray(X), jnp.asarray(Y)
    )["params"]
    with pytest.raises(DeepSpeedConfigError, match="offload"):
        deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            mesh=build_mesh(data_parallel_size=8),
            config_params={
                "train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "bf16": {"enabled": True},
                "zero_optimization": {
                    "stage": 2, "offload_optimizer": {"device": "cpu"},
                },
                "data_types": {"master_dtype": "compensated"},
            },
        )


def test_offload_config_validation():
    from deepspeed_tpu.config.zero_config import DeepSpeedZeroConfig

    cfg = DeepSpeedZeroConfig(
        {"zero_optimization": {"stage": 2,
                               "offload_optimizer": {"device": "cpu"}}}
    )
    assert cfg.offload_optimizer_device == "cpu"
    assert DeepSpeedZeroConfig(
        {"zero_optimization": {"stage": 2}}
    ).offload_optimizer_device == "none"
    with pytest.raises(ValueError, match="offload_optimizer"):
        DeepSpeedZeroConfig(
            {"zero_optimization": {"offload_optimizer": {"device": "nvme"}}}
        )
    # a block WITHOUT an explicit device (e.g. a ported config carrying
    # only pin_memory) must not silently enable offload — upstream's
    # device default is 'none'
    assert DeepSpeedZeroConfig(
        {"zero_optimization": {"offload_optimizer": {"pin_memory": True}}}
    ).offload_optimizer_device == "none"
