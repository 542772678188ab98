"""ZeRO stage 3 on a 2-way data-parallel CPU mesh, what
tests/unit/test_zero3.py leaves open: at ``stage3_gather_block`` 1 (the
exact-byte gathers) a stage-3 run is bitwise-reproducible against
itself, and a checkpoint it saves loads into a STAGE-2 engine bitwise
(the unit suite has stage 3 -> 0 and stage 2 -> 3). The arming, the
dp-sharded leaves, the first window bitwise against stage 2 and the
trajectory are test_zero3.py's section 3."""

import jax
import numpy as np
from jax.sharding import Mesh

import deepspeed_tpu
from _common import toy_gpt2


def _build(stage, zextra=None):
    # fresh config per engine: the engine arms the gather seam by
    # setting cfg.zero3_gather, and init must always run the plain
    # nn.scan path so every engine starts from identical params
    _cfg, model, params = toy_gpt2(
        np.random.default_rng(0), n_positions=32, n_head=2, remat=True,
        use_flash=True,
    )
    z = {"stage": stage}
    z.update(zextra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters=params,
        mesh=Mesh(np.array(jax.devices()[:2]), ("data",)),
        rng_seed=0,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": z,
            "steps_per_print": 10_000,
        },
    )
    return engine


def _run(engine, n=3):
    r = np.random.default_rng(7)
    out = []
    for _ in range(n):
        b = r.integers(0, 128, (8, 16)).astype(np.int32)
        loss = engine.train_batch(iter([(b, b)]))
        out.append((float(loss), float(engine._last_grad_norm)))
    return out


def test_stage3_reproducible_and_its_checkpoint_loads_at_stage2(tmp_path):
    e3 = _build(3, {"stage3_gather_block": 1})
    assert e3.zero3_gather_enabled, "stage-3 gather seam did not arm"
    s3 = _run(e3)
    # stage 3 is bitwise-reproducible against itself
    assert _run(_build(3, {"stage3_gather_block": 1})) == s3

    # checkpoint roundtrip: dp-sharded save -> replicated-stage load is
    # bitwise (save gathers to host, load re-shards to the active specs)
    assert e3.save_checkpoint(str(tmp_path), tag="xfer")
    want = jax.tree_util.tree_map(np.asarray, e3.params)
    dst = _build(2)
    path, _ = dst.load_checkpoint(str(tmp_path), tag="xfer")
    assert path is not None, "stage-2 engine failed to load stage-3 save"
    got = jax.tree_util.tree_map(np.asarray, dst.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), want, got
    )
