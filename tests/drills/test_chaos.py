"""A toy training run under the fault-injection registry
(docs/resilience.md): one injected checkpoint-I/O fault (absorbed by
retry backoff) and one NaN-gradient fault (healed by a supervisor
rollback to the last committed checkpoint, replayed from the rewound
data source) in the SAME run, with the staged input pipeline on. The
run must COMPLETE: >= 1 recorded rollback, every loss finite, both
faults recorded, and the io-retry counter moved."""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.resilience import ReplayableDataSource


def test_training_heals_an_io_fault_and_a_nan_window(tmp_path):
    micro, dim = 4, 8

    def loss_fn(params, batch, rng):
        x, y = batch
        pred = x @ params["w"]
        noise = 0.01 * jax.random.normal(rng, pred[:, 0].shape)
        return jnp.mean((pred[:, 0] + noise - y) ** 2)

    rng = np.random.default_rng(0)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 10_000,
        # staged input pipeline ON: the rollback must close, rewind, and
        # re-arm the stager (the production self-healing path)
        "data_pipeline": {"enabled": True, "staging_buffers": 2},
        "resilience": {
            "supervisor": {
                "enabled": True, "nonfinite_window": 1, "max_rollbacks": 2,
            },
            "fault_injection": {
                "enabled": True,
                "faults": [
                    {"site": "checkpoint.write", "times": 1},
                    {"site": "grads.nan", "after": 4, "times": 1},
                ],
            },
        },
    }
    params = {"w": rng.standard_normal((dim, 1)).astype(np.float32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=params, config_params=config,
    )
    rows = micro * engine.dp_world_size

    def factory(start):
        def gen(i):
            while True:
                r = np.random.default_rng(7_000 + i)
                yield (
                    r.standard_normal((rows, dim)).astype(np.float32),
                    r.standard_normal((rows,)).astype(np.float32),
                )
                i += 1

        return gen(start)

    source = ReplayableDataSource(factory)
    try:
        losses = [float(engine.train_batch(source)) for _ in range(2)]
        # the commit point the rollback restores; its first file write eats
        # the injected OSError under retry backoff
        engine.save_checkpoint(str(tmp_path), tag="chaos_base")
        # window 5 (traversal 5 of grads.nan, after=4) is NaN-poisoned: the
        # supervisor detects the non-finite window, rolls back to chaos_base,
        # rewinds the source, and the loop completes as if nothing happened
        losses += [float(engine.train_batch(source)) for _ in range(6)]
    finally:
        engine.close_data_pipeline()

    snap = engine.resilience.registry.snapshot()
    assert all(np.isfinite(losses)), losses
    assert snap["resilience/rollbacks"] >= 1, snap
    assert snap["resilience/faults_injected"] == 2, snap
    assert snap["resilience/io_retries"] >= 1, snap
    assert snap["resilience/anomalies"] >= 1, snap
