"""The multi-tenant LoRA vertical slice end to end (docs/adapters.md) —
a toy base GPT-2 trains one window and checkpoints; TWO tenant adapters
fine-tune on top of it (base bitwise-frozen, adapter-only optimizer
state) onto distinctive token distributions and commit adapter-only
checkpoints through the atomic protocol; a multi-LoRA serving engine
then loads both checkpoints into its in-HBM pool and serves tenant-a,
tenant-b, and a base request CONCURRENTLY in one continuous batch.
Asserts: base frozen, adapter checkpoint < 2% of the base checkpoint,
zero recompiles across the adapter mix change, distinct greedy output
per adapter, adapters/* telemetry populated."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from _common import toy_gpt2

# each tenant's corpus is one repeated token, so a converged adapter
# greedily continues any prompt with its tenant's token — cheap,
# deterministic per-tenant behavior the serving check can observe
TENANTS = {"tenant-a": 7, "tenant-b": 11}


def _dir_bytes(d):
    return sum(
        os.path.getsize(os.path.join(r, f))
        for r, _dirs, files in os.walk(d) for f in files
    )


@pytest.fixture(scope="module")
def tuned(tmp_path_factory):
    """Base checkpoint + one fine-tuned adapter checkpoint per tenant,
    with what each fine-tune showed on the way."""
    tmp = tmp_path_factory.mktemp("lora")
    world = jax.device_count()
    rng = np.random.default_rng(0)
    cfg, model, params = toy_gpt2(rng, vocab_size=512, n_embd=64)
    base_host = jax.tree_util.tree_map(np.asarray, params)

    # ---- 1. base model: one training window + a full checkpoint -------
    base_ckpt = str(tmp / "base_ckpt")
    engine, _o, _d, _s = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_batch_size": 8 * world,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        },
    )
    batch = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8 * world, 16)), jnp.int32
    )
    engine.train_batch([(batch, batch)])
    assert engine.save_checkpoint(base_ckpt, tag="base")
    base_bytes = _dir_bytes(base_ckpt)

    # ---- 2. two tenant adapters fine-tune on the SAME base ------------
    runs = {}
    for tenant, tok in TENANTS.items():
        eng_t, _o2, _d2, _s2 = deepspeed_tpu.initialize(
            model=model, model_parameters=base_host,
            config_params={
                "train_batch_size": 8 * world,
                "optimizer": {"type": "adam", "params": {"lr": 0.3}},
                "adapters": {"enabled": True, "rank": 1},
            },
        )
        tb = jnp.full((8 * world, 16), tok, jnp.int32)
        losses = [float(eng_t.train_batch([(tb, tb)])) for _ in range(6)]
        frozen = jax.tree_util.tree_map(
            np.asarray, eng_t.frozen_base_params
        )
        ckpt_dir = str(tmp / f"{tenant}_ckpt")
        assert eng_t.save_checkpoint(ckpt_dir, tag="tuned")
        runs[tenant] = {
            "losses": losses, "frozen": frozen, "ckpt": ckpt_dir,
            "ratio": _dir_bytes(ckpt_dir) / base_bytes,
        }
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 9)]
    return model, base_host, runs, prompt


@pytest.mark.parametrize("tenant", sorted(TENANTS))
def test_finetune_learns_on_a_frozen_base_and_saves_a_small_checkpoint(
        tuned, tenant):
    _model, base_host, runs, _prompt = tuned
    run = runs[tenant]
    assert run["losses"][-1] < run["losses"][0], (tenant, run["losses"])
    # the base is BITWISE-frozen across the whole fine-tune
    for (kp, a), (_kq, b) in zip(
        jax.tree_util.tree_flatten_with_path(run["frozen"])[0],
        jax.tree_util.tree_flatten_with_path(base_host)[0],
    ):
        assert np.array_equal(a, b.astype(a.dtype)), (tenant, kp)
    assert run["ratio"] < 0.02, (
        f"{tenant} adapter checkpoint is {run['ratio']:.1%} of the base "
        "checkpoint (must be < 2%)"
    )


def test_both_adapters_and_the_base_share_one_continuous_batch(tuned):
    model, base_host, runs, prompt = tuned
    serve = deepspeed_tpu.init_inference(
        model=model, model_parameters=base_host,
        config={
            "inference": {
                "max_batch_slots": 3, "max_seq_len": 48,
                "prefill_len": 16, "sampling": {"greedy": True},
            },
            "adapters": {"enabled": True, "rank": 1, "pool_slots": 4},
        },
    )
    try:
        recompiles = serve.metrics.counter("jax/recompiles")
        serve.load_adapter("tenant-a", load_dir=runs["tenant-a"]["ckpt"])
        out_a = serve.generate([prompt], max_new_tokens=8,
                               adapter="tenant-a")[0]
        out_base = serve.generate([prompt], max_new_tokens=8)[0]
        warm = recompiles.value
        # tenant-b's checkpoint loads into the live engine and joins a batch
        # already mixing tenant-a and base traffic — zero recompiles
        serve.load_adapter("tenant-b", load_dir=runs["tenant-b"]["ckpt"])
        r_a = serve.submit(prompt, max_new_tokens=8, adapter="tenant-a")
        r_b = serve.submit(prompt, max_new_tokens=8, adapter="tenant-b")
        r_0 = serve.submit(prompt, max_new_tokens=8)
        serve.scheduler.run_until_idle()
        assert recompiles.value == warm, (
            f"{recompiles.value - warm} recompiles after the adapter mix "
            "changed"
        )
        assert r_a.tokens == out_a and r_0.tokens == out_base
        outs = {"tenant-a": r_a.tokens, "tenant-b": r_b.tokens,
                "base": r_0.tokens}
        assert len({tuple(v) for v in outs.values()}) == 3, (
            f"adapter outputs not distinct: {outs}"
        )
        # each converged adapter parrots its tenant's token
        for tenant, tok in TENANTS.items():
            assert outs[tenant].count(tok) >= 6, (tenant, tok, outs[tenant])
        snap = serve.load_snapshot()
        assert snap["adapters_loaded"] == ["tenant-a", "tenant-b"]
        assert snap["adapter_requests"]["tenant-a"] == 2
        metrics = serve.metrics.snapshot()
        assert metrics["adapters/pool_occupancy"] == 2
        assert metrics["adapters/loads"] == 2
        assert metrics["adapters/requests/tenant-b"] == 1
    finally:
        serve.close()
