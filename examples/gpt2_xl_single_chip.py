"""GPT-2 1.5B (the reference perf harness's flagship,
tests/model/Megatron_GPT2/run_perf_test.py:18-34) training on a SINGLE
16 GB TPU chip — the configuration behind the headline bench number
(5.4k tokens/s, 1.32x the reference's per-GPU claim; docs/memory.md).

The recipe: compensated bf16 masters + int8/bf16 Adam moments + bf16 grad
accumulation + blocked LM-head cross-entropy + flash-residual-only remat,
holding total training state at 8 bytes/param. The reference needs ZeRO
over 4+ GPUs for this model.

    python examples/gpt2_xl_single_chip.py          # full 1.5B (TPU)
    GPT2_PRESET=small python examples/gpt2_xl_single_chip.py  # smoke (CPU ok)
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

SEQ = 1024


def main():
    preset = os.environ.get("GPT2_PRESET", "xl")
    if preset == "xl":
        cfg = GPT2Config.xl_1_5b(
            remat=True, remat_policy="flash_out+flash_lse"
        )
        micro, steps = 4, 20
    else:  # smoke-test shape for CPU runs
        cfg = GPT2Config(
            vocab_size=1024, n_positions=256, n_embd=256, n_layer=4,
            n_head=8, remat=True, remat_policy="flash_out+flash_lse",
            use_flash=jax.devices()[0].platform == "tpu",
        )
        micro, steps = 4, 10

    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    seq = min(SEQ, cfg.n_positions)
    ids = rng.integers(0, cfg.vocab_size, (micro, seq)).astype(np.int32)

    import dataclasses

    init_model = GPT2LMHeadModel(dataclasses.replace(cfg, use_flash=False))
    from deepspeed_tpu.utils.device import host_cpu_device

    with jax.default_device(host_cpu_device()):
        params = init_model.init(
            {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
            jnp.asarray(ids[:1]), jnp.asarray(ids[:1]),
        )["params"]
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f"params: {n / 1e6:.1f}M")

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters=params,
        config_params={
            "train_batch_size": micro,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            # the single-chip memory recipe (docs/memory.md)
            "data_types": {
                "master_dtype": "compensated",
                "optimizer_state_dtype": "int8",
                "grad_accum_dtype": "bf16",
            },
            "scheduler": {
                "type": "WarmupLR",
                "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-4,
                           "warmup_num_steps": 1000},
            },
            "steps_per_print": 5,
        },
    )
    del params

    t0 = time.time()
    for step in range(steps):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        if step == 0:
            print(f"first step (compile) {time.time() - t0:.1f}s "
                  f"loss={float(loss):.4f}")
            t0 = time.time()
    dt = (time.time() - t0) / max(1, steps - 1)
    print(
        f"loss={float(loss):.4f}  {dt * 1000:.0f} ms/step  "
        f"{micro * seq / dt:.0f} tokens/s  "
        f"({6 * n * micro * seq / dt / 1e12:.1f} model TFLOPS)"
    )


if __name__ == "__main__":
    main()
