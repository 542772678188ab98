"""Kernel autotuning: empirical block-size selection for the Pallas flash
attention kernel.

The TPU counterpart of the reference's GEMM autotuner
(reference: csrc/includes/gemm_test.h:27-293 — `GemmTest` sweeps
``cublasGemmAlgo_t`` over fwd/bw1/bw2 and picks the fastest; invoked via the
layer config's ``test_gemm`` flag). On TPU, XLA autotunes its own GEMMs, so
the only hand-scheduled choice left is the flash kernel's (block_q,
block_k) outer tiling — which is worth real time: on one "TPU v5 lite"
chip at [8, 20, 1024, 64] bf16 causal the three kernels alone take
0.76 + 0.89 + 1.33 ms (forward + dq + dkv, device time) at 512x512 blocks
and 0.43 + 0.48 + 0.60 ms at 1024x1024, where the sequence is one block
each way and the kernels' loops unroll (PR 25, docs/TESTING.md; the
comment above DEFAULT_BLOCK_Q in ops/attention.py has the kernels before
PR 25 beside them). The sub-tiles inside a block are the kernels' own
choice (ops/attention.py:pick_subtiles).

Use offline (results are cached per (shape, causal, device-kind)):

    from deepspeed_tpu.ops.autotune import autotune_flash_blocks
    (bq, bk), table = autotune_flash_blocks(batch=4, heads=20, seq=1024,
                                            head_dim=64, causal=True)
    layer = flash_attention(..., block_q=bq, block_k=bk)
"""

import time

import jax
import jax.numpy as jnp

_CACHE = {}

DEFAULT_CANDIDATES = ((128, 128), (256, 256), (512, 512), (1024, 1024))


def autotune_flash_blocks(
    batch, heads, seq, head_dim, *, causal=False, dtype=jnp.bfloat16,
    candidates=DEFAULT_CANDIDATES, steps=5, include_backward=True,
):
    """Time fwd (+bwd) of the flash kernel for each (block_q, block_k) and
    return ``((best_bq, best_bk), {blocks: seconds_per_step})``.

    Candidates that don't tile ``seq`` or whose VMEM footprint the compiler
    rejects are skipped. Like gemm_test.h, this measures the real kernels on
    the real device — run it once offline, not in the training loop.
    """
    from .attention import flash_attention

    key = (batch, heads, seq, head_dim, causal, str(dtype),
           tuple(candidates), include_backward,
           jax.devices()[0].device_kind)
    if key in _CACHE:
        return _CACHE[key]

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, heads, seq, head_dim)
    q, k, v = (jax.random.normal(kk, shape, dtype) * 0.5 for kk in ks)

    results = {}
    for bq, bk in candidates:
        bq_eff, bk_eff = min(bq, seq), min(bk, seq)
        if seq % bq_eff or seq % bk_eff:
            continue
        if (bq_eff, bk_eff) in results:
            continue  # clamped duplicates: don't re-time the same config

        if include_backward:
            def run(q, k, v, bq=bq_eff, bk=bk_eff):
                def loss(q, k, v):
                    out = flash_attention(
                        q, k, v, causal=causal, block_q=bq, block_k=bk
                    )
                    return jnp.sum(out.astype(jnp.float32) ** 2)

                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        else:
            def run(q, k, v, bq=bq_eff, bk=bk_eff):
                return flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk
                )

        try:
            f = jax.jit(run)
            out = f(q, k, v)
            jax.block_until_ready(out)
            t0 = time.time()
            for _ in range(steps):
                out = f(q, k, v)
            jax.block_until_ready(out)
            results[(bq_eff, bk_eff)] = (time.time() - t0) / steps
        except Exception:  # noqa: BLE001 — VMEM/lowering rejection: skip
            continue

    if not results:
        raise RuntimeError(
            f"no flash block candidate compiled for seq={seq} "
            f"(candidates {candidates})"
        )
    best = min(results, key=results.get)
    _CACHE[key] = (best, results)
    return best, results
