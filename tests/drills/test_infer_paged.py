"""Paged KV cache, what tests/unit/test_paged_kv.py leaves open: at
kv_block_size 32 the paged engine sustains TWICE the contiguous
engine's slots under the SAME cache bytes (the infer/kv_cache_bytes
gauges), with all eight slots occupied at once, zero recompiles while
they fill, and every page back in the pool afterwards. Parity with the
contiguous path, the prefix hit and the warm-hit recompile pin are
test_paged_engine_matrix_matches_contiguous,
test_prefix_hit_counts_and_matches_cold_generation and
test_paged_decode_steps_do_not_recompile there."""

import numpy as np

import deepspeed_tpu
from _common import prompt, toy_gpt2


def test_paged_engine_doubles_the_slots_in_the_same_cache_bytes():
    _cfg, model, params = toy_gpt2(np.random.default_rng(0), n_positions=256)

    def build(block):
        base = {"max_seq_len": 128, "prefill_len": 64,
                "sampling": {"greedy": True}}
        base.update(block)
        return deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": base},
        )

    # contiguous baseline: 4 slots x 128 positions = 512 cache rows
    contiguous = build({"max_batch_slots": 4})
    # paged, same HBM: 15 usable + 1 null page of 32 tokens = 512 rows —
    # but EIGHT slots: short mixed-length requests reserve only the pages
    # they can touch, so 2x the concurrency fits the same bytes
    paged = build({
        "max_batch_slots": 8, "kv_block_size": 32, "kv_pool_blocks": 15,
    })
    try:
        bytes_c = contiguous.metrics.gauge("infer/kv_cache_bytes").value
        bytes_p = paged.metrics.gauge("infer/kv_cache_bytes").value
        assert bytes_p <= bytes_c, (
            f"paged pool ({bytes_p}B) exceeds the contiguous cache "
            f"({bytes_c}B) it claims to undercut"
        )
        assert paged.num_slots == 2 * contiguous.num_slots

        # warm every program on the same mixed-length workload first
        prompts = [prompt(9, 1), prompt(24, 2), prompt(5, 3), prompt(14, 4)]
        out_c = contiguous.generate(prompts, max_new_tokens=8)
        out_p = paged.generate(prompts, max_new_tokens=8)
        assert out_c == out_p, "paged decode diverged from the contiguous path"

        # 2x slots under the same HBM: saturate all 8 paged slots
        recompiles = paged.metrics.counter("jax/recompiles")
        warm = recompiles.value
        mixed = [paged.submit(prompt(6 + 2 * i, 10 + i), max_new_tokens=8)
                 for i in range(8)]
        for _ in range(3):
            paged.scheduler.step()
        occupancy = paged.metrics.gauge("infer/slot_occupancy").value
        assert occupancy == 8, (
            f"paged engine only sustained {occupancy} of 8 slots "
            "(pool too small for the mixed workload?)"
        )
        paged.scheduler.run_until_idle()
        assert all(len(r.result(0)) == 8 for r in mixed)
        saturate_recompiles = int(recompiles.value - warm)
        assert saturate_recompiles == 0, (
            f"{saturate_recompiles} recompiles while saturating slots"
        )
        snap = paged.metrics.snapshot()
        assert snap["infer/kv_pool_occupancy"] == 0, "pages leaked after idle"
    finally:
        contiguous.close()
        paged.close()
