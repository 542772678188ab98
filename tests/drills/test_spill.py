"""Host-memory spill tier, what tests/unit/test_host_tier.py leaves
open: with the Prometheus exporter on, a spill -> promote cycle on a
served engine lands the host_tier/* catalog in the textfile export.
Spill/promote parity, peer promotion, the preemption cycle and adapter
auto-load are test_kv_spill_promote_bitwise_roundtrip,
test_peer_promotion_warms_cohosted_engine,
test_preempt_park_resume_bitwise_exactness and
test_adapter_spill_and_auto_load_with_generation_restore there."""

import os

import numpy as np

import deepspeed_tpu
from _common import prompt, telemetry_block, toy_gpt2


def test_host_tier_streams_reach_the_prometheus_export(tmp_path):
    _cfg, model, params = toy_gpt2(np.random.default_rng(0))
    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config={
            "inference": {
                "max_batch_slots": 4, "max_seq_len": 48, "prefill_len": 32,
                "kv_block_size": 8, "kv_pool_blocks": 6,
                "sampling": {"greedy": True},
                "host_tier": {"enabled": True, "share_group": "drill"},
            },
            "telemetry": telemetry_block(
                tmp_path, "spill", exporters=["prometheus"]
            ),
        },
    )
    try:
        template = prompt(16, 7)  # two full 8-token pages once registered
        cold_out = engine.generate([template + prompt(4, 8)],
                                   max_new_tokens=4)[0]
        assert engine.block_pool.cached_blocks == 2
        churn = [engine.submit(prompt(8, 20 + i), max_new_tokens=8)
                 for i in range(3)]
        engine.scheduler.run_until_idle()
        assert all(len(r.result(0)) == 8 for r in churn)
        snap = engine.kv_snapshot()
        assert snap["host_tier_spills"] >= 2, (
            f"evicted prefix pages did not spill: {snap}"
        )
        hot_out = engine.generate([template + prompt(4, 8)],
                                  max_new_tokens=4)[0]
        snap = engine.kv_snapshot()
        assert snap["host_tier_promotions"] >= 1, snap
        assert hot_out == cold_out, "promoted pages diverged from the cold serve"
    finally:
        engine.close()
    prom = open(
        os.path.join(tmp_path, "telemetry", "spill", "metrics.prom")
    ).read()
    for stream in ("host_tier_spills", "host_tier_promotions",
                   "host_tier_occupancy_bytes"):
        assert stream in prom, f"{stream} missing from the prom sink"
