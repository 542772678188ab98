"""Two toy in-process replicas behind the FleetRouter (docs/serving.md)
serving concurrent mixed-tenant traffic through ONE rolling
drain/restart cycle with telemetry on: ZERO lost requests (every
submission answered exactly once, greedy outputs bitwise those of a
single replica), capacity never below the floor, and the fleet's p99
TTFT recorded through the telemetry sinks."""

import os
import threading
import time

import numpy as np

import deepspeed_tpu
from _common import telemetry_block, toy_gpt2


def test_rolling_restart_loses_nothing_and_records_fleet_ttft(tmp_path):
    rng = np.random.default_rng(0)
    _cfg, model, params = toy_gpt2(rng)

    def engine_factory():
        return deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": {
                "max_batch_slots": 2, "max_seq_len": 48,
                "prefill_len": 16, "sampling": {"greedy": True},
            }},
        )

    prompts = [
        [int(t) for t in rng.integers(0, 128, n)] for n in (9, 5, 13, 7)
    ]
    single = engine_factory()
    reference = single.generate(prompts, max_new_tokens=8)
    single.close()

    router = deepspeed_tpu.init_fleet(
        engine_factory=engine_factory,
        config={
            "serving": {"replicas": 2, "capacity_floor": 0.5},
            "telemetry": telemetry_block(tmp_path, "fleet"),
        },
    )
    available = router.metrics.gauge("fleet/replicas_available")
    floor_breaches = []
    results, errors = {}, []

    def client(i):
        tenant = "alpha" if i % 2 == 0 else "beta"
        try:
            req = router.submit(
                prompts[i % 4], tenant=tenant, max_new_tokens=8
            )
            results.setdefault(i, []).append(req.result(300.0))
        except Exception as e:
            errors.append((i, repr(e)))

    stop_watch = threading.Event()

    def watch_floor():
        while not stop_watch.is_set():
            if available.value < 1.0:  # ceil(0.5 * 2) replicas
                floor_breaches.append(available.value)
            time.sleep(0.002)

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        watcher = threading.Thread(target=watch_floor, daemon=True)
        watcher.start()
        router.rolling_restart(wait_timeout=120.0)  # the drain/restart cycle
        for t in threads:
            t.join(300.0)
        stop_watch.set()
        watcher.join(5.0)

        assert not errors, errors
        assert len(results) == 8, f"lost requests: {sorted(results)}"
        for i, answers in results.items():
            assert len(answers) == 1, f"request {i} answered {len(answers)}x"
            assert answers[0] == reference[i % 4], f"request {i} diverged"
        router.refresh_telemetry()
        snap = router.metrics.snapshot()
        assert snap["fleet/requests_completed"] == 8, snap
        assert snap["fleet/replica_restarts"] == 2, snap
        assert snap["fleet/ttft_ms/count"] == 8, snap
        assert snap["fleet/ttft_p99_ms"] > 0, "fleet p99 TTFT not recorded"
        assert not floor_breaches, floor_breaches
    finally:
        stop_watch.set()
        router.shutdown()
    prom = open(
        os.path.join(tmp_path, "telemetry", "fleet", "metrics.prom")
    ).read()
    assert "fleet_ttft_ms_bucket" in prom, "fleet TTFT missing from prom"
    assert "fleet_requests_routed" in prom, "fleet counters missing"
