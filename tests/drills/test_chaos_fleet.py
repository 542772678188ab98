"""The serving tier under a seeded fault schedule (docs/serving.md):
zero lost or duplicated requests and bitwise greedy parity for the
survivors, over REAL GPT-2 engines.

  A. RPC corruption absorbed by the circuit breaker: a 2-replica
     SUBPROCESS fleet of real GPT-2 workers with one corrupted submit
     line on replica 0's pipe — the submit falls through to replica 1,
     the breaker opens, every answer matches a clean single engine
     bitwise.
  C. Brownout degradation: with the fleet queue in the brownout band, a
     sheddable request completes with max_new_tokens clamped to the
     floor (bitwise equal to a clean engine run at the clamped budget)
     instead of FleetOverloaded.

(The old window B, a wedged worker zombie-detected, restarted and its
request re-routed, is tests/unit/test_serving.py's
test_zombie_subprocess_hang_engine_restarted_and_rerouted.)"""

import time

import numpy as np

import deepspeed_tpu
from _common import toy_gpt2, worker_spec
from deepspeed_tpu.inference import RequestRejected
from deepspeed_tpu.resilience.faults import FaultInjector, FaultSpec
from deepspeed_tpu.serving import FleetRouter, InProcessReplica, SubprocessReplica
from deepspeed_tpu.serving.worker import build_engine_from_spec


def test_rpc_corruption_is_absorbed_by_the_breaker():
    spec = worker_spec()
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(0, 64, 6)] for _ in range(4)]

    single = build_engine_from_spec(spec)
    reference = single.generate(prompts, max_new_tokens=5)
    single.close()

    # parent-side injector on replica 0 only: sends are init (1), the
    # start() refresh snapshot (2), then per submit a candidates
    # snapshot + the submit op — traversal 4 is the FIRST submit line
    faults = FaultInjector(
        [FaultSpec("rpc.send", after=3, times=1,
                   args={"mode": "corrupt"}, seed=0)],
        seed=0,
    )
    replicas = [
        SubprocessReplica("0", spec, start_timeout=240.0, rpc_timeout=2.0,
                          fault_injector=faults),
        SubprocessReplica("1", spec, start_timeout=240.0, rpc_timeout=2.0),
    ]
    router = FleetRouter(
        replicas, monitor_interval=0.01, telemetry_refresh_secs=3600.0,
        breaker_failure_threshold=1, breaker_backoff_secs=0.5,
    ).start()
    try:
        t0 = time.monotonic()
        reqs = [router.submit(p, max_new_tokens=5) for p in prompts]
        outs = [r.result(120.0) for r in reqs]
        recovery = time.monotonic() - t0
        assert outs == reference, "divergence under RPC corruption"
        assert all(r.finish_reason == "max_new_tokens" for r in reqs)
        assert faults.injected.get("rpc.send") == 1, faults.injected
        snap = router.metrics.snapshot()
        assert snap["fleet/breaker_opens"] >= 1, snap
        assert snap["fleet/requests_completed"] == 4, snap
        assert recovery < 60.0, f"recovery took {recovery:.1f}s"
    finally:
        router.shutdown()


def test_brownout_clamps_a_sheddable_request_to_the_floor():
    rng = np.random.default_rng(7)
    _cfg, model, params = toy_gpt2(rng)

    def engine_factory():
        # queue_depth 8 keeps the 3-filler burst under the REPLICA's own
        # degraded gate (0.75) while sitting inside the FLEET's brownout
        # band (0.2): the degradation asserted is the router's, not the
        # engine's priority shedding
        return deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": {
                "max_batch_slots": 1, "max_seq_len": 64, "prefill_len": 16,
                "queue_depth": 8, "sampling": {"greedy": True},
            }},
        )

    probe_prompt = [int(t) for t in rng.integers(0, 128, 7)]
    single = engine_factory()
    clamped_reference = single.generate([probe_prompt], max_new_tokens=4)[0]
    single.close()

    router = FleetRouter(
        [InProcessReplica("0", engine_factory)], monitor_interval=0.01,
        shed_queue_ratio=0.9, brownout_queue_ratio=0.2,
        brownout_max_new_tokens=4,
    ).start()
    try:
        browned = router.metrics.counter("fleet/requests_browned_out")
        probe = None
        for _attempt in range(5):
            # fill the single slot + queue so the fill ratio sits in the
            # brownout band when the sheddable probe arrives
            fillers = [
                router.submit([int(t) for t in rng.integers(0, 128, 5)],
                              max_new_tokens=40)
                for _ in range(3)
            ]
            try:
                probe = router.submit(probe_prompt, priority=1,
                                      max_new_tokens=40)
            except RequestRejected:
                probe = None  # raced a full/degraded replica: retry
            for f in fillers:
                assert f.result(120.0), "filler request lost"
            if probe is not None and browned.value > 0:
                break
            if probe is not None:
                probe.result(120.0)  # raced an empty queue: drain, retry
                probe = None
        assert probe is not None and browned.value >= 1, (
            "brownout window never engaged"
        )
        out = probe.result(120.0)
        assert out == clamped_reference, "clamped probe diverged"
        assert len(out) == 4, out  # the floor, not the requested 40
        deadline = time.monotonic() + 30.0
        while router.brownout and time.monotonic() < deadline:
            router.refresh_telemetry()  # queue drained: the window exits
            time.sleep(0.05)
        assert not router.brownout, "brownout failed to exit"
        snap = router.metrics.snapshot()
        assert snap["fleet/brownout"] == 0.0, snap
    finally:
        router.shutdown()
