"""The SLO autoscaler's elastic loop over REAL TCP node fleets
(docs/serving.md "SLO autoscaling").

  A. Surge -> predictive scale-up -> idle scale-down: a burst of
     requests against a 1-replica node fleet of real tiny GPT-2
     engines pushes predicted load over the scale-up line while the
     queue fill is still BELOW the brownout band — the autoscaler
     spawns a second replica on the node (control-session
     spawn_replica; it joins the router behind its half-open probe)
     with ZERO requests shed and ZERO requests browned out, every
     request answered exactly once with bitwise greedy parity
     against a clean single engine. The following idle window
     drains the spawned replica back out (drain -> retire; its
     gauges retire with it; the node frees the engine) with zero
     lost requests.
  B. SIGKILL re-provision: a 2-node stub fleet loses one node to
     SIGKILL; the socket replica exhausts its reconnect budget, the
     router evicts it, and the autoscaler restores the lost
     capacity on the surviving node within the budget.
"""

import time

import numpy as np

from _common import kill, launch_node, stub_answer, wait_for, worker_spec
from deepspeed_tpu.serving import (
    Autoscaler,
    FleetRouter,
    SLOTargets,
    SocketNodeProvider,
    SocketReplica,
)
from deepspeed_tpu.serving.transport import NodeControlClient
from deepspeed_tpu.serving.worker import build_engine_from_spec
from deepspeed_tpu.telemetry.registry import MetricsRegistry


def test_surge_scales_up_before_the_cliff_and_idle_scales_down():
    spec = worker_spec(n_positions=48, max_seq_len=40, queue_depth=32)
    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(0, 64, 6)]
               for _ in range(10)]

    single = build_engine_from_spec(spec)
    reference = single.generate(prompts, max_new_tokens=24)
    single.close()

    proc_a, addr_a = launch_node("n0", spec)
    reg = MetricsRegistry()
    provider = SocketNodeProvider(
        {"n0": {"address": f"{addr_a[0]}:{addr_a[1]}",
                "replicas": ["r0"]}},
        rpc_timeout=5.0, connect_timeout=5.0, spawn_timeout=180.0,
        registry=reg,
    )
    autoscaler = Autoscaler(
        provider,
        slo=SLOTargets(ttft_p99_ms=200.0, eval_window_secs=10.0),
        min_replicas=1, max_replicas=2, cooldown_secs=0.2,
        hysteresis_secs=0.4, flap_budget=8, interval_secs=0.05,
        scale_up_utilization=0.5, scale_down_utilization=0.3,
        drain_timeout_secs=30.0,
    )
    router = FleetRouter(
        [SocketReplica("n0:r0", addr_a, remote_name="r0",
                       rpc_timeout=5.0, registry=reg)],
        registry=reg, monitor_interval=0.01,
        brownout_queue_ratio=0.35, brownout_max_new_tokens=4,
        autoscaler=autoscaler,
    ).start()
    try:
        # the surge: 10 requests against 2 slots — fill 10/32 = 0.31
        # sits BELOW the 0.35 brownout band, but at 0.8 * 0.35 = 0.28
        # the predictive policy already calls the load SLO-unmeetable
        reqs = [router.submit(p, max_new_tokens=24) for p in prompts]
        wait_for(
            lambda: len(router.live_replica_ids()) == 2, 120.0,
            "the surge never scaled the fleet to a second replica",
        )
        # the executor counts the transition just after registration
        wait_for(
            lambda: reg.counter("fleet/autoscale_ups").value >= 1,
            10.0, "scale-up never counted",
        )
        # the proactive pin: elastic capacity arrived while degradation
        # stayed idle — nothing shed, nothing browned out, band never
        # entered
        assert not router.brownout, (
            "the brownout band engaged before the autoscaler acted"
        )
        outs = [r.result(120.0) for r in reqs]
        assert outs == reference, "divergence through the scale-up"
        snap = reg.snapshot()
        assert snap["fleet/requests_shed"] == 0.0, snap
        assert snap["fleet/requests_browned_out"] == 0.0, snap
        assert snap["fleet/brownout"] == 0.0, snap
        assert snap["fleet/requests_completed"] == len(prompts), snap
        # idle: sustained headroom drains the spawned replica back out
        wait_for(
            lambda: len(router.live_replica_ids()) == 1, 120.0,
            "idle never scaled the fleet back down",
        )
        wait_for(
            lambda: reg.counter("fleet/autoscale_downs").value >= 1,
            10.0, "scale-down never counted",
        )
        snap = reg.snapshot()
        # exactly-once held through the drain (no lost, no duplicated)
        assert snap["fleet/requests_completed"] == len(prompts), snap
        # the retired replica's gauges left the registry with it
        stale = [k for k in snap if k.startswith("fleet/replican0:as")]
        assert stale == [], stale
        # the node freed the engine (control-plane retire landed)
        wait_for(
            lambda: NodeControlClient(addr_a).node_info()["replicas"]
            == ["r0"],
            30.0, "the node still hosts the retired replica's engine",
        )
        # the shrunken fleet still serves, bitwise
        probe = router.submit(prompts[0], max_new_tokens=24)
        assert probe.result(60.0) == reference[0]
    finally:
        router.shutdown()
        kill(proc_a)


def test_sigkilled_node_is_reprovisioned_on_the_survivor():
    stub_spec = {"stub": {"delay_secs": 0.05}}
    proc_c, addr_c = launch_node("nc", stub_spec)
    proc_d, addr_d = launch_node("nd", stub_spec)
    reg = MetricsRegistry()
    provider = SocketNodeProvider(
        {"nc": {"address": f"{addr_c[0]}:{addr_c[1]}",
                "replicas": ["r0"]},
         "nd": {"address": f"{addr_d[0]}:{addr_d[1]}",
                "replicas": ["r0"]}},
        rpc_timeout=1.0, connect_timeout=2.0, connect_retries=1,
        spawn_timeout=60.0, node_retry_secs=5.0, registry=reg,
    )
    autoscaler = Autoscaler(
        provider, min_replicas=2, max_replicas=3, interval_secs=0.05,
        cooldown_secs=3600.0,  # re-provision must not need the cooldown
    )
    rc = SocketReplica("nc:r0", addr_c, remote_name="r0",
                       rpc_timeout=1.0, registry=reg)
    rd = SocketReplica("nd:r0", addr_d, remote_name="r0",
                       rpc_timeout=1.0, reconnect_attempts=2,
                       reconnect_backoff_secs=0.05, registry=reg)
    router = FleetRouter(
        [rc, rd], registry=reg, monitor_interval=0.01,
        breaker_failure_threshold=1, breaker_backoff_secs=0.2,
        autoscaler=autoscaler,
    ).start()
    try:
        assert autoscaler.state.target == 2
        t0 = time.monotonic()
        proc_d.kill()  # chaos takes a whole node
        wait_for(
            lambda: "nd:r0" in router.evicted_ids, 60.0,
            "the dead node's replica was never evicted",
        )
        wait_for(
            lambda: len(router.live_replica_ids()) == 2, 60.0,
            "the lost capacity was never re-provisioned",
        )
        reprovision_secs = time.monotonic() - t0
        wait_for(
            lambda: reg.counter(
                "fleet/autoscale_reprovisions"
            ).value >= 1,
            10.0, "re-provision never counted",
        )
        # the replacement landed on the SURVIVING node and serves
        spawned = [rid for rid in router.live_replica_ids()
                   if rid.startswith("nc:as")]
        assert spawned, router.live_replica_ids()
        outs = [router.submit([50 + i], max_new_tokens=3).result(30.0)
                for i in range(4)]
        assert outs == [stub_answer([50 + i], 3) for i in range(4)]
        assert reprovision_secs < 60.0, reprovision_secs
    finally:
        router.shutdown()
        kill(proc_c, proc_d)
