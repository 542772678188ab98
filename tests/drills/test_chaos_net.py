"""The socket transport's failure envelope over REAL TCP to real
node-agent subprocesses (docs/serving.md "Networked fleet").

  A. Network chaos absorbed in place: a 2-node fleet of real GPT-2
     replicas under a seeded client-side schedule covering all four
     socket seams — one garbled frame (frame.corrupt: the node
     counts-and-drops, the lost op falls through), one peer RST
     mid-conversation (conn.reset: reconnect-with-resume re-attaches
     the session), one black-holed frame (net.partition: only the
     reply timeout notices), one send stall (conn.stall). Every
     request completes exactly once with bitwise greedy parity
     against a clean single-engine run, with ZERO re-routes burned.
  B. Node failover: one node SIGKILLed with requests in flight; the
     client's reconnect budget exhausts, the replica flips failed,
     and the router evicts + re-routes within the max_reroutes
     budget — exactly-once delivery, bitwise parity, no hangs.
"""

import time

import numpy as np

from _common import kill, launch_node, stub_answer, worker_spec
from deepspeed_tpu.resilience.faults import FaultInjector, FaultSpec
from deepspeed_tpu.serving import FleetRouter, SocketReplica
from deepspeed_tpu.serving.worker import build_engine_from_spec
from deepspeed_tpu.telemetry.registry import MetricsRegistry


def test_four_socket_seams_are_absorbed_without_a_reroute():
    spec = worker_spec()
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, 64, 6)] for _ in range(6)]

    single = build_engine_from_spec(spec)
    reference = single.generate(prompts, max_new_tokens=5)
    single.close()

    proc_a, addr_a = launch_node("na", spec)
    proc_b, addr_b = launch_node("nb", spec)
    # every client->node send on replica na:r0 traverses all four armed
    # sites (the hello is raw, uncounted); submits contribute traversals
    # but HOW MANY land on na:r0 is placement's call (a reconnect blip
    # steers traffic to nb), so the drive loop below keeps snapshot RPCs
    # flowing until the later sites reach their firing traversal
    sites = ("frame.corrupt", "conn.reset", "net.partition", "conn.stall")
    faults = FaultInjector(
        [FaultSpec("frame.corrupt", after=2, times=1, seed=0),
         FaultSpec("conn.reset", after=4, times=1, seed=0),
         FaultSpec("net.partition", after=6, times=1, seed=0),
         FaultSpec("conn.stall", after=8, times=1,
                   args={"duration_ms": 150}, seed=0)],
        seed=0,
    )
    reg = MetricsRegistry()
    ra = SocketReplica(
        "na:r0", addr_a, remote_name="r0", rpc_timeout=1.5,
        rpc_retries=2, rpc_backoff_secs=0.05,
        reconnect_backoff_secs=0.05, registry=reg, fault_injector=faults,
    )
    rb = SocketReplica(
        "nb:r0", addr_b, remote_name="r0", rpc_timeout=1.5, registry=reg,
    )
    # failure threshold ABOVE the armed fault count: this pins the
    # transport absorbing chaos in place (fall-through + retry +
    # reconnect), not the breaker path (test_chaos_fleet.py owns that)
    router = FleetRouter(
        [ra, rb], registry=reg, monitor_interval=0.01,
        telemetry_refresh_secs=3600.0, breaker_failure_threshold=5,
        breaker_backoff_secs=0.25,
    ).start()
    try:
        t0 = time.monotonic()
        reqs = [
            router.submit(p, tenant=f"tenant-{i % 2}", max_new_tokens=5)
            for i, p in enumerate(prompts)
        ]
        # deterministically drive the faulted seam while the fleet is
        # decoding: placement is load-aware, so the submits alone may
        # leave na:r0 short of the later sites' firing traversals —
        # snapshot RPCs are real frames over the real socket and the
        # retry/reconnect machinery absorbs whichever fault they eat
        drive_deadline = time.monotonic() + 60.0
        while (
            any(faults.injected.get(s, 0) < 1 for s in sites)
            and time.monotonic() < drive_deadline
        ):
            try:
                ra.load_snapshot()
            except Exception:
                pass  # this snapshot ate a fault; the next poll re-drives
            time.sleep(0.02)
        outs = [r.result(120.0) for r in reqs]
        window = time.monotonic() - t0
        assert outs == reference, "divergence under socket chaos"
        assert all(r.finish_reason == "max_new_tokens" for r in reqs)
        for site in sites:
            assert faults.injected.get(site) == 1, (site, faults.injected)
        snap = reg.snapshot()
        assert snap["fleet/requests_completed"] == 6, snap
        assert snap["fleet/requests_rerouted"] == 0, (
            "chaos was absorbed by re-routes instead of the transport"
        )
        assert snap["fleet/net_reconnects"] >= 1, (
            "the injected RST never exercised reconnect-with-resume"
        )
        assert window < 90.0, f"the chaos window took {window:.1f}s"
    finally:
        router.shutdown()
        kill(proc_a, proc_b)


def test_sigkilled_node_fails_over_within_the_reroute_budget():
    stub_spec = {"stub": {"delay_secs": 1.0}}
    proc_c, addr_c = launch_node("nc", stub_spec)
    proc_d, addr_d = launch_node("nd", stub_spec)
    reg = MetricsRegistry()
    rc = SocketReplica(
        "nc:r0", addr_c, remote_name="r0", rpc_timeout=1.0,
        reconnect_attempts=2, reconnect_backoff_secs=0.05, registry=reg,
    )
    rd = SocketReplica(
        "nd:r0", addr_d, remote_name="r0", rpc_timeout=1.0, registry=reg,
    )
    router = FleetRouter(
        [rc, rd], registry=reg, placement="round_robin",
        monitor_interval=0.01, telemetry_refresh_secs=3600.0,
        breaker_failure_threshold=1, breaker_backoff_secs=0.3,
    ).start()
    try:
        t0 = time.monotonic()
        # round-robin: requests 0/2 land on nc, 1/3 on nd; the stub's 1s
        # completion delay keeps nc's pair IN FLIGHT when the node dies
        reqs = [router.submit([30 + i], max_new_tokens=3)
                for i in range(4)]
        proc_c.kill()
        outs = [r.result(120.0) for r in reqs]
        failover = time.monotonic() - t0
        for i, out in enumerate(outs):
            assert out == stub_answer([30 + i], 3), (i, out)
        assert all(r.reroutes <= router.max_reroutes for r in reqs)
        assert any(r.reroutes >= 1 for r in reqs), (
            "the killed node's requests never re-routed"
        )
        snap = reg.snapshot()
        assert snap["fleet/requests_completed"] == 4, snap
        assert snap["fleet/requests_rerouted"] >= 1, snap
        assert "nc:r0" in router.evicted_ids, (
            "the dead node's replica was never evicted"
        )
        assert failover < 60.0, f"failover took {failover:.1f}s"
    finally:
        router.shutdown()
        kill(proc_c, proc_d)
