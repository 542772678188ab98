"""The whole-node failure domain end to end (docs/serving.md "Node
failure domain"). One real-TCP fleet of provisioner-launched stub
nodes, three acts:

  A. Node failover under mixed-tenant traffic: one node SIGKILLed with
     requests in flight. Every request completes exactly once
     (re-routed, never duplicated, never lost) and the dead node's
     replica is evicted.
  B. Capacity restoration: the autoscaler's REPROVISION escalates to
     the node tier — the provisioner re-launches the dead node under
     its own name and a replacement replica rejoins; traffic flows
     across the restored fleet.
  C. Stale-router drill: a deliberately "restarted" stale router
     incarnation (epoch - 1) is rejected by BOTH live nodes with the
     typed FencedOut — control dial and data-plane session alike —
     while the live router keeps serving, undisturbed.

A and B are one test (B is what follows A's kill); C needs only a
healthy two-node fleet and runs after them on the same one.
"""

import time

import pytest

from _common import stub_answer
from deepspeed_tpu.serving import (
    Autoscaler,
    FencedOut,
    FleetRouter,
    LocalSubprocessProvisioner,
    SocketNodeProvider,
    SocketReplica,
)
from deepspeed_tpu.serving.transport import NodeControlClient
from deepspeed_tpu.telemetry.registry import MetricsRegistry

EPOCH = 3


@pytest.fixture(scope="module")
def fleet():
    template = {
        "replicas": {"r0": {"stub": {"delay_secs": 0.5}}},
        "lease_secs": 10.0,
        "resume_grace_secs": 10.0,
    }
    reg = MetricsRegistry()
    prov = LocalSubprocessProvisioner(
        template, launch_timeout=60.0, epoch=EPOCH, registry=reg,
    )
    router = None
    try:
        h0 = prov.launch_node("n0")
        h1 = prov.launch_node("n1")
        nodes = {
            "n0": {"address": h0.address, "replicas": ["r0"]},
            "n1": {"address": h1.address, "replicas": ["r0"]},
        }
        provider = SocketNodeProvider(
            nodes, rpc_timeout=1.0, reconnect_attempts=2,
            reconnect_backoff_secs=0.05, registry=reg, epoch=EPOCH,
            provisioner=prov, max_replicas_per_node=1, max_nodes=2,
            node_retry_secs=5.0, spawn_timeout=60.0,
        )
        scaler = Autoscaler(
            provider, min_replicas=2, max_replicas=2, cooldown_secs=0.05,
            hysteresis_secs=0.0, flap_budget=100, interval_secs=0.05,
            drain_timeout_secs=5.0,
        )
        r0 = SocketReplica(
            "n0:r0", h0.address, remote_name="r0", rpc_timeout=1.0,
            reconnect_attempts=2, reconnect_backoff_secs=0.05,
            registry=reg, epoch=EPOCH,
        )
        r1 = SocketReplica(
            "n1:r0", h1.address, remote_name="r0", rpc_timeout=1.0,
            registry=reg, epoch=EPOCH,
        )
        router = FleetRouter(
            [r0, r1], registry=reg, placement="round_robin",
            monitor_interval=0.02, telemetry_refresh_secs=3600.0,
            breaker_failure_threshold=1, breaker_backoff_secs=0.2,
            autoscaler=scaler,
        ).start()
        yield router, prov, reg, h0
    finally:
        if router is not None:
            router.shutdown()
        prov.close()


def test_sigkilled_node_fails_over_and_is_reprovisioned(fleet):
    router, prov, reg, h0 = fleet

    # ---- act A: SIGKILL one node mid-traffic --------------------------
    # round-robin: even requests land on n0, odd on n1; the stub's
    # completion delay keeps n0's share IN FLIGHT when it dies
    reqs = [
        router.submit([40 + i], tenant=f"tenant-{i % 3}", max_new_tokens=3)
        for i in range(8)
    ]
    h0.proc.kill()
    outs = [r.result(120.0) for r in reqs]
    for i, out in enumerate(outs):
        assert out == stub_answer([40 + i], 3), (i, out)
    assert all(r.finish_reason == "max_new_tokens" for r in reqs)
    snap = reg.snapshot()
    assert snap["fleet/requests_completed"] == 8, snap
    assert any(r.reroutes >= 1 for r in reqs), (
        "the killed node's in-flight requests never re-routed"
    )
    assert "n0:r0" in router.evicted_ids, (
        "the dead node's replica was never evicted"
    )

    # ---- act B: the provisioner restores whole-node capacity ----------
    deadline = time.monotonic() + 90.0
    while time.monotonic() < deadline:
        if len(router.live_replica_ids()) >= 2:
            break
        time.sleep(0.05)
    live = router.live_replica_ids()
    assert len(live) >= 2, f"capacity never restored: {live}"
    assert any(str(rid).startswith("n0:") for rid in live), (
        "the replacement replica did not rejoin on the "
        f"re-provisioned node: {live}"
    )
    assert "n0" in prov.list_nodes() and prov.list_nodes()["n0"].alive
    snap = reg.snapshot()
    assert snap["fleet/nodes_provisioned"] >= 3, snap  # n0, n1, n0'
    reqs2 = [
        router.submit([80 + i], tenant=f"tenant-{i % 3}", max_new_tokens=2)
        for i in range(4)
    ]
    outs2 = [r.result(60.0) for r in reqs2]
    for i, out in enumerate(outs2):
        assert out == stub_answer([80 + i], 2), (i, out)
    assert reg.snapshot()["fleet/requests_completed"] == 12


def test_stale_router_is_fenced_on_both_planes(fleet):
    router, prov, reg, _h0 = fleet
    completed = reg.snapshot()["fleet/requests_completed"]

    # ---- act C: the stale-router drill --------------------------------
    # a "restarted" stale incarnation presents epoch - 1 to both
    # live nodes: control dial and data-plane hello alike must be
    # rejected with the typed FencedOut, and neither may retry
    live_addresses = {
        name: handle.address
        for name, handle in prov.list_nodes().items()
    }
    assert sorted(live_addresses) == ["n0", "n1"], live_addresses
    fenced_ctl = 0
    for name in sorted(live_addresses):
        try:
            NodeControlClient(
                live_addresses[name], connect_timeout=5.0,
                op_timeout=5.0, epoch=EPOCH - 1,
            ).node_info()
        except FencedOut as e:
            assert e.high_water >= EPOCH, (name, e.high_water)
            fenced_ctl += 1
    assert fenced_ctl == 2, (
        f"only {fenced_ctl}/2 nodes fenced the stale control dial"
    )
    stale = SocketReplica(
        "stale:r0", live_addresses["n1"], remote_name="r0",
        rpc_timeout=1.0, registry=MetricsRegistry(), epoch=EPOCH - 1,
    )
    try:
        stale.start()
        fenced_data = False
    except FencedOut:
        fenced_data = True
    finally:
        stale.shutdown()
    assert fenced_data, (
        "the stale data-plane session was admitted, not fenced"
    )
    # the live router rode through the drill undisturbed
    assert not router.fenced
    req = router.submit([200], max_new_tokens=2)
    assert req.result(60.0) == [201, 202]
    assert reg.snapshot()["fleet/requests_completed"] == completed + 1
