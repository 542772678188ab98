"""The fleet observability plane end to end (docs/observability.md
"fleet-wide view") over a REAL 2-node TCP stub fleet. Pins, in order:

  1. Fleet-aggregated scrape: one ``GET /metrics`` off the door
     answers with the router's own series AND a REMOTE node's
     ``infer/*`` engine series carrying ``{node, replica}`` labels
     — the hub's metrics_snapshot control op crossed the wire.
  2. Cross-host traces: a remote replica's sampled ``node.submit``
     spans and a forced flight dump land in the ROUTER-side
     telemetry directory as one loadable Chrome trace (remote pids
     present, the fleet flight file carries both nodes' rings).
  3. Burn-rate + alerting: under injected SLO-violating load the
     ``/statz`` fast burn window moves, the ``slo_burn`` alert
     fires its rising edge (fleet/alerts_slo_burn counter) and the
     hub.alert instant event is in the flight ring.
  4. Zero overhead when disabled: a hub-less fleet runs no hub
     threads and the door 404s /metrics, /statz and /dashboard.
"""

import http.client
import json
import os
import re
import threading
import time

import pytest

import deepspeed_tpu
from _common import kill, launch_node, telemetry_block, wait_for
from deepspeed_tpu.serving import HTTPDoor
from deepspeed_tpu.telemetry.tracing import load_chrome_trace


def _get(host, port, path):
    conn = http.client.HTTPConnection(host, port, timeout=30.0)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


@pytest.fixture(scope="module")
def nodes():
    """A 2-node stub fleet with node-side tracing armed."""
    node_cfg = {
        "telemetry": {"tracing": {"enabled": True, "sample_rate": 1.0}},
    }
    stub_spec = {"stub": {"delay_secs": 0.02}}
    procs = []
    try:
        table = {}
        for name in ("obs-a", "obs-b"):
            proc, addr = launch_node(name, stub_spec, config=node_cfg)
            procs.append(proc)
            table[name] = {"address": f"{addr[0]}:{addr[1]}",
                           "replicas": ["r0"]}
        yield table
    finally:
        kill(*procs)


def test_hub_scrapes_alerts_and_brings_remote_traces_home(nodes, tmp_path):
    router = deepspeed_tpu.init_fleet(
        nodes=nodes,
        config={
            "serving": {
                "backend": "socket",
                # an unmeetable TTFT target: every completion tick is
                # an SLO violation, so the burn windows saturate fast
                "slo": {"ttft_p99_ms": 0.001, "eval_window_secs": 2.0},
                # min == max: SLO accounting runs every tick but the
                # fleet never actually scales under the injected burn
                "autoscale": {"enabled": True, "min_replicas": 2,
                              "max_replicas": 2, "interval_secs": 0.05,
                              "cooldown_secs": 3600.0},
                "hub": {"enabled": True, "interval_secs": 0.1,
                        "drain_interval_secs": 3600.0,
                        "alerts": {"fast_window_secs": 1.0,
                                   "slow_window_secs": 2.0}},
            },
            "telemetry": telemetry_block(
                tmp_path, "obs",
                tracing={"enabled": True, "sample_rate": 1.0},
            ),
        },
    )
    door = HTTPDoor(router)
    host, port = door.start()
    try:
        # ---- SLO-violating load until the alert's rising edge ---------
        t0 = time.monotonic()
        alerts = router.metrics.counter("fleet/alerts_slo_burn")
        while alerts.value < 1 and time.monotonic() - t0 < 60.0:
            reqs = [router.submit([7 + i], max_new_tokens=2)
                    for i in range(4)]
            for r in reqs:
                r.result(30.0)
        assert alerts.value >= 1, (
            "the slo_burn alert never fired under all-violating load"
        )

        # ---- pin 1: one scrape, fleet-aggregated, {node,replica} ------
        wait_for(
            lambda: router.hub.statz()["nodes_up"] == 2, 30.0,
            "the hub never scraped both nodes",
        )
        status, body = _get(host, port, "/metrics")
        assert status == 200, (status, body[:200])
        remote = [
            line for line in body.splitlines()
            if line.startswith("infer_")
            and 'node="obs-' in line and 'replica="r0"' in line
        ]
        assert remote, "no remote infer_* series on the /metrics scrape"
        assert any('node="obs-b"' in line for line in remote), (
            "the second node's engine series never aggregated"
        )
        # the router's own unlabeled series share the same scrape
        assert re.search(r"^fleet_requests_completed ", body, re.M), (
            "the router's local series are missing from /metrics"
        )

        # ---- pin 3: /statz burn window moved + alert is active --------
        status, body = _get(host, port, "/statz")
        assert status == 200
        statz = json.loads(body)
        fast = statz["windows"]["1s"]
        assert fast["slo_samples"] and fast["slo_samples"] > 0, fast
        assert fast["burn_rate"] and fast["burn_rate"] > 1.0, fast
        assert "slo_burn" in statz["alerts"]["active"], statz["alerts"]
        assert statz["fleet"]["fleet/alerts_slo_burn"] >= 1

        status, body = _get(host, port, "/dashboard")
        assert status == 200
        assert "<html" in body and "EventSource" in body

        # ---- pin 2: remote spans + fleet flight dump come home --------
        spans, dump_path = router.hub.drain_once(
            flight=True, reason="drill"
        )
        assert spans > 0, "no remote spans came home on drain_telemetry"
        assert dump_path and os.path.exists(dump_path)
        with open(dump_path) as f:
            flight = json.load(f)
        flight_names = {e["name"] for e in flight["traceEvents"]}
        assert "hub.alert" in flight_names, sorted(flight_names)
        assert "node.flight_drain" in flight_names, sorted(flight_names)
        drained_nodes = {
            e["args"].get("node") for e in flight["traceEvents"]
            if e["name"] == "node.flight_drain"
        }
        assert drained_nodes == {"obs-a", "obs-b"}, drained_nodes
    finally:
        door.shutdown()
        router.shutdown()

    # one loadable router-side Chrome trace covers the whole fleet
    trace_path = os.path.join(tmp_path, "telemetry", "obs", "trace.json")
    events = load_chrome_trace(trace_path)
    node_submits = [e for e in events if e["name"] == "node.submit"]
    assert node_submits, "no remote node.submit spans in the fleet trace"
    assert {e["args"]["node"] for e in node_submits} == {"obs-a", "obs-b"}
    assert {e["pid"] for e in node_submits} & (
        {e["pid"] for e in events if e["name"] == "fleet.request"}
    ) == set(), "remote spans carry the router's pid — not cross-host"


def test_fleet_without_a_hub_runs_no_hub_thread_and_serves_no_hub_path(nodes):
    router = deepspeed_tpu.init_fleet(nodes=nodes, config={
        "serving": {"backend": "socket"},
    })
    door = HTTPDoor(router)
    host, port = door.start()
    try:
        assert router.hub is None
        hub_threads = [t.name for t in threading.enumerate()
                       if t.name.startswith("ds-hub")]
        assert not hub_threads, hub_threads
        for path in ("/metrics", "/statz", "/dashboard"):
            status, _body = _get(host, port, path)
            assert status == 404, (path, status)
        # the fleet itself still serves
        assert len(router.submit([3], max_new_tokens=2).result(30.0)) == 2
    finally:
        door.shutdown()
        router.shutdown()
