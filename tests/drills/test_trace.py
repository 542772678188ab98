"""The distributed request-tracing acceptance slice
(docs/observability.md "Request tracing & flight recorder") — ONE fleet
request served through a SubprocessReplica with a prefix-cache HIT and a
LoRA adapter must yield ONE connected trace in ONE file, router door to
finish-reason.

The worker runs a paged+prefix-cache multi-LoRA engine in its own
process with tracing armed; its per-request spans ship back over the
newline-JSON RPC and the router's tracer stitches them under the
fleet.request root. Asserts: every phase span present, one trace_id
end to end, parent links reconstruct the chain across TWO pids, the
second templated request's prefill span says prefix_hit with the
adapter name, and the trace file is Perfetto-loadable JSON."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from _common import telemetry_block, toy_gpt2
from deepspeed_tpu.telemetry.tracing import load_chrome_trace


def test_one_fleet_request_is_one_connected_trace_across_two_pids(tmp_path):
    world = jax.device_count()
    model_kw = dict(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        dropout=0.0, use_flash=False,
    )
    rng = np.random.default_rng(0)
    _cfg, model, params = toy_gpt2(rng, **model_kw)

    # ---- 1. a tenant adapter checkpoint (the only adapter form that
    # crosses the worker's process boundary is load_dir) ---------------
    adapter_ckpt = str(tmp_path / "tenant_ckpt")
    eng_t, _o, _d, _s = deepspeed_tpu.initialize(
        model=model,
        model_parameters=jax.tree_util.tree_map(np.asarray, params),
        config_params={
            "train_batch_size": 4 * world,
            "optimizer": {"type": "adam", "params": {"lr": 0.1}},
            "adapters": {"enabled": True, "rank": 1},
        },
    )
    tb = jnp.full((4 * world, 16), 7, jnp.int32)
    eng_t.train_batch([(tb, tb)])
    assert eng_t.save_checkpoint(adapter_ckpt, tag="tuned")

    # ---- 2. a 1-replica SUBPROCESS fleet, tracing armed on BOTH sides -
    worker_spec = {
        "model": model_kw,
        "init_seed": 0,
        "config": {
            "inference": {
                "max_batch_slots": 2, "max_seq_len": 64,
                "prefill_len": 48, "sampling": {"greedy": True},
                "kv_block_size": 16,
            },
            "adapters": {"enabled": True, "rank": 1, "pool_slots": 2},
            # the worker keeps no file of its own ("none"): its
            # sampled spans ship home over the RPC instead
            "telemetry": telemetry_block(
                tmp_path / "worker", "trace_worker",
                tracing={"enabled": True, "export": "none"},
            ),
        },
    }
    router = deepspeed_tpu.init_fleet(
        worker_spec=worker_spec,
        config={
            "serving": {"replicas": 1, "backend": "subprocess"},
            "telemetry": telemetry_block(
                tmp_path, "trace",
                tracing={"enabled": True, "sample_rate": 1.0},
            ),
        },
    )
    try:
        router.load_adapter("tenant-a", load_dir=adapter_ckpt)

        # ---- 3. two templated tenant requests: cold, then a prefix HIT
        template = [int(t) for t in rng.integers(0, 128, 32)]  # 2 full pages
        r1 = router.submit(template + [5, 6, 7, 8], adapter="tenant-a",
                           max_new_tokens=4)
        assert len(r1.result(120.0)) == 4
        r2 = router.submit(template + [9, 10, 11, 12], adapter="tenant-a",
                           max_new_tokens=4)
        assert len(r2.result(120.0)) == 4
        deadline = time.time() + 10.0
        while router.outstanding_count and time.time() < deadline:
            time.sleep(0.01)
        assert router.outstanding_count == 0, "sweep never completed"
    finally:
        router.shutdown()

    # ---- 4. ONE file reconstructs both requests end to end ------------
    trace_path = os.path.join(tmp_path, "telemetry", "trace", "trace.json")
    events = load_chrome_trace(trace_path)
    by_trace = {}
    for e in events:
        tid = e["args"].get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(e)
    roots = [e for e in events if e["name"] == "fleet.request"]
    assert len(roots) == 2, f"expected 2 fleet roots, got {len(roots)}"
    hit_traces = 0
    for root in roots:
        chain = by_trace[root["args"]["trace_id"]]
        names = {e["name"] for e in chain}
        required = {"fleet.request", "router.admission", "router.place",
                    "sched.request", "sched.queue", "sched.prefill"}
        assert required <= names, sorted(names)
        spans = {e["name"]: e for e in chain}
        # the chain crosses the process boundary: router spans carry the
        # parent pid, scheduler spans the worker's
        assert spans["fleet.request"]["pid"] != spans["sched.request"]["pid"]
        # parent links reconstruct door -> placement -> replica -> phases
        root_id = spans["fleet.request"]["args"]["span_id"]
        assert spans["fleet.request"]["args"]["parent_id"] is None
        assert spans["router.place"]["args"]["parent_id"] == root_id
        assert spans["sched.request"]["args"]["parent_id"] == root_id
        req_id = spans["sched.request"]["args"]["span_id"]
        assert spans["sched.queue"]["args"]["parent_id"] == req_id
        assert spans["sched.prefill"]["args"]["parent_id"] == req_id
        assert spans["fleet.request"]["args"]["finish_reason"] == (
            "max_new_tokens"
        )
        # replica-prefixed globally-unique request id as the root attr
        assert str(
            spans["sched.request"]["args"]["request_id"]
        ).startswith("r0-")
        prefill = spans["sched.prefill"]["args"]
        assert prefill.get("adapter") == "tenant-a", prefill
        if prefill.get("prefix_hit"):
            hit_traces += 1
    assert hit_traces == 1, (
        f"expected exactly the second templated request to hit the "
        f"prefix cache, saw {hit_traces} hit trace(s)"
    )
