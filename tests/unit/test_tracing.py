"""Distributed request tracing + flight recorder (telemetry/tracing.py).

Covers the ISSUE-9 acceptance surface: span/context mechanics, ring-buffer
overwrite order, sampling at 0.0/1.0, the zero-overhead-when-disabled pin,
RPC trace propagation through the worker protocol, subprocess replica
span adoption, scheduler phase spans with globally-unique request ids,
flight dumps on decode-driver crashes, histogram exemplars, and a real
in-process fleet request reconstructing end-to-end from one trace file.
"""

import json
import os
import queue
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.inference.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    RequestRejected,
)
from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel  # noqa: E402
from deepspeed_tpu.serving.replica import SubprocessReplica  # noqa: E402
from deepspeed_tpu.serving.worker import WorkerServer  # noqa: E402
from deepspeed_tpu.telemetry.exporters import (  # noqa: E402
    PrometheusTextfileExporter,
)
from deepspeed_tpu.telemetry.manager import Telemetry  # noqa: E402
from deepspeed_tpu.telemetry.registry import (  # noqa: E402
    Histogram,
    MetricsRegistry,
)
from deepspeed_tpu.telemetry.tracing import (  # noqa: E402
    NOOP_TRACER,
    NoopTracer,
    SpanTracer,
    TraceContext,
    build_tracer,
    load_chrome_trace,
    phase,
    phase_totals,
    reset_phase_totals,
)


# ---------------------------------------------------------------------------
# core span mechanics
# ---------------------------------------------------------------------------
def test_record_parents_under_context():
    t = SpanTracer(ring_events=16)
    root = t.child_of(None)
    child = t.record("child", 1.0, 2.0, ctx=root)
    assert child["trace_id"] == root.trace_id
    assert child["parent_id"] == root.span_id
    assert child["dur_ms"] == pytest.approx(1000.0)
    # explicit span_id override: how a pre-allocated container span
    # closes retroactively
    closed = t.record(
        "root", 0.5, 3.0,
        ctx=TraceContext(root.trace_id, None, root.sampled),
        span_id=root.span_id,
    )
    assert closed["span_id"] == root.span_id
    assert closed["parent_id"] is None
    assert closed["trace_id"] == child["trace_id"]


def test_span_context_manager_records_block():
    t = SpanTracer(ring_events=16)
    with phase("blk", tracer=t, a=1) as h:
        h.set_attr("b", 2)
    (span,) = t.flight_snapshot()
    assert span is h.span
    assert span["name"] == "blk"
    assert span["attrs"] == {"a": 1, "b": 2}
    assert span["dur_ms"] == pytest.approx(h.seconds * 1e3)


def test_phase_nests_by_thread_and_inherits_the_tracer():
    t = SpanTracer(ring_events=16)
    root = t.child_of(None)
    with phase("outer", root, t) as outer:
        with phase("inner") as inner:  # no ctx, no tracer: the outer's
            pass
        seen = []
        other = threading.Thread(
            target=lambda: seen.append(_run_phase("elsewhere"))
        )
        other.start()
        other.join(10)
    assert outer.span["parent_id"] == root.span_id
    assert inner.span["parent_id"] == outer.ctx.span_id
    assert inner.span["trace_id"] == root.trace_id
    # another thread has its own stack: nothing to inherit, not recorded
    assert seen == [None]
    with pytest.raises(ValueError):
        with phase("fails", tracer=t) as failed:
            raise ValueError("boom")
    assert "boom" in failed.span["attrs"]["error"]


def _run_phase(name):
    with phase(name) as p:
        pass
    return p.span


def test_wire_roundtrip():
    ctx = TraceContext("t" * 16, "s" * 16, sampled=False)
    wire = ctx.to_wire()
    json.dumps(wire)  # must be RPC-safe
    back = TraceContext.from_wire(wire)
    assert (back.trace_id, back.span_id, back.sampled) == (
        ctx.trace_id, ctx.span_id, False,
    )
    assert TraceContext.from_wire(None) is None
    assert TraceContext.from_wire(ctx) is ctx
    assert TraceContext.from_wire({"junk": 1}) is None


def test_ring_overwrite_order():
    t = SpanTracer(ring_events=4, sample_rate=0.0)
    for i in range(10):
        t.record(f"s{i}", 0.0, 1.0)
    names = [s["name"] for s in t.flight_snapshot()]
    assert names == ["s6", "s7", "s8", "s9"]  # oldest evicted, order kept


def test_sampling_zero_keeps_ring_but_exports_nothing(tmp_path):
    path = str(tmp_path / "trace.json")
    t = SpanTracer(sample_rate=0.0, ring_events=32, export_path=path)
    for i in range(5):
        t.record(f"s{i}", 0.0, 1.0)
    t.close()
    # the always-on flight recorder saw everything...
    assert len(t.flight_snapshot()) == 5
    # ...but nothing was sampled for export: no trace file at all
    assert not os.path.exists(path)


def test_sampling_one_exports_everything(tmp_path):
    path = str(tmp_path / "trace.json")
    t = SpanTracer(sample_rate=1.0, ring_events=32, export_path=path)
    for i in range(5):
        t.record(f"s{i}", float(i), float(i) + 1.0)
    t.close()
    events = load_chrome_trace(path)
    assert [e["name"] for e in events] == [f"s{i}" for i in range(5)]
    # Perfetto-loadable complete events with the ids in args
    assert all(e["ph"] == "X" and e["args"]["trace_id"] for e in events)


def test_flight_dump_writes_complete_chrome_trace(tmp_path):
    t = SpanTracer(ring_events=8, dump_dir=str(tmp_path))
    ctx = t.child_of(None)
    t.record("a", 0.0, 1.0, ctx=ctx)
    t.event("boom", attrs={"reason": "test"}, ctx=ctx)
    path = t.dump_flight("unit_test", extra={"k": "v"})
    payload = json.load(open(path))
    names = [e["name"] for e in payload["traceEvents"]]
    assert names == ["a", "boom"]
    assert payload["metadata"]["reason"] == "unit_test"
    assert payload["metadata"]["k"] == "v"
    assert "suppressed_errors" in payload["metadata"]
    # a second dump gets its own file
    assert t.dump_flight("unit_test") != path


def test_ingest_adopts_foreign_pids_only():
    t = SpanTracer(ring_events=8)
    mine = t.record("local", 0.0, 1.0)
    foreign = dict(mine, pid=mine["pid"] + 1, name="remote")
    assert t.ingest([mine, foreign, "junk", None]) == 1
    names = [s["name"] for s in t.flight_snapshot()]
    assert names == ["local", "remote"]


# ---------------------------------------------------------------------------
# the zero-overhead-when-disabled pin
# ---------------------------------------------------------------------------
def test_noop_tracer_is_zero_overhead_passthrough():
    assert NOOP_TRACER.enabled is False
    # a phase under the no-op tracer records nothing and allocates no
    # trace context; it still times the block
    with phase("anything", tracer=NOOP_TRACER) as h:
        h.set_attr("ignored", 1)
    assert h.span is None and h.ctx is None and h.seconds >= 0.0
    assert NOOP_TRACER.record("x", 0.0, 1.0) is None
    assert NOOP_TRACER.child_of(None) is None
    assert NOOP_TRACER.dump_flight("nope") is None
    assert NOOP_TRACER.flight_snapshot() == []


def test_disabled_config_builds_the_noop_singleton(tmp_path):
    cfg = deepspeed_tpu.DeepSpeedConfig(
        None, param_dict={"train_batch_size": 1}, world_size=1
    )
    assert build_tracer(cfg) is NOOP_TRACER
    # a disabled Telemetry facade carries the same singleton
    assert Telemetry(enabled=False).tracer is NOOP_TRACER


def test_build_tracer_from_armed_config(tmp_path):
    cfg = deepspeed_tpu.DeepSpeedConfig(
        None,
        param_dict={
            "train_batch_size": 1,
            "telemetry": {
                "enabled": True,
                "output_path": str(tmp_path),
                "tracing": {"enabled": True, "sample_rate": 0.5,
                            "ring_events": 99},
            },
        },
        world_size=1,
    )
    t = build_tracer(cfg)
    assert isinstance(t, SpanTracer)
    assert t.sample_rate == 0.5 and t.ring_events == 99
    assert t.export_path.endswith("trace.json")
    t.close()


# ---------------------------------------------------------------------------
# histogram exemplars: the metric -> trace link
# ---------------------------------------------------------------------------
def test_histogram_exemplars_record_per_bucket():
    h = Histogram("x/lat", buckets=(10.0, 100.0))
    h.observe(5.0)  # untraced: no exemplar
    h.observe(50.0, trace_id="abc")
    h.observe(500.0, trace_id="inf-bucket")
    assert 0 not in h.exemplars
    assert h.exemplars[1][:2] == (50.0, "abc")
    assert h.exemplars[2][:2] == (500.0, "inf-bucket")


def test_prometheus_exporter_emits_exemplar_comment_lines(tmp_path):
    reg = MetricsRegistry()
    h = reg.histogram("infer/ttft_ms", buckets=(10.0, 100.0))
    h.observe(50.0, trace_id="deadbeef")
    path = str(tmp_path / "m.prom")
    PrometheusTextfileExporter(path).export(reg.collect(), step=1)
    text = open(path).read()
    assert (
        '# EXEMPLAR infer_ttft_ms_bucket{le="100.0"} '
        '{trace_id="deadbeef"} 50.0'
    ) in text
    # every SAMPLE line stays valid classic 0.0.4 text format: the
    # trace link rides full-line comments only (a trailing-token tail
    # would make the node-exporter textfile collector reject the file)
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert len(line.rsplit(" ", 1)) == 2, line
    assert 'infer_ttft_ms_bucket{le="10.0"} 0\n' in text


# ---------------------------------------------------------------------------
# scheduler integration: phase spans, unique request ids, crash dump
# ---------------------------------------------------------------------------
class _StubEngine:
    """The minimal engine surface the scheduler drives."""

    prefill_len = 16

    def __init__(self, crash_on_decode=False):
        self.crash_on_decode = crash_on_decode

    def prefill_request(self, slot, prompt_tokens, temperature):
        return 100 + slot

    def prefill_trace_attrs(self, slot):
        return {"prefix_hit": False, "prompt_tokens": 3}

    def decode_tokens(self, active):
        if self.crash_on_decode:
            raise RuntimeError("injected decode crash")
        return [7 for _ in active]


def _scheduler(engine, tracer=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("queue_depth", 8)
    kw.setdefault("queue_timeout", 0.1)
    kw.setdefault("eos_token_id", None)
    kw.setdefault("temperature", 0.0)
    return ContinuousBatchingScheduler(
        engine, registry=MetricsRegistry(), tracer=tracer, **kw
    )


def test_scheduler_phase_spans_and_exemplars():
    tracer = SpanTracer(ring_events=64)
    sched = _scheduler(_StubEngine(), tracer=tracer)
    sched.set_id_prefix("r7")
    req = sched.submit([1, 2, 3], max_new_tokens=2)
    assert req.request_id.startswith("rr7-")
    sched.run_until_idle()
    assert req.result(1.0)
    names = {s["name"] for s in req.trace_spans}
    assert {"sched.queue", "sched.prefill", "sched.request"} <= names
    by_name = {s["name"]: s for s in req.trace_spans}
    # one connected trace: phases parent to the request's container span
    assert by_name["sched.queue"]["parent_id"] == req.trace_ctx.span_id
    assert by_name["sched.prefill"]["parent_id"] == req.trace_ctx.span_id
    assert by_name["sched.request"]["span_id"] == req.trace_ctx.span_id
    assert len({s["trace_id"] for s in req.trace_spans}) == 1
    assert by_name["sched.prefill"]["attrs"]["prefix_hit"] is False
    assert by_name["sched.request"]["attrs"]["request_id"] == req.request_id
    assert by_name["sched.request"]["attrs"]["finish_reason"] == (
        "max_new_tokens"
    )
    # decode-step batch spans landed in the ring under the driver trace
    ring_names = [s["name"] for s in tracer.flight_snapshot()]
    assert "sched.decode_step" in ring_names
    # TTFT exemplar links the histogram bucket to this trace
    ttft = sched._registry.histogram("infer/ttft_ms")
    assert any(
        e[1] == req.trace_ctx.trace_id for e in ttft.exemplars.values()
    )


def test_scheduler_joins_caller_trace_context():
    tracer = SpanTracer(ring_events=64)
    sched = _scheduler(_StubEngine(), tracer=tracer)
    parent = tracer.child_of(None)
    req = sched.submit(
        [1, 2, 3], max_new_tokens=1, trace_ctx=parent.to_wire()
    )
    sched.run_until_idle()
    req.result(1.0)
    assert req.trace_ctx.trace_id == parent.trace_id
    by_name = {s["name"]: s for s in req.trace_spans}
    # the request's container span parents to the caller's span
    assert by_name["sched.request"]["parent_id"] == parent.span_id


def test_scheduler_disabled_tracing_is_inert():
    sched = _scheduler(_StubEngine())  # no tracer -> NOOP passthrough
    assert isinstance(sched._tracer, NoopTracer)
    req = sched.submit([1, 2, 3], max_new_tokens=1)
    sched.run_until_idle()
    req.result(1.0)
    assert req.trace_ctx is None
    assert req.trace_spans == []


def test_request_ids_globally_unique_across_instances():
    a = _scheduler(_StubEngine())
    b = _scheduler(_StubEngine())  # same replica id, e.g. post-restart
    a.set_id_prefix("0")
    b.set_id_prefix("0")
    ids = set()
    for sched in (a, b):
        for _ in range(3):
            ids.add(sched.submit([1], max_new_tokens=1).request_id)
        sched.run_until_idle()
    assert len(ids) == 6  # the per-instance token keeps restarts distinct
    assert all(i.startswith("r0-") for i in ids)


def test_decode_crash_dumps_flight_recorder(tmp_path):
    tracer = SpanTracer(ring_events=64, dump_dir=str(tmp_path))
    sched = _scheduler(
        _StubEngine(crash_on_decode=True), tracer=tracer,
        driver_restart_budget=0,
    )
    sched.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(RuntimeError):
        sched.run_until_idle()
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
    assert len(dumps) == 1
    payload = json.load(open(tmp_path / dumps[0]))
    assert payload["metadata"]["reason"] == "decode_driver_crash"
    # the ring carried the request's phase spans into the dump
    assert any(
        e["name"] == "sched.prefill" for e in payload["traceEvents"]
    )


# ---------------------------------------------------------------------------
# worker RPC propagation (in-process protocol, no spawn)
# ---------------------------------------------------------------------------
class _ChanIn:
    def __init__(self):
        self._q = queue.Queue()

    def send(self, line):
        self._q.put(line + "\n")

    def __iter__(self):
        while True:
            line = self._q.get()
            if line is None:
                return
            yield line


class _ChanOut:
    def __init__(self):
        self.lines = []
        self._cond = threading.Condition()

    def write(self, text):
        with self._cond:
            self.lines.append(text.strip())
            self._cond.notify_all()

    def flush(self):
        pass

    def wait_for(self, predicate, timeout=5.0):
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                for raw in self.lines:
                    msg = json.loads(raw)
                    if predicate(msg):
                        return msg
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"no matching line in {self.lines}")
                self._cond.wait(remaining)


class _TracedHandle:
    def __init__(self, spans):
        self.tokens = [1, 2]
        self.finish_reason = "max_new_tokens"
        self.first_token_at = time.monotonic()
        self.done = True
        self.trace_spans = spans


class _TracedWorkerEngine:
    """Records the kwargs the worker hands to submit (the trace_ctx wire
    dict must survive the RPC) and hands back pre-traced requests."""

    def __init__(self):
        self.scheduler = self
        self.submit_kwargs = None
        self.replica_prefix = None

    def serve_forever(self):
        pass

    def set_id_prefix(self, replica_id):
        self.replica_prefix = replica_id

    def drain(self):
        pass

    def close(self):
        pass

    def submit(self, prompt, max_new_tokens=32, **kwargs):
        self.submit_kwargs = dict(kwargs)
        ctx = kwargs.get("trace_ctx") or {}
        spans = [{
            "name": "sched.request", "trace_id": ctx.get("trace_id"),
            "span_id": "w" * 16, "parent_id": ctx.get("span_id"),
            "ts": time.time(), "dur_ms": 1.0, "pid": os.getpid() + 1,
            "tid": 0, "attrs": {}, "sampled": True,
        }]
        return _TracedHandle(spans)


def test_worker_rpc_carries_trace_context_and_returns_spans():
    stdin, stdout = _ChanIn(), _ChanOut()
    engine = _TracedWorkerEngine()
    server = WorkerServer(stdin, stdout, lambda spec: engine,
                          poll_interval=0.001)
    thread = threading.Thread(target=server.run, daemon=True)
    thread.start()
    stdin.send(json.dumps({
        "op": "init", "spec": {"replica_id": "3"},
    }))
    stdout.wait_for(lambda m: m.get("event") == "ready")
    # the init spec's replica id reached the scheduler's id prefix
    assert engine.replica_prefix == "3"
    wire = {"trace_id": "t" * 16, "span_id": "p" * 16, "sampled": True}
    stdin.send(json.dumps({
        "op": "submit", "id": 1, "prompt": [5, 6],
        "max_new_tokens": 2, "kwargs": {"trace_ctx": wire},
    }))
    stdout.wait_for(
        lambda m: m.get("event") == "reply" and m.get("id") == 1
    )
    # the wire dict crossed the protocol untouched
    assert engine.submit_kwargs["trace_ctx"] == wire
    fin = stdout.wait_for(
        lambda m: m.get("event") == "finished" and m.get("id") == 1
    )
    # ...and the worker shipped its spans home with the answer,
    # parented to the router's wire context
    assert fin["spans"][0]["trace_id"] == wire["trace_id"]
    assert fin["spans"][0]["parent_id"] == wire["span_id"]
    stdin.send(json.dumps({"op": "shutdown"}))
    thread.join(5.0)
    assert not thread.is_alive()


def test_subprocess_replica_adopts_finished_spans():
    replica = SubprocessReplica("0", {})
    from deepspeed_tpu.serving.replica import RemoteRequest

    req = RemoteRequest(1, [1, 2], 4)
    replica._outstanding[1] = req
    spans = [{"name": "sched.request", "pid": os.getpid() + 1,
              "sampled": True}]
    replica._dispatch({
        "event": "finished", "id": 1, "tokens": [9],
        "reason": "max_new_tokens", "spans": spans,
    })
    assert req.done and req.trace_spans == spans


# ---------------------------------------------------------------------------
# end-to-end: one fleet request -> one connected trace in one file
# ---------------------------------------------------------------------------
VOCAB = 96


def _small_engine_factory():
    cfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        dropout=0.0, use_flash=False,
    )
    model = GPT2LMHeadModel(cfg)
    ids0 = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (1, 8)), jnp.int32
    )
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        ids0, ids0,
    )["params"]

    def build():
        return deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": {
                "max_batch_slots": 2, "max_seq_len": 48,
                "prefill_len": 16, "sampling": {"greedy": True},
            }},
        )

    return build


def test_fleet_request_trace_connects_end_to_end(tmp_path):
    router = deepspeed_tpu.init_fleet(
        engine_factory=_small_engine_factory(),
        config={
            "serving": {"replicas": 1, "placement": "least_loaded"},
            "telemetry": {
                "enabled": True,
                "output_path": str(tmp_path),
                "job_name": "trace_e2e",
                "watchdog": {"enabled": False},
                "tracing": {"enabled": True, "sample_rate": 1.0},
            },
        },
    )
    try:
        fr = router.submit([3, 1, 4, 1, 5], max_new_tokens=4)
        assert len(fr.result(30.0)) == 4
        deadline = time.monotonic() + 5.0
        while router.outstanding_count and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        router.shutdown()
    events = load_chrome_trace(
        str(tmp_path / "trace_e2e" / "trace.json")
    )
    spans = {e["name"]: e["args"] for e in events}
    required = {"fleet.request", "router.admission", "router.place",
                "sched.request", "sched.queue", "sched.prefill"}
    assert required <= set(spans), sorted(spans)
    # ONE trace id end to end, router door to finish-reason
    tids = {e["args"]["trace_id"] for e in events
            if e["name"] in required}
    assert len(tids) == 1
    root = spans["fleet.request"]
    assert root["parent_id"] is None
    assert root["finish_reason"] == "max_new_tokens"
    # parent links reconstruct the chain: admission/place under the
    # root, scheduler phases under the replica's request span
    assert spans["router.admission"]["parent_id"] == root["span_id"]
    assert spans["router.place"]["parent_id"] == root["span_id"]
    assert spans["sched.request"]["parent_id"] == root["span_id"]
    assert spans["sched.queue"]["parent_id"] == (
        spans["sched.request"]["span_id"]
    )
    assert spans["sched.prefill"]["parent_id"] == (
        spans["sched.request"]["span_id"]
    )
    # replica-prefixed request id rides the trace as the root attr
    assert str(spans["sched.request"]["request_id"]).startswith("r0-")


def test_fleet_tracing_disabled_writes_no_trace_files(tmp_path):
    router = deepspeed_tpu.init_fleet(
        engine_factory=_small_engine_factory(),
        config={
            "serving": {"replicas": 1},
            "telemetry": {
                "enabled": True,
                "output_path": str(tmp_path),
                "job_name": "untraced",
                "watchdog": {"enabled": False},
            },
        },
    )
    try:
        assert router.tracer is NOOP_TRACER
        fr = router.submit([3, 1, 4], max_new_tokens=2)
        assert len(fr.result(30.0)) == 2
    finally:
        router.shutdown()
    leftovers = [
        f for f in os.listdir(tmp_path / "untraced")
        if "trace" in f or f.startswith("flight-")
    ]
    assert leftovers == []


# ---------------------------------------------------------------------------
# phases: one clock with the profiler, totals, off cost, collective scopes
# ---------------------------------------------------------------------------
def _toy_loss(params, batch, rng):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y[:, None]) ** 2)


def _toy_train_engine(staged, telemetry=None, accum=2):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": accum,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 1000,
        "data_pipeline": {"enabled": staged},
    }
    if telemetry:
        cfg["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_toy_loss,
        model_parameters={"w": np.ones((8, 1), np.float32),
                          "b": np.zeros((1,), np.float32)},
        config_params=cfg,
    )
    return engine


def _toy_batches(engine, n):
    rows = engine.train_micro_batch_size_per_gpu() * engine.dp_world_size
    r = np.random.default_rng(0)
    return [(r.standard_normal((rows, 8)).astype(np.float32),
             r.standard_normal((rows,)).astype(np.float32))
            for _ in range(n)]


def _three_windows(engine, staged):
    batches = _toy_batches(engine, 6)
    feed = iter(batches)
    for i in range(3):
        # the unstaged path is the one a caller takes who hands a list
        float(engine.train_batch(
            feed if staged else batches[2 * i:2 * i + 2]))
    engine.close_data_pipeline()


def _toy_decode():
    sched = _scheduler(_StubEngine())
    sched.submit([1, 2, 3], max_new_tokens=3)
    sched.run_until_idle()


@pytest.fixture
def profiled(tmp_path):
    """Run a callable under a jax.profiler session (host events only) and
    get back {line: [event]} of the host plane, by thread."""

    def run(fn):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = (tmp_path / "plugins" / "profile").glob("*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(str(path))
        (host,) = [p for p in data.planes if p.name == "/host:CPU"]
        out = []
        for line in host.lines:
            events = [e for e in line.events
                      if e.name.startswith(("train.", "stage.", "sched."))]
            if events:
                out.append(events)
        return out

    return run


def _inside(outer, events, name):
    return [e for e in events if e.name == name
            and e.start_ns >= outer.start_ns
            and e.start_ns + e.duration_ns
            <= outer.start_ns + outer.duration_ns]


@pytest.mark.parametrize("staged", [True, False])
def test_training_phases_reach_the_profiler_with_telemetry_off(
        profiled, staged):
    engine = _toy_train_engine(staged)
    assert engine.telemetry.enabled is False
    float(engine.train_batch(iter(_toy_batches(engine, 2))))  # compile
    engine.close_data_pipeline()
    lines = profiled(lambda: _three_windows(engine, staged))
    (caller,) = [ev for ev in lines
                 if any(e.name == "train.window" for e in ev)]
    windows = [e for e in caller if e.name == "train.window"]
    assert len(windows) == 3
    for w in windows:
        assert len(_inside(w, caller, "train.dispatch")) == 1
        (finish,) = _inside(w, caller, "train.finish_step")
        assert dict(w.stats).keys() >= {"window", "global_steps"}
        if staged:
            assert len(_inside(w, caller, "train.stage_wait")) == 1
        else:
            assert len(_inside(w, caller, "train.stack_and_place")) == 1
    # windows after the first settle the one before: a child of finish_step
    assert any(_inside(w, caller, "train.settle") for w in windows[1:])
    workers = [ev for ev in lines if ev is not caller]
    staged_names = {e.name for ev in workers for e in ev}
    if staged:
        assert {"train.stage_window", "stage.pull", "stage.stack",
                "stage.h2d"} <= staged_names
    else:
        assert "train.stage_window" not in staged_names


def test_scheduler_phases_reach_the_profiler_with_tracing_off(profiled):
    (driver,) = profiled(_toy_decode)
    (prefill,) = [e for e in driver if e.name == "sched.prefill"]
    stats = dict(prefill.stats)
    assert stats["prompt_tokens"] == 3 and "queue_wait_ms" in stats
    steps = [dict(e.stats) for e in driver if e.name == "sched.decode_step"]
    assert len(steps) == 2  # the first token comes from the prefill
    assert [s["active_slots"] for s in steps] == [1, 1]
    assert [s["admitted"] for s in steps] == [1, 0]


@pytest.mark.parametrize("staged", [True, False])
def test_training_phases_reach_the_tracer_with_their_parents(
        tmp_path, staged):
    engine = _toy_train_engine(staged, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "ph",
        "exporters": [], "watchdog": {"enabled": False},
        "tracing": {"enabled": True, "ring_events": 512, "export": "none"},
    })
    try:
        _three_windows(engine, staged)
        ring = engine.telemetry.tracer.flight_snapshot()
    finally:
        engine.telemetry.close()
    by_id = {s["span_id"]: s for s in ring}
    run = engine.telemetry.train_trace_ctx()

    def parents(name):
        return [by_id.get(s["parent_id"], {}).get("name", s["parent_id"])
                for s in ring if s["name"] == name]

    windows = [s for s in ring if s["name"] == "train.window"]
    assert [s["attrs"]["window"] for s in windows] == [1, 2, 3]
    assert {s["parent_id"] for s in windows} == {run.span_id}
    assert parents("train.dispatch") == ["train.window"] * 3
    assert parents("train.finish_step") == ["train.window"] * 3
    assert set(parents("train.settle")) == {"train.finish_step"}
    if staged:
        assert parents("train.stage_wait") == ["train.window"] * 3
        assert set(parents("train.stage_window")) == {run.span_id}
        assert set(parents("stage.h2d")) == {"train.stage_window"}
        tids = {s["tid"] for s in ring if s["name"] == "train.stage_window"}
        assert tids and windows[0]["tid"] not in tids
    else:
        assert parents("train.stack_and_place") == ["train.window"] * 3
    # the histogram is fed from the phase: one sample a window
    hist = engine.telemetry.registry.histogram("train/window_time_ms")
    assert hist.count == 3
    assert hist.sum == pytest.approx(
        sum(s["dur_ms"] for s in windows), rel=1e-6)


def test_decode_step_span_carries_admitted_under_the_driver_trace():
    tracer = SpanTracer(ring_events=64)
    sched = _scheduler(_StubEngine(), tracer=tracer)
    req = sched.submit([1, 2, 3], max_new_tokens=3)
    sched.run_until_idle()
    ring = tracer.flight_snapshot()
    steps = [s for s in ring if s["name"] == "sched.decode_step"]
    assert [s["attrs"]["admitted"] for s in steps] == [1, 0]
    assert {s["parent_id"] for s in steps} == {sched._driver_ctx.span_id}
    (prefill,) = [s for s in ring if s["name"] == "sched.prefill"]
    assert prefill["parent_id"] == req.trace_ctx.span_id
    assert prefill["attrs"]["queue_wait_ms"] >= 0.0
    # the retroactive spans are as they were: same names, same parents
    by_name = {s["name"]: s for s in req.trace_spans}
    assert by_name["sched.queue"]["parent_id"] == req.trace_ctx.span_id
    assert by_name["sched.queue"]["ts"] + by_name["sched.queue"][
        "dur_ms"] / 1e3 <= prefill["ts"] + 1e-3


def test_phase_totals_hold_init_and_compile_time_by_phase():
    reset_phase_totals()
    assert phase_totals() == {}
    with phase("unit.block"):
        pass
    with phase("unit.block"):
        time.sleep(0.002)
    count, total, longest = phase_totals()["unit.block"]
    assert count == 2 and total >= longest >= 0.002
    reset_phase_totals()

    engine = _toy_train_engine(staged=False, accum=3)  # a shape of its own
    float(engine.train_batch(_toy_batches(engine, 3)))
    totals = phase_totals()
    for name in ("init.place_params", "init.optimizer_state",
                 "init.build_steps", "train.window", "train.dispatch"):
        assert totals[name][0] >= 1, sorted(totals)
    # the first window compiled the fused program inside train.dispatch
    assert totals["compile.backend@train.dispatch"][0] >= 1
    assert totals["compile.trace@train.dispatch"][1] > 0.0
    assert all("@" in k for k in totals if k.startswith("compile."))
    reset_phase_totals()
    assert phase_totals() == {}


def test_phase_off_cost_is_microseconds():
    n = 10_000
    best = float("inf")
    for _ in range(3):  # the least of three: a shared CPU hiccups
        t0 = time.perf_counter()
        for _ in range(n):
            with phase("unit.cost", tracer=NOOP_TRACER, step=1):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 20e-6, f"{best * 1e6:.2f} us a phase"


def test_zero2_collectives_carry_their_producers_scope():
    """What the benchmark's scoped collective reader stands on: under
    ZeRO-2 on four devices every collective of the fused window is the
    gradients' (under window_fwd_bwd) or the update's (update_grad_norm,
    update_apply under window_optimizer_update)."""
    import re

    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import _split_window_keys

    cfg = GPT2Config(vocab_size=VOCAB, n_positions=32, n_embd=32,
                     n_layer=2, n_head=2, dropout=0.0, use_flash=False)
    model = GPT2LMHeadModel(cfg)
    ids = np.zeros((8, 16), np.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        ids, ids,
    )["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 1000,
        },
        mesh=build_mesh(devices=jax.devices()[:4]),
    )
    stacked = engine._shard_window_batch(
        engine._stack_window([(ids, ids)] * 2))
    _, keys = _split_window_keys(engine._rng, 2)
    text = engine._jit_train_window.lower(
        engine.params, engine.optimizer_state, engine.loss_scale_state,
        stacked, keys, jnp.float32(1e-3), jnp.float32(0.9),
    ).compile().as_text()
    found = {"window_fwd_bwd": 0, "update_grad_norm": 0, "update_apply": 0}
    for line in text.splitlines():
        if not re.search(r" (all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start)?\(", line):
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert op_name, f"a collective without metadata: {line[:200]}"
        path = op_name.group(1)
        if "/window_fwd_bwd/" in path:
            found["window_fwd_bwd"] += 1
        else:
            scope = re.search(
                r"/window_optimizer_update/(update_grad_norm|update_apply)/",
                path)
            assert scope, f"a collective outside the two scopes: {path}"
            found[scope.group(1)] += 1
    assert all(found.values()), found
