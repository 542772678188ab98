"""Distributed request tracing + always-on flight recorder.

The Dapper-style span layer under the serving fleet and the training
engine (docs/observability.md "Request tracing & flight recorder"): a
lock-cheap :class:`SpanTracer` records (trace_id, span_id, parent) spans
with monotonic t0/t1 and free-form attrs, propagates context through the
whole serving path — router door -> replica submit -> scheduler
queue/defer -> prefill -> per-decode-step batch spans -> finish-reason —
including over the subprocess worker's newline-JSON RPC (a
:class:`TraceContext` serializes to a plain dict, so it rides the
existing ``kwargs`` channel untouched), and exports Chrome
trace-event / Perfetto-loadable JSON next to the jsonl/prometheus sinks.

Two consumers with different retention:

- **export buffer**: finished spans whose trace was SAMPLED
  (``sample_rate``) flush to ``trace.json`` in the telemetry output
  directory — the file Perfetto opens. Volume control for production.
- **flight recorder**: a bounded ring (``ring_events``) that records
  EVERY finished span and instant event regardless of sampling — always
  on while tracing is enabled, dumped as a complete Chrome trace on
  watchdog stall reports, supervisor escalations, decode-driver crashes,
  and replica evictions, i.e. exactly when someone starts debugging.

Block-shaped host phases (a training window, its dispatch, a prefill, a
decode step) are marked with :class:`phase`, the ONE way to mark them:
it always enters a ``jax.profiler.TraceAnnotation``, so any live
profiler session shows the phase on the ``/host:CPU`` plane on the clock
of the device planes, always adds to a process-wide table of totals by
name (:func:`phase_totals`), and records into the tracer when one is
enabled. ``record()`` stays for spans that cross threads or are only
known afterwards (``sched.request``, ``sched.queue``, ``fleet.request``,
``router.*``).

Tracing disabled is a near-zero-overhead passthrough: every integration
point holds :data:`NOOP_TRACER`, whose ``record()`` is a bare ``return
None`` — the hot paths pay a single attribute check
(``tracer.enabled``), and a ``phase`` costs one inactive TraceMe, two
clock reads and a dict update, pinned by tests/unit/test_tracing.py.

Timestamps: callers pass ``time.monotonic()`` instants (what the
schedulers already collect); each tracer converts to wall-clock at
record time via a per-process offset, so spans from a router process and
its worker subprocesses land on one comparable timeline in a single
Perfetto view.
"""

import collections
import json
import os
import random
import threading
import time
import uuid

from jax.profiler import TraceAnnotation

from ..utils.logging import logger
from .registry import count_suppressed, suppressed_errors_snapshot


def _new_id():
    """16-hex random id (trace and span ids share the generator)."""
    return uuid.uuid4().hex[:16]


class TraceContext:
    """Propagatable trace position: ``trace_id`` names the request's
    whole tree, ``span_id`` the node children parent to, ``sampled``
    whether the export buffer wants the tree (the flight-recorder ring
    takes it either way). ``to_wire()``/``from_wire()`` round-trip a
    plain JSON-safe dict — the RPC propagation format."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id, span_id=None, sampled=True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)

    def to_wire(self):
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "sampled": self.sampled,
        }

    @classmethod
    def from_wire(cls, obj):
        """None / TraceContext / wire dict -> TraceContext or None."""
        if obj is None or isinstance(obj, cls):
            return obj
        if isinstance(obj, dict) and obj.get("trace_id"):
            return cls(
                obj["trace_id"], obj.get("span_id"),
                obj.get("sampled", True),
            )
        return None

    def __repr__(self):
        return (
            f"TraceContext({self.trace_id}, {self.span_id}, "
            f"sampled={self.sampled})"
        )


class NoopTracer:
    """Disabled tracing: every method is a constant-time no-op and the
    integration points see ``enabled == False`` before doing any work.
    One process-wide instance (:data:`NOOP_TRACER`)."""

    enabled = False

    def record(self, name, t0, t1, ctx=None, attrs=None, span_id=None):
        return None

    def child_of(self, ctx):
        return None

    def event(self, name, attrs=None):
        return None

    def ingest(self, spans):
        return 0

    def drain_sampled(self):
        return []

    def flight_snapshot(self):
        return []

    def dump_flight(self, reason, extra=None):
        return None

    def flush(self):
        pass

    def close(self):
        pass


NOOP_TRACER = NoopTracer()


# Phase totals: name -> [count, total seconds, longest]. Process-wide,
# because set-up is over before any trace starts and its reader (the
# benchmark's ``phase_total``) asks after the fact; the compile listener
# (registry.py) adds its events here under ``compile.<kind>@<phase>``.
_totals = {}
_totals_lock = threading.Lock()
_open = threading.local()  # .stack: phases open on this thread


def add_phase_time(name, seconds):
    with _totals_lock:
        row = _totals.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += seconds
        row[2] = max(row[2], seconds)


def phase_totals():
    """{name: (count, total seconds, longest)} since the last reset."""
    with _totals_lock:
        return {name: tuple(row) for name, row in _totals.items()}


def reset_phase_totals():
    with _totals_lock:
        _totals.clear()


def add_compile_time(kind, seconds):
    """Charge one ``jax.monitoring`` compile event to the innermost phase
    open on the thread where it fired: ``<kind>@<phase>``, ``@-`` outside
    any. A jitted function traced inside another fires its own event
    inside the outer's and before it, so an outer event gives up what its
    inner ones already counted: every second is counted once."""
    stack = getattr(_open, "stack", None)
    if kind == "compile.trace":
        now = time.monotonic()
        inner = _open.__dict__.setdefault("traced", [])
        nested = 0.0
        while inner and inner[-1][0] >= now - seconds:
            nested += inner.pop()[1]
        inner.append((now, seconds))
        seconds = max(seconds - nested, 0.0)
    add_phase_time(f"{kind}@{stack[-1].name if stack else '-'}", seconds)


class phase:
    """Mark one block-shaped host phase: ``with phase("train.dispatch"):``.

    ``ctx``/``tracer`` name the parent context and the tracer to record
    into; a phase opened inside another on the same thread inherits both,
    so only the outermost call site of a thread passes them. ``seconds``
    holds the duration after exit (what the histograms are fed from) and
    ``span`` the recorded span, if the tracer was enabled."""

    __slots__ = ("name", "attrs", "seconds", "span", "ctx", "_tracer",
                 "_parent", "_annotation", "_t0")

    def __init__(self, name, ctx=None, tracer=None, **attrs):
        self.name = name
        self.attrs = attrs
        self.seconds = self.span = self.ctx = None
        self._tracer = tracer
        self._parent = ctx
        self._annotation = TraceAnnotation(name, **attrs)

    def set_attr(self, key, value):
        """An attr only known mid-block (a prefill's prefix hit)."""
        self.attrs[key] = value
        self._annotation.set_metadata(**{key: value})

    def __enter__(self):
        stack = _open.__dict__.setdefault("stack", [])
        outer = stack[-1] if stack else None
        if self._tracer is None:
            self._tracer = outer._tracer if outer else NOOP_TRACER
        if self._tracer.enabled:
            parent = TraceContext.from_wire(self._parent)
            if parent is None and outer is not None:
                parent = outer.ctx
            self._parent = parent
            self.ctx = self._tracer.child_of(parent)
        stack.append(self)
        self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic()
        self._annotation.__exit__(exc_type, exc, tb)
        _open.stack.pop()
        self.seconds = t1 - self._t0
        add_phase_time(self.name, self.seconds)
        if self.ctx is not None:
            if exc_type is not None:
                self.attrs.setdefault("error", repr(exc))
            self.span = self._tracer.record(
                self.name, self._t0, t1,
                ctx=self._parent
                or TraceContext(self.ctx.trace_id, None, self.ctx.sampled),
                span_id=self.ctx.span_id, attrs=self.attrs,
            )
        return False


class SpanTracer:
    """The enabled tracer. Thread-safety: span records happen on router
    submit threads, scheduler driver threads, staging workers, and the
    watchdog's polling thread — the ring is a deque (atomic appends) and
    the export buffer takes one short lock per record; no span ever
    blocks on I/O except at explicit flush boundaries."""

    enabled = True

    def __init__(self, sample_rate=1.0, ring_events=512, export_path=None,
                 dump_dir=None, flush_every=256, rng=None):
        if not 0.0 <= float(sample_rate) <= 1.0:
            raise ValueError(
                f"sample_rate must be within [0, 1], got {sample_rate!r}"
            )
        if int(ring_events) < 1:
            raise ValueError(
                f"ring_events must be >= 1, got {ring_events!r}"
            )
        self.sample_rate = float(sample_rate)
        self.ring_events = int(ring_events)
        self.export_path = export_path
        self.dump_dir = dump_dir or (
            os.path.dirname(export_path) if export_path else None
        )
        self._ring = collections.deque(maxlen=self.ring_events)
        self._pending = []
        self._lock = threading.Lock()
        self._flush_every = max(1, int(flush_every))
        self._rng = rng or random.Random()
        self._pid = os.getpid()
        # monotonic -> wall-clock translation (per process, fixed at
        # construction): wall clocks agree across a host's processes,
        # monotonic clocks do not
        self._mono_offset = time.time() - time.monotonic()
        self._file = None
        self._dump_seq = 0
        self._closed = False

    # -- context ---------------------------------------------------------
    def _sample(self):
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        return self._rng.random() < self.sample_rate

    def child_of(self, ctx):
        """A fresh context UNDER ``ctx`` (its span_id pre-allocated, so
        the owning span can be recorded retroactively once its t1 is
        known, while children parent to it in the meantime). ``ctx``
        None starts a new trace, rolling the sampling dice."""
        ctx = TraceContext.from_wire(ctx)
        if ctx is None:
            return TraceContext(_new_id(), _new_id(), self._sample())
        return TraceContext(ctx.trace_id, _new_id(), ctx.sampled)

    # -- recording -------------------------------------------------------
    def record(self, name, t0, t1, ctx=None, attrs=None, span_id=None):
        """Record one finished span: ``t0``/``t1`` are monotonic seconds,
        ``ctx`` the PARENT context (None = new root trace), ``span_id``
        overrides the generated id (how a pre-allocated request span
        closes). Returns the span dict (always ring-buffered; appended
        to the export buffer only when the trace is sampled)."""
        ctx = TraceContext.from_wire(ctx)
        if ctx is None:
            trace_id, parent_id, sampled = _new_id(), None, self._sample()
        else:
            trace_id, parent_id, sampled = (
                ctx.trace_id, ctx.span_id, ctx.sampled
            )
        span = {
            "name": name,
            "trace_id": trace_id,
            "span_id": span_id or _new_id(),
            "parent_id": parent_id,
            "ts": float(t0) + self._mono_offset,
            "dur_ms": max(float(t1) - float(t0), 0.0) * 1e3,
            "pid": self._pid,
            "tid": threading.get_ident(),
            "attrs": dict(attrs) if attrs else {},
            "sampled": bool(sampled),
        }
        self._ring.append(span)
        if sampled:
            with self._lock:
                self._pending.append(span)
                want_flush = len(self._pending) >= self._flush_every
            if want_flush:
                self.flush()
        return span

    def event(self, name, attrs=None, ctx=None):
        """Instant event (admission verdicts, rejections, crashes):
        flight-recorder ring only — events are debugging breadcrumbs,
        not latency spans, so they skip the export buffer."""
        ctx = TraceContext.from_wire(ctx)
        evt = {
            "name": name,
            "trace_id": ctx.trace_id if ctx else None,
            "span_id": None,
            "parent_id": ctx.span_id if ctx else None,
            "ts": time.time(),
            "dur_ms": None,
            "pid": self._pid,
            "tid": threading.get_ident(),
            "attrs": dict(attrs) if attrs else {},
            "sampled": False,
        }
        self._ring.append(evt)
        return evt

    def ingest(self, spans):
        """Adopt finished spans recorded in ANOTHER process (a worker's
        per-request spans shipped back over the RPC) into this tracer's
        ring + export buffer, so one trace file holds the whole fleet
        request. Same-pid spans are skipped — they were recorded here
        already (the in-process replica path shares the tracer)."""
        n = 0
        for span in spans or ():
            if not isinstance(span, dict) or span.get("pid") == self._pid:
                continue
            self._ring.append(span)
            if span.get("sampled"):
                with self._lock:
                    self._pending.append(span)
            n += 1
        return n

    def drain_sampled(self):
        """Atomically take the sampled-span batch accumulated since the
        last flush/drain — the node agent's ``drain_telemetry`` reply
        body. A tracer with no ``export_path`` (node agents export
        nothing locally; the hub ships spans home instead) would
        otherwise discard the batch at its next auto-flush, so node
        tracers pair this with a large ``flush_every``. Returns the
        spans oldest first; the flight-recorder ring is untouched."""
        with self._lock:
            batch, self._pending = self._pending, []
        return batch

    # -- flight recorder -------------------------------------------------
    def flight_snapshot(self):
        """The ring's current contents, oldest first (bounded at
        ``ring_events``; older spans were overwritten)."""
        return list(self._ring)

    def dump_flight(self, reason, extra=None):
        """Dump the ring as a complete Chrome trace file (plus the
        suppressed-errors diagnostics registry — the swallowed
        exceptions surface at exactly the moment someone is debugging a
        stall). Returns the dump path, or None when no dump directory is
        configured (the summary still logs)."""
        snapshot = self.flight_snapshot()
        suppressed = suppressed_errors_snapshot()
        path = None
        if self.dump_dir:
            try:
                os.makedirs(self.dump_dir, exist_ok=True)
                self._dump_seq += 1
                path = os.path.join(
                    self.dump_dir,
                    f"flight-{reason}-{self._dump_seq}.trace.json",
                )
                payload = {
                    "traceEvents": [_chrome_event(s) for s in snapshot],
                    "metadata": {
                        "reason": reason,
                        "suppressed_errors": suppressed,
                        **(dict(extra) if extra else {}),
                    },
                }
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(payload, f)
                os.replace(tmp, path)
            except OSError as e:
                count_suppressed("tracing.flight_dump", e)
                path = None
        logger.error(
            "FLIGHT RECORDER dump (%s): %d spans/events -> %s; "
            "suppressed errors: %s",
            reason, len(snapshot), path or "<no dump dir>",
            suppressed or "none",
        )
        return path

    # -- export ----------------------------------------------------------
    def flush(self):
        """Append the sampled spans accumulated since the last flush to
        the Chrome trace file (Perfetto's 'JSON Array Format' tolerates
        the unterminated array, so a crash mid-run still leaves a
        loadable trace; close() writes the closing bracket)."""
        with self._lock:
            if not self._pending:
                return
            batch, self._pending = self._pending, []
            if self.export_path is None or self._closed:
                return
            try:
                if self._file is None:
                    d = os.path.dirname(self.export_path)
                    if d:
                        os.makedirs(d, exist_ok=True)
                    self._file = open(self.export_path, "w")
                    self._file.write("[\n")
                for span in batch:
                    self._file.write(json.dumps(_chrome_event(span)) + ",\n")
                self._file.flush()
            except OSError as e:
                count_suppressed("tracing.flush", e)

    def close(self):
        self.flush()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._file is not None:
                try:
                    # the trailing comma before ']' is tolerated by both
                    # json5-style readers and Perfetto; emit a null
                    # sentinel so strict json.loads works too
                    self._file.write("null\n]\n")
                    self._file.close()
                except OSError as e:
                    count_suppressed("tracing.close", e)
                self._file = None


def _chrome_event(span):
    """Span/event dict -> one Chrome trace-event object. Spans map to
    'X' (complete) events; instant events (dur_ms None) map to 'i'. The
    trace/span/parent ids ride ``args`` so a Perfetto query (or the
    bench's trace walker) can reconstruct the tree."""
    args = {
        "trace_id": span.get("trace_id"),
        "span_id": span.get("span_id"),
        "parent_id": span.get("parent_id"),
    }
    args.update(span.get("attrs") or {})
    evt = {
        "name": span.get("name"),
        "cat": "span" if span.get("dur_ms") is not None else "event",
        "ph": "X" if span.get("dur_ms") is not None else "i",
        "ts": float(span.get("ts", 0.0)) * 1e6,
        "pid": span.get("pid", 0),
        "tid": span.get("tid", 0),
        "args": args,
    }
    if span.get("dur_ms") is not None:
        evt["dur"] = float(span["dur_ms"]) * 1e3
    else:
        evt["s"] = "p"  # instant-event scope: process
    return evt


def load_chrome_trace(path):
    """Parse a trace file written by :meth:`SpanTracer.flush`/``close``
    (or a flight dump): returns the list of event dicts. Tolerates the
    unterminated-array form a crashed process leaves behind."""
    with open(path) as f:
        text = f.read().strip()
    if text.startswith("{"):
        return json.loads(text)["traceEvents"]
    if not text.endswith("]"):
        text = text.rstrip().rstrip(",") + "\n]"
    return [e for e in json.loads(text) if e is not None]


def build_tracer(config, out_dir=None):
    """Construct the process's tracer from a validated DeepSpeedConfig's
    ``telemetry.tracing`` block; :data:`NOOP_TRACER` (the zero-overhead
    passthrough) unless the block — and telemetry itself — is enabled.
    ``out_dir`` defaults to the telemetry output directory, so
    ``trace.json`` and the flight dumps land beside the metric sinks."""
    if not getattr(config, "telemetry_tracing_enabled", False):
        return NOOP_TRACER
    if out_dir is None:
        base = config.telemetry_output_path or os.path.join(
            os.path.expanduser("~"), "telemetry"
        )
        out_dir = os.path.join(base, config.telemetry_job_name)
    os.makedirs(out_dir, exist_ok=True)
    export_path = None
    if config.telemetry_tracing_export == "chrome":
        export_path = os.path.join(out_dir, "trace.json")
    return SpanTracer(
        sample_rate=config.telemetry_tracing_sample_rate,
        ring_events=config.telemetry_tracing_ring_events,
        export_path=export_path,
        dump_dir=out_dir,
    )
