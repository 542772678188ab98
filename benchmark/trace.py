"""The reduction from the profiler's trace to numbers.

What the trace holds on this runtime (my chip run, PR 23,
``tests/record_trace.py``): one plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per run of a jitted program, named
``jit_<function>(<hash>)``), ``XLA Ops`` (one event per executed HLO
operation, NESTED: a ``while`` spans its body's operations) and ``Async XLA
Ops`` (one event per async start..done pair); a Pallas kernel is an ``XLA
Ops`` event named after the kernel (``flash_fwd.7``); an operation's
``jax.named_scope`` path is the ``tf_op`` stat of its metadata. The host's
``jax.profiler.TraceAnnotation`` spans are events on the ``/host:CPU``
plane, on the same clock.

Times inside are picoseconds on the trace's clock; what goes out is seconds
or milliseconds as named.
"""

import re

from . import xplane

PS = 1e-12
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTAINERS = ("while", "conditional", "call")


def base_name(name):
    """``flash_fwd.7`` -> ``flash_fwd``; ``fusion.193`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name)


def union(intervals):
    """Total length of the union of (start, end) intervals, and the merged
    intervals themselves."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def clip(events, window):
    lo, hi = window
    return [(max(e.start_ps, lo), min(e.end_ps, hi)) for e in events
            if e.end_ps > lo and e.start_ps < hi]


def gaps(merged, window):
    """The idle intervals of ``window`` left by ``merged`` busy intervals."""
    out, at = [], window[0]
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def self_times(events):
    """[(event, picoseconds not covered by an event nested in it)]."""
    order = sorted(events, key=lambda e: (e.start_ps, -e.duration_ps))
    own = [e.duration_ps for e in order]
    stack = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end_ps <= e.start_ps:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.duration_ps
        stack.append(i)
    return [(e, max(t, 0)) for e, t in zip(order, own)]


def is_collective(event):
    return base_name(event.name).replace("-start", "").replace(
        "-done", "") in COLLECTIVES


class Device:
    def __init__(self, plane):
        lines = {line.name: line.events for line in plane.lines}
        self.name = plane.name
        self.ops = lines.get("XLA Ops", [])
        self.modules = lines.get("XLA Modules", [])
        self.async_ops = lines.get("Async XLA Ops", [])


class Trace:
    def __init__(self, tracer, spans=None, path=None, whole=False):
        """``whole``: take everything the devices did as the window (a
        recording without the harness's ``bench.window`` span)."""
        path = path or (tracer.path if tracer else None)
        if path is None:
            raise SystemExit("benchmark: the traced run left no .xplane.pb")

        def want(plane, line):
            if plane.startswith("/device:TPU:"):
                return line in ("XLA Ops", "XLA Modules", "Async XLA Ops")
            return plane == "/host:CPU"

        planes = xplane.read(
            path, want,
            lambda plane, event: plane != "/host:CPU" or event.startswith("bench."))
        self.devices = [Device(p) for p in sorted(
            (p for p in planes if p.name.startswith("/device:TPU:")),
            key=lambda p: int(p.name.rsplit(":", 1)[1]))]
        self.host = [e for p in planes if p.name == "/host:CPU"
                     for line in p.lines for e in line.events]
        if not self.devices:
            raise SystemExit("benchmark: no device plane in the trace")
        marks = [e for e in self.host if e.name == "bench.window"]
        if marks:
            self.window = (marks[0].start_ps, marks[0].end_ps)
        elif whole:
            ops = [e for d in self.devices for e in d.ops + d.modules]
            self.window = (min(e.start_ps for e in ops),
                           max(e.end_ps for e in ops))
        else:
            raise SystemExit("benchmark: no bench.window span in the trace")
        self.host = [e for e in self.host if e.name != "bench.window"]

    # -- whole-device numbers ------------------------------------------------
    def busy(self, device):
        return union(clip(device.ops, self.window))

    def busy_and_window(self):
        busy = [self.busy(d)[0] for d in self.devices]
        return {"busy_s": PS * sum(busy) / len(busy),
                "window_s": PS * (self.window[1] - self.window[0])}

    def idle_share(self, device=0):
        busy, _ = self.busy(self.devices[device])
        return 1.0 - busy / (self.window[1] - self.window[0])

    # -- runs of a program ---------------------------------------------------
    def runs(self, module, device=0):
        """The runs of the jitted program ``module`` that lie wholly inside
        the traced window, in order."""
        lo, hi = self.window
        return sorted(
            (e for e in self.devices[device].modules
             if e.name.startswith(f"jit_{module}(")
             and e.start_ps >= lo and e.end_ps <= hi),
            key=lambda e: e.start_ps)

    def _inside(self, runs, events):
        """The events that lie inside one of ``runs``."""
        spans = [(r.start_ps, r.end_ps) for r in runs]
        out, i = [], 0
        for e in sorted(events, key=lambda e: e.start_ps):
            while i < len(spans) and spans[i][1] <= e.start_ps:
                i += 1
            if i < len(spans) and e.start_ps >= spans[i][0] \
                    and e.end_ps <= spans[i][1]:
                out.append(e)
        return out

    def per_run(self, module, pick, device=0, own_time=False):
        """Seconds per run of ``module`` spent in the operations ``pick``
        chooses; None if the program never ran whole inside the window."""
        runs = self.runs(module, device)
        if not runs:
            return None
        inside = self._inside(runs, self.devices[device].ops)
        if own_time:
            total = sum(t for e, t in self_times(inside) if pick(e))
        else:
            total = sum(e.duration_ps for e in inside if pick(e))
        return PS * total / len(runs)

    def gaps_between_runs(self, module, device=0):
        runs = self.runs(module, device)
        return [PS * (b.start_ps - a.end_ps) for a, b in zip(runs, runs[1:])]

    def kernel_events(self, kernel, device=0):
        lo, hi = self.window
        return [e for e in self.devices[device].ops
                if base_name(e.name) == kernel
                and e.start_ps >= lo and e.end_ps <= hi]

    # -- collectives ---------------------------------------------------------
    def collectives(self, module, device=0):
        """(seconds per run in collectives, seconds per run of them during
        which no other operation ran on that device)."""
        runs = self.runs(module, device)
        if not runs:
            return None
        dev = self.devices[device]
        sync = [e for e in self._inside(runs, dev.ops)
                if is_collective(e) and "-start" not in e.name
                and "-done" not in e.name]
        spans = [e for e in self._inside(runs, dev.async_ops)
                 if is_collective(e)]
        coll = sync + spans
        if not coll:
            return None
        total, merged = union([(e.start_ps, e.end_ps) for e in coll])
        others = [e for e, _t in self_times(self._inside(runs, dev.ops))
                  if not is_collective(e)
                  and base_name(e.name) not in CONTAINERS]
        _, busy = union([(e.start_ps, e.end_ps) for e in others])
        hidden = 0
        for s, e in merged:
            hidden += union([(max(s, a), min(e, b)) for a, b in busy
                             if b > s and a < e])[0]
        return PS * total / len(runs), PS * (total - hidden) / len(runs)

    # -- the breakdown ---------------------------------------------------------
    def breakdown(self, top=10):
        dev = self.devices[0]
        lo, hi = self.window
        inside = [e for e in dev.ops if e.end_ps > lo and e.start_ps < hi]
        by_name = {}
        for e, t in self_times(inside):
            by_name[e.name] = by_name.get(e.name, 0) + t
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        _, merged = self.busy(dev)
        idle = gaps(merged, self.window)
        by_span = {}
        for s, e in idle:
            mid = (s + e) // 2
            # the innermost benchmark span open on the host at the gap's middle
            open_ = [h for h in self.host if h.start_ps <= mid < h.end_ps]
            name = min(open_, key=lambda h: h.duration_ps).name if open_ \
                else "no benchmark span"
            by_span[name] = by_span.get(name, 0) + (e - s)
        longest = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, PS * t] for n, t in ops],
                "idle_gaps": [[n, PS * t] for n, t in longest]}
