"""The program's OWN host phases in the profiler's trace.

``deepspeed_tpu.telemetry.tracing.phase`` enters a
``jax.profiler.TraceAnnotation`` for every block-shaped phase of the program
(``train.window``, ``train.dispatch``, ``train.stage_window``,
``sched.decode_step``, ...), so a traced run holds them as events on the
``/host:CPU`` plane, one line per thread, on the clock of the device planes.
``trace.py`` keeps only the benchmark's ``bench.*`` events; this reads the
program's, once a run, and keeps them on ``ctx``.

"Per window" is the total of a phase inside the ``train.window`` events that
lie wholly inside the traced window, over the number of those events. A
program without phases (the parent of the PR that added them) gives no
``train.window`` event, and every reader here then returns None.
"""

from . import trace as trace_mod
from . import xplane

PS = trace_mod.PS
PREFIXES = ("train.", "stage.", "sched.")
WINDOW = "train.window"


class ProgramTrace:
    def __init__(self, path, window):
        planes = xplane.read(
            path, lambda plane, line: plane == "/host:CPU",
            lambda plane, event: event.startswith(PREFIXES))
        self.adopt(window, [line.events for p in planes for line in p.lines])

    def adopt(self, window, threads):
        """``threads``: one list of events per host thread; those wholly
        inside ``window`` are kept."""
        lo, hi = self.window = window
        self.threads = [
            sorted((e for e in events
                    if e.start_ps >= lo and e.end_ps <= hi),
                   key=lambda e: (e.start_ps, -e.duration_ps))
            for events in threads]
        self.threads = [t for t in self.threads if t]
        # the thread that called train_batch(), and its windows
        self.caller = next(
            (t for t in self.threads if any(e.name == WINDOW for e in t)), [])
        self.windows = [e for e in self.caller if e.name == WINDOW]

    def named(self, name):
        return [e for t in self.threads for e in t if e.name == name]

    def in_a_window(self, at_ps, end_ps=None):
        end_ps = at_ps if end_ps is None else end_ps
        return any(w.start_ps <= at_ps and end_ps <= w.end_ps
                   for w in self.windows)

    def per_window(self, name):
        """Seconds a window in phase ``name``, counted where it lies inside
        a ``train.window`` event of the calling thread; None without
        windows."""
        if not self.windows:
            return None
        total = sum(e.duration_ps for e in self.caller if e.name == name
                    and self.in_a_window(e.start_ps, e.end_ps))
        return PS * total / len(self.windows)

    def innermost(self, at_ps):
        """The innermost phase open on the calling thread at ``at_ps``."""
        open_ = [e for e in self.caller
                 if e.start_ps <= at_ps < e.end_ps]
        return min(open_, key=lambda e: e.duration_ps) if open_ else None

    def idle_by_phase(self, device_trace, device=0):
        """{innermost program phase, or "outside the program": idle
        picoseconds of ``device``}, each idle gap of the traced window
        going to the phase open at the gap's middle, and the idle
        picoseconds that fell inside a ``train.window`` event."""
        dev = device_trace.devices[device]
        _, merged = device_trace.busy(dev)
        by_phase, in_window = {}, 0
        for s, e in trace_mod.gaps(merged, self.window):
            mid = (s + e) // 2
            inner = self.innermost(mid)
            name = inner.name if inner else "outside the program"
            by_phase[name] = by_phase.get(name, 0) + (e - s)
            if self.in_a_window(mid):
                in_window += e - s
        return by_phase, in_window


def of(ctx):
    """The run's program trace, read once and kept on ``ctx``."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = ProgramTrace(
            ctx["tracer"].path, ctx["trace"].window)
    return ctx["program_trace"]


def _collectives(device_trace, module, device):
    """(runs of ``module``, its collectives, the merged intervals in which
    another operation ran): ``Trace.collectives``'s own choice of events
    (sync operations and async start..done spans; a container's own time
    is not another operation)."""
    runs = device_trace.runs(module, device)
    dev = device_trace.devices[device]
    ops = device_trace._inside(runs, dev.ops)
    coll = [e for e in ops if trace_mod.is_collective(e)
            and "-start" not in e.name and "-done" not in e.name]
    coll += [e for e in device_trace._inside(runs, dev.async_ops)
             if trace_mod.is_collective(e)]
    others = [e for e, _t in trace_mod.self_times(ops)
              if not trace_mod.is_collective(e)
              and trace_mod.base_name(e.name) not in trace_mod.CONTAINERS]
    _, busy = trace_mod.union([(e.start_ps, e.end_ps) for e in others])
    return runs, coll, busy


def _exposed(intervals, busy):
    """Picoseconds of the merged ``intervals`` not covered by ``busy``."""
    total, merged = trace_mod.union(intervals)
    for s, e in merged:
        total -= trace_mod.union([(max(s, a), min(e, b)) for a, b in busy
                                  if b > s and a < e])[0]
    return total


def collective_rows(device_trace, module, device=0):
    """Every collective of the runs of ``module`` on ``device`` by name and
    ``tf_op``: rows ``{"name", "tf_op", "calls", "total_ps",
    "exposed_ps"}`` summed over the runs, longest first, and the number of
    runs. None if the program never ran whole inside the traced window or
    ran no collective."""
    runs, coll, busy = _collectives(device_trace, module, device)
    if not coll:
        return None
    rows = {}
    for e in coll:
        tf_op = str(e.meta.get("tf_op", ""))
        row = rows.setdefault((e.name, tf_op), {
            "name": e.name, "tf_op": tf_op, "calls": 0, "total_ps": 0,
            "exposed_ps": 0})
        row["calls"] += 1
        row["total_ps"] += e.duration_ps
        row["exposed_ps"] += _exposed([(e.start_ps, e.end_ps)], busy)
    return sorted(rows.values(), key=lambda r: -r["total_ps"]), len(runs)


def exposed_in_scope(device_trace, module, scope, device=0):
    """Seconds a run of ``module`` during which a collective whose
    ``tf_op`` holds ``/scope/`` ran on ``device`` and no other operation
    did; None where no collective carries the scope."""
    runs, coll, busy = _collectives(device_trace, module, device)
    tag = f"/{scope}/"
    mine = [(e.start_ps, e.end_ps) for e in coll
            if tag in str(e.meta.get("tf_op", ""))]
    if not mine:
        return None
    return PS * _exposed(mine, busy) / len(runs)
