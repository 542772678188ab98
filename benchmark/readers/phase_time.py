"""Milliseconds in one of the PROGRAM's own host phases
(``deepspeed_tpu.telemetry.tracing.phase``, read from the profiler's trace
by ``program_trace.py``). ``stat`` is ``per_window`` (the phase's total
inside the ``train.window`` events of the traced window over their number,
less the phases named in ``minus``, which nest in it) or ``median`` (of the
phase's events on any thread, such as the stager's worker). None where the
trace holds no such event: a program without phases."""

from .. import metrics, program_trace


def read(ctx, result, phase, stat, minus=()):
    found = program_trace.of(ctx)
    if stat == "median":
        events = found.named(phase)
        return 1e3 * metrics.median(
            [program_trace.PS * e.duration_ps for e in events]) \
            if events else None
    value = found.per_window(phase)
    if value is None:
        return None
    return 1e3 * (value - sum(found.per_window(m) for m in minus))
