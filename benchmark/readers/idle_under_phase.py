"""Milliseconds a window in which no operation ran on device 0 WHILE the
program held control: the idle gaps of the traced window whose middle lies
inside a ``phase`` event (``train.window``) of the program, over the number
of those events; the rest of the idle time is the caller's. An earlier line
(``idle_by_program_phase``) gives the idle seconds of the whole traced
window by the innermost program phase open on the calling thread at each
gap's middle, as ``breakdown.idle_gaps`` does by benchmark span. None for a
program without phases."""

from .. import harness, program_trace


def read(ctx, result, phase):
    found = program_trace.of(ctx)
    windows = found.named(phase)
    if not windows:
        return None
    by_phase, inside = found.idle_by_phase(ctx["trace"])
    harness.say("idle_by_program_phase", windows=len(windows), seconds={
        name: program_trace.PS * ps
        for name, ps in sorted(by_phase.items(), key=lambda kv: -kv[1])})
    return 1e3 * program_trace.PS * inside / len(windows)
