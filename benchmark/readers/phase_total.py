"""Seconds the PROGRAM counted under names of its phase table
(``deepspeed_tpu.telemetry.tracing.phase_totals()``: count, total and
longest by phase name, kept from process start, so it holds the set-up,
which is over before any trace starts). ``kinds`` picks the names by their
start (``compile.trace``, ``compile.lower``, ``compile.backend``: a backend
compile that the persistent cache answers is counted there too, with its
load inside it, so ``compile.cache_load`` is a part of it and not a term of
the sum) and ``charged_to`` the phases that paid them
(``compile.backend@train.dispatch`` is charged to ``train.dispatch``; ``@-``
is outside any phase: the benchmark's own programs).

An earlier line (``phase_totals``) prints what was summed, and beside it
every other ``compile.*`` total and the ``also`` phases. None where the
program keeps no table."""

from .. import harness


def read(ctx, result, kinds, charged_to, also=()):
    try:
        from deepspeed_tpu.telemetry.tracing import phase_totals
    except ImportError:
        return None
    seconds = {name: row[1] for name, row in phase_totals().items()}
    summed = {name: s for name, s in seconds.items()
              if name.startswith(tuple(kinds))
              and name.split("@", 1)[-1].startswith(tuple(charged_to))}
    harness.say("phase_totals", summed=summed, beside={
        name: s for name, s in seconds.items() if name not in summed
        and name.startswith(("compile.",) + tuple(also))})
    return sum(summed.values()) if summed else None
