"""Device milliseconds per run of a jitted program spent under one
``jax.named_scope`` in ONE pass of training: ``forward``, ``recompute`` or
``backward``, by each operation's own time as ``scope_time`` counts it, so
the three add up to ``scope_time`` of the same scope.

The pass is read off the operation's ``tf_op`` path, where JAX writes the
transformations an operation was made by (jax 0.9.0; checked on the lowered
window of every family, ``tests/unit/test_device_scopes.py``, and on the
chip, ``PERF.md`` §6 PR 35):

- ``recompute``: the path holds ``rematted_computation``, which
  ``jax.checkpoint`` puts around everything it runs again in the backward
  pass (``jax/_src/ad_checkpoint.py``). The blocked head loss checkpoints
  each block of rows, so its second product reads here too.
- ``backward``: else the path holds ``transpose(``, autodiff's mark on what
  it derived (``transpose(jvp(Model))``).
- ``forward``: everything else (``jvp(Model)``, and what carries neither
  mark: the micro-step loop's own time, the gradients' accumulation).

A hand-written backward is ``backward`` whole, whatever it computes again
inside itself: the flash backward kernel recomputes the scores, ``gdn_bwd``
a segment's states, and ``grouped_expert_ffn``'s backward its hidden
activations, and none of that carries ``rematted_computation``. A kernel
the forward runs again under a checkpoint (``flash_fwd`` in BERT's recipe)
is ``recompute``.

**Which scopes there are** is data: every ``benchmark/scopes/*.json`` lists
scopes the program opens, under ``layers`` (the parts of a window's forward
and backward that no other of them holds: a model's layers, the engine's
gradient sum), ``around`` (a scope that encloses layers and has time of its
own besides: the scans that walk a stack) and ``other`` (scopes that enclose
the window's parts or lie inside a layer). A PR that opens a scope adds a
file of its own there; ``listed()`` merges them. ``unscoped_time`` takes the
first two as what it leaves out.

One walk of the window's operations a run (``table``, kept on ``ctx``) feeds
every metric of this reader and of ``unscoped_time`` and both their notes.
An earlier line (``passes_by_scope``, once a run) gives, for every listed
scope that the trace holds, milliseconds a run as ``[forward, recompute,
backward]``, and what making the table cost. None where no operation
carries the scope."""

import os
import time

from .. import harness
from .. import trace as trace_mod

PASSES = ("forward", "recompute", "backward")
KINDS = ("layers", "around", "other")


def listed():
    """{kind: [scope, ...]} over every file of ``benchmark/scopes/``, in the
    files' order by name, each scope once."""
    out = {kind: [] for kind in KINDS}
    folder = os.path.join(harness.HERE, "scopes")
    for name in sorted(os.listdir(folder)):
        if name.endswith(".json"):
            spec = harness.load_json("scopes", name)
            for kind in KINDS:
                out[kind] += [s for s in spec.get(kind, [])
                              if not any(s in seen for seen in out.values())]
    return out


def which_pass(path):
    if "rematted_computation" in path:
        return "recompute"
    return "backward" if "transpose(" in path else "forward"


def split(own, scopes):
    """{scope: [forward, recompute, backward] picoseconds} of the scopes
    among ``scopes`` that some operation of ``own`` carries."""
    tags = [(scope, f"/{scope}/") for scope in scopes]
    out = {}
    for _event, ps, path, at in own:
        for scope, tag in tags:
            if tag in path:
                out.setdefault(scope, [0, 0, 0])[at] += ps
    return out


def table(ctx, module):
    """``{"own", "runs", "by_scope"}`` over the whole runs of ``module`` on
    device 0: ``own`` is ``[(operation, its own picoseconds, its path, the
    index of its pass)]``, the walk of ``Trace.per_run(own_time=True)``, and
    ``by_scope`` is ``split`` over every listed scope. Made once a run and
    kept on ``ctx``. None if the program never ran whole inside the window."""
    kept = ctx.setdefault("window_table", {})
    if module not in kept:
        t0 = time.perf_counter()
        found = ctx["trace"]
        # the operations ``per_run`` takes (its device, its whole runs), by a
        # predicate that keeps each and picks none
        inside = []
        if found.per_run(module, inside.append, own_time=True) is None:
            kept[module] = None
            return None
        own = []
        for event, ps in trace_mod.self_times(inside):
            path = str(event.meta.get("tf_op", ""))
            own.append((event, ps, path, PASSES.index(which_pass(path))))
        scopes = [s for kind in listed().values() for s in kind]
        kept[module] = {"own": own, "runs": len(found.runs(module)),
                        "by_scope": split(own, scopes), "listed": scopes}
        note(module, kept[module], time.perf_counter() - t0)
    return kept[module]


def note(module, made, seconds):
    runs = made["runs"]
    harness.say("passes_by_scope", module=module, runs=runs,
                passes=list(PASSES),
                ms_per_run={scope: [1e3 * trace_mod.PS * ps / runs
                                    for ps in made["by_scope"][scope]]
                            for scope in made["listed"]
                            if scope in made["by_scope"]},
                reader_s=seconds)


def read(ctx, result, module, scope, which):
    if which not in PASSES:
        raise ValueError(f"pass_time: which is one of {PASSES}, not {which!r}")
    made = table(ctx, module)
    if made is None:
        return None
    if scope not in made["listed"]:      # a scope no file of scopes/ lists
        made["listed"].append(scope)
        made["by_scope"].update(split(made["own"], [scope]))
    passes = made["by_scope"].get(scope)
    if passes is None:
        return None
    return 1e3 * trace_mod.PS * passes[PASSES.index(which)] / made["runs"]
