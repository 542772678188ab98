"""Device milliseconds per run of a jitted program spent under one
``jax.named_scope`` (``under``) in operations whose path holds NONE of
``scopes``: what is left of a window once every named part has taken its
own, by each operation's own time as ``scope_time`` counts it.

``scopes`` left out (as every metric's file leaves it) means the ``layers``
and ``around`` lists of ``benchmark/scopes/*.json`` (``pass_time.listed``),
less ``under`` itself: a PR that opens a new layer's scope adds a file
there, and what it named leaves this remainder without an edit here. So

- under ``window_fwd_bwd`` this is what lies outside every layer's scope,
  outside the engine's gradient sum AND outside the scans that walk a
  stack (``unscoped_ms.train``);
- under ``stack_scan`` it is the scans' own time: everything inside them
  that no layer holds, the slices out of and the writes into the stacked
  residuals, the zero-fill, the loop (``stack_scan_ms.train``).

With the ``layers`` disjoint (one is never opened inside another in a
cell's program; ``tests/unit/test_device_scopes.py``), ``scope_time`` of
each layer, plus this under each ``around`` scope, plus this under the
window, is ``scope_time`` of the window. Where one layer could lie inside
another (``dense_ffn`` around an ``ffn_fn`` that opens ``moe_*``: GPT-2's
expert variant, which no cell runs) both names are in the inner operations'
paths, each scope's own metric counts them, and the sum over the list counts
them twice: count the outer one only.

A fusion is one operation and carries one path: where XLA folds a norm into
the product that reads it, the fusion is named after one of the two and its
whole time goes to that one's scope. Every operation is counted once.

An earlier line (``unscoped_ops``, once a call) lists the ten largest of
the operations counted here by name: calls and milliseconds a run, the pass
(``pass_time``), the HLO opcode (a fusion's with its ``kind``) and the
profiler's category (``convolution fusion`` is a matmul), and the last 120
characters of the path. ``op_rows`` makes those rows, for this note and for
``tools/window_ops.py``.

None, with the ``reason`` in the note, if the program never ran whole, if no
operation lies under ``under``, or if scopes were to be left out and NO
operation under ``under`` carries any of them: the program then has none of
the names this metric is the remainder of (it was compiled before they were
opened, or a warm compile cache answered with such a program, ``PERF.md``
§6 PR 35), and "everything" would be a number that means nothing."""

import re
import time

from .. import harness
from .. import trace as trace_mod
from . import pass_time

TOP = 10


def opcode(long_name):
    """``fusion kOutput`` / ``copy-done`` / ``while`` out of an operation's
    HLO text (``%name = shape opcode(operands), kind=...``)."""
    found = re.search(r" ([a-z][a-z\-]*)\(", long_name)
    kind = re.search(r"\bkind=(\w+)", long_name)
    return " ".join(m.group(1) for m in (found, kind) if m)


def op_rows(own, runs, pick):
    """One row an operation name over the operations of ``own``
    (``pass_time.table``) whose path ``pick`` takes, longest first:
    ``name``, ``calls`` and ``ms`` a run, ``pass``, ``opcode``,
    ``category``, the whole ``path`` and the HLO text ``hlo``."""
    by_name = {}
    for event, ps, path, at in own:
        if not pick(path):
            continue
        row = by_name.setdefault(event.name, {
            "name": event.name, "calls": 0, "ms": 0,
            "pass": pass_time.PASSES[at],
            "opcode": opcode(event.long_name),
            "category": str(event.meta.get("hlo_category", "")),
            "path": path, "hlo": event.long_name})
        row["calls"] += 1
        row["ms"] += ps
    for row in by_name.values():
        row["calls"] /= runs
        row["ms"] *= 1e3 * trace_mod.PS / runs
    return sorted(by_name.values(), key=lambda row: -row["ms"])


def read(ctx, result, module, under, scopes=None):
    t0 = time.perf_counter()
    made = pass_time.table(ctx, module)
    if made is None:
        return None
    if scopes is None:
        named = pass_time.listed()
        scopes = named["layers"] + named["around"]
    inside, tags = f"/{under}/", [f"/{s}/" for s in scopes if s != under]
    paths = [path for _event, _ps, path, _at in made["own"] if inside in path]
    if not paths:
        reason = f"no operation under {under}"
    elif tags and not any(tag in path for path in paths for tag in tags):
        reason = (f"no operation under {under} carries any of the scopes "
                  "to leave out")
    else:
        reason = None
    rows = op_rows(made["own"], made["runs"], lambda path: inside in path
                   and not any(tag in path for tag in tags))
    total = sum(row["ms"] for row in rows)
    harness.say("unscoped_ops", module=module, under=under,
                runs=made["runs"], operations=len(rows), ms_per_run=total,
                reason=reason,
                largest=[{**{k: row[k] for k in (
                    "name", "calls", "ms", "pass", "opcode", "category")},
                    "path": row["path"][-120:]} for row in rows[:TOP]],
                reader_s=time.perf_counter() - t0)
    return None if reason else total
