"""Every device operation of a traced benchmark run under one named scope,
by its own time a run of the jitted program.

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 12 --trace 1
    python3 tools/window_ops.py <cell> <out.json> [scope] [module]

Reads the ``.xplane.pb`` that the traced run left under ``.bench_trace/<cell>``
with the benchmark's own reduction (``benchmark/trace.py``: own time, so a
``while`` does not count its body twice) and writes one row an operation
name: calls and milliseconds a run, the pass (forward, recompute, backward),
the opcode and the profiler's category, the operation's HLO text (its result
shape and layout) and its scope path. The rows are
``benchmark/readers/unscoped_time.py:op_rows``'s, which the ``unscoped_ops``
note of a traced run lists the first ten of for the operations under no
layer's scope: the tool and the metric cannot disagree. ``fwd_bwd_ms.train``
is the sum of these rows; ``breakdown`` prints only the first ten.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402
from benchmark.readers import pass_time, unscoped_time  # noqa: E402


def rows(path, scope, module):
    ctx = {"trace": trace.Trace(None, path=path)}
    made = pass_time.table(ctx, module)
    if made is None:
        raise SystemExit(f"no whole run of jit_{module} in the traced window")
    out = unscoped_time.op_rows(
        made["own"], made["runs"], lambda p: f"/{scope}/" in p)
    for row in out:
        row["hlo"], row["scope"] = row["hlo"][:400], row.pop("path")[-200:]
    return {"runs": made["runs"], "scope": scope, "module": module,
            "total_ms": sum(r["ms"] for r in out), "ops": out}


def main(argv):
    cell, out = argv[0], argv[1]
    scope = argv[2] if len(argv) > 2 else "window_fwd_bwd"
    module = argv[3] if len(argv) > 3 else "train_window"
    found = glob.glob(os.path.join(
        ROOT, ".bench_trace", cell, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise SystemExit(f"no trace under .bench_trace/{cell}: run the cell "
                         "with --trace 1 first")
    table = rows(found[0], scope, module)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as fd:
        json.dump(table, fd, indent=1)
    print(json.dumps({"ops": len(table["ops"]), "runs": table["runs"],
                      "total_ms": table["total_ms"],
                      "first": [[r["name"], round(r["ms"], 3)]
                                for r in table["ops"][:12]]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
