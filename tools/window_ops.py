"""Every device operation of a traced benchmark run under one named scope,
by its own time a run of the jitted program.

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 12 --trace 1
    python3 tools/window_ops.py <cell> <out.json> [scope] [module]

Reads the ``.xplane.pb`` that the traced run left under ``.bench_trace/<cell>``
with the benchmark's own reduction (``benchmark/trace.py``: own time, so a
``while`` does not count its body twice) and writes one row an operation
name: calls and milliseconds a run, the operation's HLO text (its result
shape and layout) and its scope path. ``fwd_bwd_ms.train`` is the sum of
these rows; ``breakdown`` prints only the first ten.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402


def rows(path, scope, module):
    t = trace.Trace(None, path=path)
    runs = t.runs(module)
    if not runs:
        raise SystemExit(f"no whole run of jit_{module} in the traced window")
    tag = f"/{scope}/"
    by_name = {}
    for e, own in trace.self_times(t._inside(runs, t.devices[0].ops)):
        scope_path = str(e.meta.get("tf_op", ""))
        if tag not in scope_path:
            continue
        row = by_name.setdefault(e.name, {
            "name": e.name, "calls": 0, "ms": 0.0,
            "hlo": e.long_name[:400], "scope": scope_path[-200:]})
        row["calls"] += 1
        row["ms"] += 1e3 * trace.PS * own
    out = sorted(by_name.values(), key=lambda r: -r["ms"])
    for row in out:
        row["calls"] /= len(runs)
        row["ms"] /= len(runs)
    return {"runs": len(runs), "scope": scope, "module": module,
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
