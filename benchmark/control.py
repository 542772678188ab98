"""The control of ``correct``: the reference, put in the program's place and
computed in the nearest precision below the one the configuration states
(fp8 for bfloat16), has to come out as NOT correct.

    chiprun -- python3 benchmark/control.py --workload <cell> --seeds 1,2,3

Run on the chip at the cell's own size; the benchmark's own runs never run
it. For a training cell it needs no engine and no window: the reference
follows the cell's first steps twice, in float32 and in the lower precision,
and the three numbers of ``loops/train.py`` are read between the two. For a
serving cell it serves a short window (``--seconds``), takes the run's own
sample of finished requests, and reads at every served position the gap of
the token that the lower precision puts first. Prints one line per seed
with each number beside its limit, and a last line that says whether every
seed failed at least one limit. ``tests/test_control.py`` keeps the same at
a size a test run can hold.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, run  # noqa: E402


LOWER = "fp8"   # the nearest precision below bfloat16


def train_control(ctx):
    from benchmark.loops import train

    cell, config, size = ctx["cell"], ctx["config"], ctx["size"]
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    stream = gen.micro_batches(ctx["seed"], cell, size)
    kept = [next(stream) for _ in range(cell["check"]["steps"] * cell["accum"])]
    sound = train.reference_readings(ref, size, config, cell, ctx["seed"], kept)
    lower = train.reference_readings(
        ref, size, config, cell, ctx["seed"], kept, LOWER)
    found = train.compare(lower, sound)
    harness.say("control_at", **{k: v[1] for k, v in found.items()})
    return {k: v[0] for k, v in found.items()}


def serve_control(ctx):
    from benchmark.loops import serve

    config, size = ctx["config"], ctx["size"]
    ref = harness.plugin("reference", config["reference"])
    result = serve.run(ctx)
    sound = max(float(r["gaps"].max()) for r in result["read"])
    lower = serve.served_gaps(
        ref, size, ctx["seed"], result["sample"], result["length"], LOWER,
        chosen=[r["rows"] for r in result["read"]])
    return {
        "widest_gap_below_best": max(float(r["gaps"].max()) for r in lower),
        "program_widest_gap_below_best": sound,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        training = harness.load_cell(args.workload)[0]["loop"] == "train"
        ctx = run.context(args.workload, seed, args.seconds, 0, args.rehearse,
                          chips=1 if training else None)
        if ctx is None:
            return 3
        reader = train_control if training else serve_control
        numbers = reader(ctx)
        limits = ctx["cell"]["check"]["limits"]
        failed = [k for k, v in numbers.items() if k in limits and v > limits[k]]
        all_failed = all_failed and bool(failed)
        print(json.dumps({"control": LOWER, "seed": seed,
                          "numbers": numbers, "limits": limits,
                          "fails": failed}), flush=True)
    print(json.dumps({"control_not_correct_on_every_seed": all_failed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
