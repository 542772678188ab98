"""What `setup_s` reads on trees that do not differ: sets of runs of ONE
cell on this tree against itself, as the driver runs a parent against a
change. The bound of `setup_s` rests on these readings (PERF.md, section 2).

    chiprun -- python3 benchmark/setup_pairs.py --workload <cell> --sets 2

Each set gets a checkout of its own under `.bench_checkout/` (the benchmark,
the program and `BENCHMARK.json`, copied), as the driver's two sides have.
The compile cache is wherever `run.py` puts it: a checkout's own
`.jax_cache`, or, where the machine sets `JAX_COMPILATION_CACHE_DIR`, that
ONE directory for every set (a program with kernels still compiles once a
checkout, since its key holds the checkout's path). A first run a checkout
compiles and is kept apart (the first set's goes to its end, for `correct`);
then six runs a set of five seconds, the sets alternated and the same seeds
in every set, each STOPPED once its `setup` line is out: that line is printed
before the window opens, and set-up does not depend on what follows. Prints
every run's parts, each part's spread by set (interquartile range over
median) and the largest gap between the medians of two disjoint pairs of
runs: what a two-pair check would have read. Touches no jax itself: the chip
is the runs'. Not part of any benchmark run.
"""

import argparse
import itertools
import json
import os
import shutil
import signal
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, metrics  # noqa: E402

PARTS = ["process_to_first_window_s", "before_the_loop_s", "setup_s",
         "bench.weights_s", "bench.engine_build_s", "bench.first_steps_s",
         "bench.read_state_s", "unnamed_s"]
COPIED = ["BENCHMARK.json", "benchmark", "deepspeed_tpu"]
RUNS, SECONDS = 6, 5.0    # a run: set-up does not depend on the window's length
PATIENCE = 30.0           # seconds a stopped run gets to unwind


def checkout(name):
    root = os.path.join(harness.ROOT, ".bench_checkout", name)
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    for item in COPIED:
        src = os.path.join(harness.ROOT, item)
        if os.path.isdir(src):
            shutil.copytree(src, os.path.join(root, item),
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, root)
    return root


def stop(proc):
    """Ctrl-C first, so that the interpreter unwinds and gives the chip
    back; a run that does not is killed."""
    proc.send_signal(signal.SIGINT)
    try:
        return proc.wait(timeout=PATIENCE)
    except subprocess.TimeoutExpired:
        proc.kill()
        return proc.wait()


def one_run(root, workload, seed, whole, log):
    """The `setup` line of one run and, from a whole run, its result."""
    command = [sys.executable, "benchmark/run.py", "--workload", workload,
               "--seed", str(seed), "--seconds", str(SECONDS), "--trace", "0"]
    with open(log, "a") as errors:
        proc = subprocess.Popen(command, cwd=root, stdout=subprocess.PIPE,
                                stderr=errors, text=True)
        setup = last = None
        try:
            for line in proc.stdout:
                if line.startswith('{"note": "setup"'):
                    setup = json.loads(line)
                    if not whole:
                        break
                elif line.startswith("{"):
                    last = line
        finally:
            if proc.poll() is None and not whole:
                stop(proc)
            proc.stdout.close()
            code = proc.wait()
    result = json.loads(last) if whole and last and code == 0 else None
    if setup is None or (whole and (result is None or "correct" not in result)):
        raise SystemExit(f"setup_pairs: run of seed {seed} in {root} gave no "
                         f"{'result' if setup else 'setup line'} (exit {code}); see {log}")
    return setup, result


def two_pair_gap(values):
    """The largest (worse - better) / better between the medians of two
    disjoint pairs of the runs."""
    worst = 0.0
    for a in itertools.combinations(range(len(values)), 2):
        rest = [i for i in range(len(values)) if i not in a]
        for b in itertools.combinations(rest, 2):
            one = metrics.median([values[i] for i in a])
            other = metrics.median([values[i] for i in b])
            worst = max(worst, abs(one - other) / min(one, other))
    return worst


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seed", type=int, default=4800000000,
                    help="the first of a set's six; give a call seeds of its own")
    args = ap.parse_args(argv)
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "setup_pairs")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, args.workload + ".stderr.log")
    roots = [checkout(f"set_{i}") for i in range(args.sets)]

    report = {"workload": args.workload, "seconds": SECONDS,
              "first_runs": [], "runs": []}
    for i, root in enumerate(roots):
        setup, result = one_run(root, args.workload, args.seed + 900 + i,
                                i == 0, log)
        report["first_runs"].append({"set": i, "setup": setup, "result": result})
        print(json.dumps(report["first_runs"][-1]), flush=True)
    for k in range(RUNS):
        order = list(range(args.sets))
        for i in (order if k % 2 == 0 else order[::-1]):
            setup, _ = one_run(roots[i], args.workload, args.seed + k, False, log)
            report["runs"].append({"set": i, "seed": args.seed + k, **setup})
            print(json.dumps(report["runs"][-1]), flush=True)

    table = {}
    for part in PARTS:
        by_set = [[r[part] for r in report["runs"] if r["set"] == i]
                  for i in range(args.sets)]
        everything = [r[part] for r in report["runs"]]
        table[part] = {
            "median_by_set": [metrics.median(v) for v in by_set],
            "spread_by_set": [metrics.iqr_share(v) for v in by_set],
            "min": min(everything), "max": max(everything),
            "two_pair_gap": two_pair_gap(everything),
        }
        print(part, json.dumps(table[part]), flush=True)
    report["table"] = table
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as fd:
        json.dump(report, fd, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
