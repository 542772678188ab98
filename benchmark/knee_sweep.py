"""Find the highest arrival rate a serving cell sustains, once, by one sweep
on the chip; the cell's file then fixes its rate at a share of it.

    chiprun -- python3 benchmark/knee_sweep.py --workload <serving cell> \
        --rates 2,3,4,5,6,8 --seconds 20

One process, one engine; each rate is offered for ``--seconds`` with the
cell's own mix, then drained. A rate is sustained when the backlog (requests
submitted and not finished) at the end of the window is no larger than at
its middle. Prints one line per rate and the knee. Not part of any
benchmark run.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, metrics, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    ctx = run.context(args.workload, args.seed, args.seconds, 0, args.rehearse)
    if ctx is None:
        return 3
    import jax

    from benchmark import program
    from benchmark.loops import serve

    cell, config, size = ctx["cell"], ctx["config"], ctx["size"]
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    from benchmark.reference import ops

    params = ops.initializer(ref, size)(ops.seed_key(args.seed))
    engine = program.build_serve(config, cell, size, params, ctx["devices"])
    del params
    serve.warm(engine, cell, size, args.seed)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell["traffic"], rate_per_s=rate)
        reqs = gen.requests(args.seed + i, traffic, size, args.seconds)
        spans = harness.Spans()
        records, refused, total_s, peak = serve.drive(
            engine, reqs, args.seconds, cell["drain_seconds"], spans,
            ctx["tracer"], 0)
        steps = spans.named("bench.step")
        at = lambda t: next((a["backlog"] for _n, _s, _e, a in reversed(steps)
                             if a["ended"] <= t), 0)
        middle, end = at(args.seconds / 2), at(args.seconds)
        ttft = [r.times[0] - r.request.due for r in records if r.times]
        gaps = [b - a for r in records for a, b in zip(r.times, r.times[1:])]
        tokens = sum(t <= args.seconds for r in records for t in r.times)
        decode = [1e3 * (e - s) for _n, s, e, a in steps
                  if a["active"] and not a["admitted"]]
        sustained = end <= max(middle, 1)
        if sustained:
            knee = rate
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs), "refused": refused,
            "backlog_middle": middle, "backlog_end": end,
            "sustained": sustained, "tokens_per_s": tokens / args.seconds,
            "ttft_p50_ms": 1e3 * metrics.median(ttft),
            "ttft_p90_ms": 1e3 * metrics.percentile(ttft, 90),
            "itl_p50_ms": 1e3 * metrics.median(gaps),
            "itl_p95_ms": 1e3 * metrics.percentile(gaps, 95),
            "decode_step_ms_p50": metrics.median(decode) if decode else None,
            "drained_after_s": total_s - args.seconds,
            "slots_in_use_peak": peak["slots"],
            "pages_in_use_peak": peak["pages"],
        }), flush=True)
    print(json.dumps({"knee_per_s": knee,
                      "device": harness.device_report(ctx["devices"])}))
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
