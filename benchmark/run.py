"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and, in
a traced run, ``breakdown``; last in it ``compared``, every number that
decided ``correct`` beside its limit (also the last lines on standard error).
Everything else goes on earlier lines.

It exits non-zero and prints no result when jax finds no TPU, or fewer chips
than the cell asks for. ``--rehearse`` is the only way to a CPU and a toy
size; its last line says ``"platform": "cpu"`` and speaks for the control
flow only.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def layer_metrics(ctx, result, bench):
    """Every per-layer metric that lists this cell, each through the reader
    its file names; a reader that finds nothing to read returns None and the
    metric is left out."""
    out = {}
    for entry in bench["per_layer"]:
        if ctx["cell"]["name"] not in entry.get(
                "workloads", [w["name"] for w in bench["workloads"]]):
            continue
        spec = harness.load_json("layer_metrics", entry["name"] + ".json")
        reader = harness.plugin("readers", spec["reader"])
        value = reader.read(ctx, result, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def bypassed_kernels(cell, trace):
    """One check per kernel the cell exists to BYPASS (``trace_must_not_run``
    in its file): finding a call of it in the trace fails the traced run."""
    out = []
    for kernel in cell.get("trace_must_not_run", []):
        calls = len(trace.kernel_events(kernel))
        out.append({"name": f"{kernel}_calls_in_trace", "value": calls,
                    "limit": 0, "ok": calls == 0})
    return out


def context(workload, seed, seconds, trace=0, rehearse=False, chips=None):
    """Everything a loop is handed; None where the machine is not what the
    cell asks for. ``chips`` overrides how many the machine must hold (the
    control of a training cell drives the reference alone, on one)."""
    cell, config, bench = harness.load_cell(workload)
    if rehearse:
        cell.update(cell.get("toy", {}))
    cache_dir = harness.place_compile_cache()
    # what the deployment's launcher exports before the process starts
    # (libtpu reads its flags once, when the backend initializes)
    for key, value in cell.get("launch_env", {}).items():
        os.environ.setdefault(key, value)

    import jax

    devices = jax.devices()
    chips = chips or cell["chips"]
    if not rehearse and (
            devices[0].platform != "tpu" or len(devices) < chips):
        print(f"benchmark: the cell needs {chips} TPU chip(s); jax "
              f"found {len(devices)} x {devices[0].platform}", file=sys.stderr)
        return None
    if len(devices) < chips:
        print("benchmark: rehearse a four-chip cell with XLA_FLAGS="
              "--xla_force_host_platform_device_count=4", file=sys.stderr)
        return None
    devices = devices[:chips]
    peaks = harness.load_json("peaks.json").get(devices[0].device_kind)
    if peaks is None and not rehearse:
        print(f"benchmark: no peaks for device kind "
              f"{devices[0].device_kind!r} in peaks.json", file=sys.stderr)
        return None
    spans = harness.Spans()
    harness.say("start", workload=cell["name"], seed=seed, seconds=seconds,
                trace=trace, cache_dir=cache_dir, jax=jax.__version__,
                device_kind=devices[0].device_kind)
    return {
        "cell": cell, "config": config, "bench": bench,
        "size": harness.sizes(config, rehearse),
        "seed": seed, "seconds": seconds, "rehearse": rehearse,
        "devices": devices, "peaks": peaks, "spans": spans,
        "compiles": harness.CompileCounter(), "t_process": T_PROCESS,
        "t_loop": time.perf_counter(),
        "tracer": harness.Tracer(
            bool(trace), spans,
            os.path.join(harness.ROOT, ".bench_trace", cell["name"])),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on whatever jax finds: control flow only")
    args = ap.parse_args(argv)
    ctx = context(args.workload, args.seed, args.seconds, args.trace,
                  args.rehearse)
    if ctx is None:
        return 3
    cell, bench, spans = ctx["cell"], ctx["bench"], ctx["spans"]
    result = harness.plugin("loops", cell["loop"]).run(ctx)

    checks = list(result["checks"])
    device, breakdown = result["device"], {}
    if args.trace:
        from benchmark import trace

        ctx["trace"] = trace.Trace(ctx["tracer"], spans)
        checks += bypassed_kernels(cell, ctx["trace"])
        reported = layer_metrics(ctx, result, bench)
        device.update(ctx["trace"].busy_and_window())
        breakdown = {"breakdown": ctx["trace"].breakdown()}
    else:
        # a loop offers every end-to-end number it takes; BENCHMARK.json
        # says which of them this cell reports
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])}
        reported = {
            name: {"value": value, "unit": units[name]}
            for name, value in result["end_to_end"].items() if name in units
        }
    # every number compared beside its limit: on an earlier line each, as the
    # result's last key, and as the last lines on standard error (what the
    # driver keeps of a run at fault)
    for c in checks:
        harness.say("compared", **c)
        print(f"compared {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {
        "correct": all(c["ok"] for c in checks),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": reported,
        "device": device,
        **breakdown,
        "compared": {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks},
    }
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
