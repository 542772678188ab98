"""Record the small trace that ``test_program_trace.py`` reduces: the
program's own phases (``deepspeed_tpu.telemetry.tracing.phase``) beside the
device's events, on one clock.

    chiprun -- python benchmark/tests/record_phases.py
    chiprun --chips 4 -- python benchmark/tests/record_phases.py   # ZeRO-2 over the four

Runs a two-layer GPT-2 at seq 1024 through three STAGED fused training
windows under ``jax.profiler``, telemetry off, on every chip jax finds (data
parallel, ZeRO-2: with more than one chip the window holds collectives).
Writes ``chiprun_out/phases/phases.xplane.pb`` and ``by_hand.json``: the
numbers the tests expect, read here with ``jax.profiler.ProfileData`` (which
shares no code with ``xplane.py``), and for each window how long after its
``train.dispatch`` began the device started ``jit_train_window``. The
one-chip pair is kept beside the tests as ``phases.xplane.pb`` and
``phases_by_hand.json``. Not part of any benchmark run; ``probe.xplane.pb``
is ``record_trace.py``'s.
"""

import glob
import itertools
import json
import os
import shutil
import statistics
import sys

OUT = os.path.join("chiprun_out", "phases")
PREFIXES = ("train.", "stage.", "bench.")


def by_hand(path):
    """What the new readers should find, from ProfileData alone (ns)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    device = next(p for p in data.planes if p.name == "/device:TPU:0")
    threads = []
    for line in host.lines:
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events if e.name.startswith(PREFIXES)]
        if events:
            threads.append(sorted(events, key=lambda e: e[1]))
    caller = next(t for t in threads if any(e[0] == "train.window" for e in t))
    (mark,) = [e for e in caller if e[0] == "bench.window"]
    windows = [e for e in caller if e[0] == "train.window"
               and e[1] >= mark[1] and e[2] <= mark[2]]

    def inside(name):
        return sum(e[2] - e[1] for e in caller if e[0] == name
                   and any(w[1] <= e[1] and e[2] <= w[2] for w in windows))

    lines = {line.name: line for line in device.lines}
    busy, at = [], None
    for s, e in sorted((e.start_ns, e.start_ns + e.duration_ns)
                       for e in lines["XLA Ops"].events):
        s, e = max(s, mark[1]), min(e, mark[2])
        if e <= s:
            continue
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    gaps, at = [], mark[1]
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if mark[2] > at:
        gaps.append((at, mark[2]))
    idle_inside = sum(
        e - s for s, e in gaps
        if any(w[1] <= (s + e) // 2 < w[2] for w in windows))
    runs = sorted(
        (e.start_ns, e.start_ns + e.duration_ns)
        for e in lines["XLA Modules"].events
        if e.name.startswith("jit_train_window("))
    # the k-th run inside the mark is the k-th dispatch's
    runs = [r for r in runs if r[0] >= mark[1]]
    dispatches = [e for e in caller if e[0] == "train.dispatch"
                  and e[1] >= mark[1]]
    lags = [r[0] - d[1] for r, d in zip(runs, dispatches)]
    stage = [e[2] - e[1] for t in threads for e in t
             if e[0] == "train.stage_window"
             and e[1] >= mark[1] and e[2] <= mark[2]]
    return {
        "windows": len(windows),
        "window_ns": sum(w[2] - w[1] for w in windows),
        "stage_wait_ns": inside("train.stage_wait"),
        "settle_ns": inside("train.settle"),
        "dispatch_ns": inside("train.dispatch"),
        "stage_window_median_ns": statistics.median(stage) if stage else None,
        "idle_inside_windows_ns": idle_inside,
        "idle_ns": sum(e - s for s, e in gaps),
        "module_start_after_dispatch_start_ns": lags,
        "host_lines_with_program_events": len(threads),
    }


def main():
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

    devices = jax.devices()
    print("devices", devices, flush=True)
    shape = dict(vocab_size=512, n_positions=1024, n_embd=256, n_layer=2,
                 n_head=4)
    micro, seq, accum = 2, 1024, 2
    rows = micro * len(devices)
    ids = np.random.default_rng(0).integers(
        0, 512, (rows, seq)).astype(np.int32)
    cfg = GPT2Config(**shape, dropout=0.0, remat=True,
                     remat_policy="dots_with_no_batch_dims_saveable+flash_out+flash_lse")
    init_cfg = GPT2Config(**shape, use_flash=False)
    params = jax.jit(GPT2LMHeadModel(init_cfg).init)(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        ids[:micro], ids[:micro])["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), model_parameters=params,
        config_params={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": accum,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "data_types": {"optimizer_state_dtype": "int8",
                           "grad_accum_dtype": "bf16",
                           "master_dtype": "compensated"},
            "steps_per_print": 10_000,
            "data_pipeline": {"enabled": True},
        })
    assert not engine.telemetry.enabled
    it = itertools.cycle([(ids, ids)])
    for _ in range(2):
        float(engine.train_batch(it))
    jax.block_until_ready(engine.params)

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(OUT, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        pending = []
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"):
                pending.append(engine.train_batch(it))
            if len(pending) > 1:
                with jax.profiler.TraceAnnotation("bench.readback"):
                    float(pending.pop(0))
        with jax.profiler.TraceAnnotation("bench.readback"):
            float(pending.pop(0))
            jax.block_until_ready(engine.params)
    jax.profiler.stop_trace()
    engine.close_data_pipeline()

    found = glob.glob(os.path.join(OUT, "plugins", "profile", "*", "*.xplane.pb"))
    print("trace files", found, [os.path.getsize(f) for f in found], flush=True)
    path = os.path.join(OUT, "phases.xplane.pb")
    shutil.copy(found[0], path)
    shutil.rmtree(os.path.join(OUT, "plugins"))
    hand = by_hand(path)
    with open(os.path.join(OUT, "by_hand.json"), "w") as fd:
        json.dump(hand, fd, indent=1)
    print(json.dumps(hand), flush=True)

    # what the new readers make of the same file
    from benchmark import program_trace, trace

    device_trace = trace.Trace(None, path=path)
    found = program_trace.ProgramTrace(path, device_trace.window)
    print(json.dumps({
        "per_window_ms": {n: 1e3 * found.per_window(n) for n in (
            "train.window", "train.stage_wait", "train.settle",
            "train.dispatch", "train.finish_step")},
        "idle_by_phase_s": {k: 1e-12 * v for k, v in
                            found.idle_by_phase(device_trace)[0].items()},
    }), flush=True)
    rows = program_trace.collective_rows(device_trace, "train_window")
    if rows:
        for r in rows[0]:
            print(json.dumps(r), flush=True)
        for scope in ("window_fwd_bwd", "window_optimizer_update"):
            print(scope, program_trace.exposed_in_scope(
                device_trace, "train_window", scope), flush=True)
        print("all", device_trace.collectives("train_window"), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    sys.exit(main())
