"""Record the small device trace that ``test_trace.py`` reduces, and dump
what a trace on this runtime looks like (planes, lines, event names and
stats), so that the reduction is written against what is there.

    chiprun -- python benchmark/tests/record_trace.py

Runs a two-layer GPT-2 at head size 64 and seq 1024 through the program's
fused training window and its paged decode, on the chip, under
``jax.profiler``. Writes ``chiprun_out/trace_probe/``: the ``.xplane.pb``
and ``structure.json``. Not part of any benchmark run.
"""

import glob
import itertools
import json
import os
import shutil
import sys

OUT = os.path.join("chiprun_out", "trace_probe")


def dump_structure(path, limit=12):
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for ev in events:
                names[ev.name] = names.get(ev.name, 0) + 1
            sample = []
            for ev in events[:limit]:
                sample.append({
                    "name": ev.name, "start_ns": ev.start_ns,
                    "duration_ns": ev.duration_ns,
                    "stats": {k: str(v)[:300] for k, v in ev.stats},
                })
            top = sorted(names.items(), key=lambda kv: -kv[1])[:60]
            lines.append({"name": line.name, "n_events": len(events),
                          "names": top, "sample": sample})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def main():
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

    print("devices", jax.devices(), flush=True)
    shape = dict(vocab_size=512, n_positions=1024, n_embd=256, n_layer=2,
                 n_head=4)
    micro, seq, accum = 2, 1024, 2
    ids = np.random.default_rng(0).integers(0, 512, (micro, seq)).astype(np.int32)
    cfg = GPT2Config(**shape, dropout=0.0, remat=True,
                     remat_policy="dots_with_no_batch_dims_saveable+flash_out+flash_lse")
    init_cfg = GPT2Config(**shape, use_flash=False)
    params = jax.jit(GPT2LMHeadModel(init_cfg).init)(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        ids, ids)["params"]
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
    it = itertools.cycle([(ids, ids)])
    for _ in range(2):
        float(engine.train_batch(it))

    serve = deepspeed_tpu.init_inference(
        model=GPT2LMHeadModel(GPT2Config(**shape, dropout=0.0)),
        model_parameters=params,
        config={"inference": {
            "dtype": "bf16", "max_batch_slots": 4, "max_seq_len": 256,
            "prefill_len": 128, "kv_block_size": 16, "kv_pool_blocks": 64,
            "fused_decode": True, "sampling": {"greedy": True}}})
    prompts = [[int(t) for t in ids[0, :n]] for n in (17, 40, 100)]
    for p in prompts:
        serve.submit(p, max_new_tokens=4)
    serve.scheduler.run_until_idle()

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(OUT, profiler_options=options)
    for w in range(2):
        with jax.profiler.TraceAnnotation("bench.submit"):
            loss = engine.train_batch(it)
        with jax.profiler.TraceAnnotation("bench.readback"):
            float(loss)
    for p in prompts:
        with jax.profiler.TraceAnnotation("bench.stage"):
            serve.submit(p, max_new_tokens=6)
    for _ in range(8):
        with jax.profiler.TraceAnnotation("bench.step"):
            serve.scheduler.step()
    jax.profiler.stop_trace()
    engine.close_data_pipeline()
    serve.close()

    found = glob.glob(os.path.join(OUT, "plugins", "profile", "*", "*.xplane.pb"))
    print("trace files", found, [os.path.getsize(f) for f in found], flush=True)
    shutil.copy(found[0], os.path.join(OUT, "probe.xplane.pb"))
    shutil.rmtree(os.path.join(OUT, "plugins"))
    with open(os.path.join(OUT, "structure.json"), "w") as fd:
        json.dump(dump_structure(os.path.join(OUT, "probe.xplane.pb")), fd, indent=1)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    sys.exit(main())
