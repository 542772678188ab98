"""Compile, for a described TPU v5e that is not attached, every program the
cells run at real size, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmark/compile_described.py [cell ...]

A training cell's two peaks end side by side on one ``peaks`` line: the
reference's gradient step (the follower's own function, ``reference/
train.py:block_step``: what decides whether ``correct`` can be read) and the
program's window. Hold ``peak_GiB`` against the chip's 15.75 GiB yourself:
the compiler ACCEPTS some programs that read above it, and the chip then
refuses to load them (PERF.md, section 6, PR 49: 15.87 was accepted and
failed to load by 0.16 GiB).

Costs no chip time (on-chip-measurement guide, section 2.3). Nothing runs,
so this says whether a program compiles and fits, never how fast it is. The
program builds its mesh from ``jax.devices()`` and places its own arrays, so
this hands it the described devices and shapes in place of arrays: it patches
``jax.devices``/``jax.local_devices``/``jax.device_count`` and
``jax.device_put`` for the length of the build (the recipe in
``.claude/skills/verify/SKILL.md``).
"""

import contextlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import harness, program  # noqa: E402

GIB = 2.0 ** 30


def report(label, compiled, t0, **more):
    m = compiled.memory_analysis()
    print(json.dumps({
        "program": label, "compile_s": round(time.time() - t0, 1), **more,
        "peak_GiB": round(m.peak_memory_in_bytes / GIB, 2),
        "arguments_GiB": round(m.argument_size_in_bytes / GIB, 2),
        "temp_GiB": round(m.temp_size_in_bytes / GIB, 2),
        "output_GiB": round(m.output_size_in_bytes / GIB, 2),
    }), flush=True)
    return compiled


def shapes_of(tree, sharding=None):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=sharding or getattr(x, "sharding", None)), tree)


class Shape(jax.ShapeDtypeStruct):
    """A shape standing in for an array on a described device."""

    @property
    def nbytes(self):
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize


@contextlib.contextmanager
def described(devices):
    """jax as the program sees it, with the described devices attached."""
    real_put = jax.device_put
    saved = (jax.devices, jax.local_devices, jax.device_count,
             jax.local_device_count)

    def put(x, device=None, **kw):
        def one(leaf, sh):
            if sh is None or not hasattr(sh, "device_set") or not (
                    set(sh.device_set) & set(devices)):
                return real_put(leaf, sh, **kw)
            return Shape(
                np.shape(leaf), getattr(leaf, "dtype", np.asarray(leaf).dtype),
                sharding=sh)
        if isinstance(device, (dict, list, tuple)) or (
                device is not None and not hasattr(device, "device_set")
                and not hasattr(device, "platform")):
            return jax.tree_util.tree_map(one, x, device)
        if device is not None and hasattr(device, "platform"):
            device = SingleDeviceSharding(device)
        return jax.tree_util.tree_map(lambda leaf: one(leaf, device), x)

    jax.devices = lambda *a, **k: list(devices)
    jax.local_devices = lambda *a, **k: list(devices)
    jax.device_count = lambda *a, **k: len(devices)
    jax.local_device_count = lambda *a, **k: len(devices)
    jax.device_put = put
    try:
        yield
    finally:
        (jax.devices, jax.local_devices, jax.device_count,
         jax.local_device_count) = saved
        jax.device_put = real_put


def reference_programs(cell, config, size, one_chip):
    """The reference's gradient step (training cells) or forward pass
    (serving cells), in float32 at the cell's own block of rows."""
    from benchmark.reference import ops, train

    ref = harness.plugin("reference", config["reference"])
    dot = ops.make_dot("float32")
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for k, s in ref.shapes(size).items()}
    if cell["loop"] == "serve":
        length = cell["engine"]["prefill_len"] + cell["traffic"]["output_tokens"]["max"]
        toks = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)
        t0 = time.time()
        return report(f"{cell['name']}: reference forward, 1 x {length}",
                      jax.jit(lambda p, t: ref.logits(p, t, size, dot)).lower(
                          params, toks).compile(), t0)
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    batch = next(gen.micro_batches(0, dict(cell, chips=1), size))
    rows = {k: jax.ShapeDtypeStruct(
        (cell["check"]["block_rows"],) + v.shape[1:], v.dtype, sharding=one_chip)
        for k, v in batch.items()}
    n_terms = len(ref.counts(batch))
    weights = tuple(jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
                    for _ in range(n_terms))

    t0 = time.time()
    return report(
        f"{cell['name']}: reference gradient step, "
        f"{cell['check']['block_rows']} rows (params + sum + one gradient)",
        train.block_step(ref, size, dot).lower(
            params, params, rows, weights).compile(), t0,
        parameters_M=round(sum(
            int(np.prod(s)) for s in ref.shapes(size).values()) / 1e6, 1))


def zeros_like_shapes(ref, size):
    """Host arrays of the right shapes for the program to copy and cast;
    their values never reach a device."""
    return {k: np.zeros(s, np.float32) for k, s in ref.shapes(size).items()}


def program_window(cell, config, size, devices):
    """The program's fused training window at the cell's sizes, lowered from
    the engine's own jitted function and the shapes of its own state."""
    from deepspeed_tpu.runtime.engine import _split_window_keys

    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    with described(devices):
        engine = program.build_train(
            config, cell, size, zeros_like_shapes(ref, size), devices)
        batch = program.feed(config, next(gen.micro_batches(0, cell, size)))
        stacked = engine._shard_window_batch(
            engine._stack_window([batch] * cell["accum"]))
        _, keys = _split_window_keys(engine._rng, cell["accum"])
        t0 = time.time()
        lowered = engine._jit_train_window.lower(
            shapes_of(engine.params), shapes_of(engine.optimizer_state),
            shapes_of(engine.loss_scale_state), shapes_of(stacked),
            shapes_of(keys), jnp.float32(1e-4), jnp.float32(0.9))
        compiled = report(
            f"{cell['name']}: fused training window, micro {cell['micro']} x "
            f"accum {cell['accum']} on {len(devices)} chip(s), per chip",
            lowered.compile(), t0)
        if len(devices) > 1:
            hlo = compiled.as_text()
            print(json.dumps({"collectives in the compiled window": {
                op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                for op in ("all-reduce", "all-gather", "reduce-scatter",
                           "collective-permute")}}), flush=True)
        program.close_train(engine)
    return compiled


def program_serving(cell, config, size, devices):
    """The serving engine's decode step and padded prefill at the cell's
    slots, lengths and pool."""
    ref = harness.plugin("reference", config["reference"])
    sh = SingleDeviceSharding(devices[0])
    with described(devices):
        engine = program.build_serve(
            config, cell, size, zeros_like_shapes(ref, size), devices)
        slots = cell["engine"]["max_batch_slots"]
        params = shapes_of(engine.params, sh)
        ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=sh)
        t0 = time.time()
        report(
            f"{cell['name']}: decode step, {slots} slots, pool of "
            f"{cell['engine']['kv_pool_blocks']} pages",
            engine._jit_decode.lower(
                params, ints, ints,
                jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=sh),
                shapes_of(engine._key, sh), shapes_of(engine._cache, sh),
                shapes_of(jnp.asarray(engine._block_tables), sh)).compile(), t0)
        t0 = time.time()
        report(
            f"{cell['name']}: prefill, 1 x {cell['engine']['prefill_len']}",
            engine._jit_prefill.lower(params, jax.ShapeDtypeStruct(
                (1, cell["engine"]["prefill_len"]), jnp.int32,
                sharding=sh)).compile(), t0)
        engine.close()


def main(argv):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = harness.load_benchmark()
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = harness.load_json("workloads", name + ".json")
        config = harness.load_json("configs", cell["config"] + ".json")
        size = harness.sizes(config, False)
        reference = reference_programs(cell, config, size, one_chip)
        if cell["loop"] == "train":
            window = program_window(
                cell, config, size, topo.devices[:cell["chips"]])
            print(json.dumps({"peaks": name, **{
                label: round(c.memory_analysis().peak_memory_in_bytes / GIB, 2)
                for label, c in (("reference_gradient_step_GiB", reference),
                                 ("training_window_GiB", window))}}), flush=True)
        elif cell["loop"] == "serve":
            program_serving(cell, config, size, topo.devices[:cell["chips"]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
