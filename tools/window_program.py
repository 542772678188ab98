"""What a cell's fused training window lowers to, as one hash: a change that
says it BYPASSES a cell shows it by printing the same line on both commits.

    JAX_PLATFORMS=cpu python3 tools/window_program.py [cell ...]

For each cell (default: every training cell) the window at the cell's real
sizes is lowered for a described TPU v5e (no chip, nothing compiled:
``benchmark/compile_described.py``'s stand-ins). The StableHLO text carries
no locations; every Mosaic kernel in it is bytecode whose debug locations
hold file paths and line numbers, so each is decoded, printed without them
and replaced by the sha256 of that text. One JSON line a cell: the sha256 of
the whole, and the kernels by name with their own hashes."""
import base64, hashlib, json, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp
from jax._src.interpreters import mlir
from jax._src.lib import tpu
from jax._src.lib.mlir import ir
from jax.experimental import topologies

from benchmark import compile_described as cd, harness, program

BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def lowered_window(cell, config, devices):
    from deepspeed_tpu.runtime.engine import _split_window_keys

    size = harness.sizes(config, False)
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    with cd.described(devices):
        engine = program.build_train(
            config, cell, size, cd.zeros_like_shapes(ref, size), devices)
        batch = program.feed(config, next(gen.micro_batches(0, cell, size)))
        stacked = engine._shard_window_batch(
            engine._stack_window([batch] * cell["accum"]))
        _, keys = _split_window_keys(engine._rng, cell["accum"])
        text = engine._jit_train_window.lower(
            cd.shapes_of(engine.params), cd.shapes_of(engine.optimizer_state),
            cd.shapes_of(engine.loss_scale_state), cd.shapes_of(stacked),
            cd.shapes_of(keys), jnp.float32(1e-4), jnp.float32(0.9)).as_text()
        program.close_train(engine)
    return text


def mosaic_assembly(body):
    """``(kernel name, assembly without debug locations)`` of one Mosaic
    module's base64 bytecode (group 2 of ``BODY``)."""
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(body)
                              ).operation.get_asm(enable_debug_info=False)
    return re.match(r"module @(\w+)", asm).group(1), asm


def without_locations(text):
    """``text`` with each Mosaic module's bytecode replaced by the hash of
    its assembly without debug locations, and {kernel: [hash, ...]}."""
    kernels = {}

    def decoded(match):
        name, asm = mosaic_assembly(match.group(2))
        digest = hashlib.sha256(asm.encode()).hexdigest()
        kernels.setdefault(name, []).append(digest[:16])
        return match.group(1) + digest + match.group(3)

    return BODY.sub(decoded, text), kernels


def main(names):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = harness.load_benchmark()
    for name in names or [w["name"] for w in bench["workloads"]]:
        cell = harness.load_json("workloads", name + ".json")
        if cell["loop"] != "train":
            continue
        config = harness.load_json("configs", cell["config"] + ".json")
        text, kernels = without_locations(
            lowered_window(cell, config, topo.devices[:cell["chips"]]))
        print(json.dumps({
            "cell": name, "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "bytes": len(text), "kernels": kernels}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
