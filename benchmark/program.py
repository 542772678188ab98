"""The seam between the benchmark and the program under test.

Everything the benchmark takes from ``deepspeed_tpu`` is taken here: the
model and config classes that a configuration file names, the two entry
points ``initialize()`` and ``init_inference()``, and readers of the
training engine's state (first moments, parameters, masters) for the
comparison with the reference. The references import nothing of this.
"""

import importlib

import numpy as np

from .reference.train import leaf_norms


def _attr(spec):
    module, name = spec.split(":")
    return getattr(importlib.import_module(module), name)


def to_tree(config, ref_params):
    """The reference's flat {name: array} as the program's parameter tree
    (``program.params`` of the configuration file: name -> path)."""
    tree = {}
    for name, path in config["program"]["params"].items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = ref_params[name]
    return tree


def from_tree(config, tree):
    out = {}
    for name, path in config["program"]["params"].items():
        node = tree
        for key in path:
            node = node[key]
        out[name] = node
    return out


def model(config, size, extra):
    """The program's model at this configuration's sizes."""
    prog = config["program"]
    kwargs = {arg: size[key] for arg, key in prog["config_args"].items()}
    kwargs.update(extra)
    return _attr(prog["model"])(_attr(prog["config"])(**kwargs))


def feed(config, batch):
    """A batch {name: array} in the order the program's model takes it."""
    return tuple(batch[k] for k in config["program"]["feed"])


def build_train(config, cell, size, ref_params, devices):
    """``deepspeed_tpu.initialize()`` under the configuration's training
    recipe at the cell's micro-batch and accumulation."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    recipe = config["train"]
    engine_config = dict(recipe["engine"])
    engine_config.update(
        train_micro_batch_size_per_gpu=cell["micro"],
        gradient_accumulation_steps=cell["accum"],
        compile_cache={"enabled": True, "min_compile_time_secs": 0.0},
    )
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model(config, size, recipe["model_args"]),
        model_parameters=to_tree(config, ref_params),
        config_params=engine_config,
        mesh=build_mesh(devices=list(devices)),
    )
    return engine


def build_serve(config, cell, size, ref_params, devices):
    """``deepspeed_tpu.init_inference()`` under the configuration's serving
    recipe at the cell's slots, lengths and pool."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    recipe = config["serve"]
    inference = dict(recipe["engine"]["inference"])
    inference.update(cell["engine"])
    return deepspeed_tpu.init_inference(
        model=model(config, size, recipe["model_args"]),
        model_parameters=to_tree(config, ref_params),
        config={"inference": inference,
                "compile_cache": {"enabled": True,
                                  "min_compile_time_secs": 0.0}},
        mesh=build_mesh(devices=list(devices)),
    )


def pages_in_use(engine):
    """KV pages that live requests hold in the serving engine's pool."""
    return engine.block_pool.used_blocks if engine.block_pool else 0


def close_train(engine):
    engine.close_data_pipeline()
    engine.telemetry.close()


def _inner(state):
    return state["inner"] if "inner" in state else state


def first_moment_norms(config, ref, engine, b1):
    """Per-leaf norms of the gradient the optimizer got in the engine's
    FIRST step, from its state after that step: mu_1 = (1 - b1) g_1, so
    g_1 = mu_1 / (1 - b1). Decoded with the program's own decoder, whatever
    format the moments are stored in."""
    import jax

    from deepspeed_tpu.ops.quant import decode_moment, moment_is_leaf

    def norms(mu, params):
        g = jax.tree_util.tree_map(
            lambda m, p: decode_moment(m, p.shape) / (1.0 - b1),
            mu, params, is_leaf=moment_is_leaf)
        return leaf_norms(ref, from_tree(config, g))

    out = jax.jit(norms)(_inner(engine.optimizer_state)["mu"], engine.params)
    return {k: np.asarray(v) for k, v in out.items()}


def _masters(params, state):
    """The engine's parameters at full precision, wherever it keeps them."""
    import jax

    if "master" in state:
        return state["master"]
    if "comp" in _inner(state):
        from deepspeed_tpu.ops.quant import decode_master

        return jax.tree_util.tree_map(
            decode_master, params, _inner(state)["comp"])
    return params


def change_norms(config, ref, engine, start_fn, key):
    """Per-leaf norms of (the engine's parameters now) - (the parameters it
    was given); ``start_fn(key)`` makes those again from the seed inside
    the same program, so no copy is kept on the device through the steps."""
    import jax

    def norms(params, state, key):
        start = start_fn(key)
        flat = from_tree(config, _masters(params, state))
        return leaf_norms(ref, {k: flat[k] - start[k] for k in flat})

    out = jax.jit(norms)(engine.params, engine.optimizer_state, key)
    return {k: np.asarray(v) for k, v in out.items()}
