"""Each plain reference against the program's own model, at a toy size in
float32 on the CPU: the same weights and rows give the same loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, program
from benchmark.reference import ops

CELLS = {"gpt2-large": "gpt2-large.train-seq1024",
         "bert-large": "bert-large.pretrain-seq128"}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reference_equals_program_model_in_float32(name):
    config = harness.load_json("configs", name + ".json")
    cell = harness.load_json("workloads", CELLS[name] + ".json")
    cell.update(cell["toy"])
    size = harness.sizes(config, True)
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    batch = next(gen.micro_batches(5, cell, size))
    params = ref.init_params(ops.seed_key(5), size)
    model = program.model(config, size, {"use_flash": False})
    with jax.default_matmul_precision("highest"):
        theirs = float(model.apply(
            {"params": program.to_tree(config, params)},
            *program.feed(config, batch), train=False))
    sums = ref.loss_sums(params, batch, size, ops.make_dot("float32"))
    ours = float(sum(s / c for s, c in zip(sums, ref.counts(batch))))
    assert ours == pytest.approx(theirs, rel=2e-6)


def test_every_program_leaf_is_mapped():
    for name in CELLS:
        config = harness.load_json("configs", name + ".json")
        size = harness.sizes(config, True)
        ref = harness.plugin("reference", config["reference"])
        tree = program.to_tree(config, ref.shapes(size))
        assert set(program.from_tree(config, tree)) == set(ref.shapes(size))


def test_seeded_weights_are_exact_in_bfloat16_and_take_large_seeds():
    a = ops.seeded_normals(ops.seed_key(2 ** 31 + 5), {"w": (64, 8), "g": (8,)}, 0.02, {"g": 1.0})
    b = ops.seeded_normals(ops.seed_key(5), {"w": (64, 8), "g": (8,)}, 0.02, {"g": 1.0})
    assert not np.array_equal(a["w"], b["w"])
    for x in a.values():
        assert np.array_equal(x, x.astype(jnp.bfloat16).astype(jnp.float32))
    assert abs(float(a["g"].mean()) - 1.0) < 0.05


def test_lower_precision_dot_rounds_both_passes():
    x = jnp.linspace(-1, 1, 24).reshape(4, 6)
    w = jnp.linspace(-2, 3, 12).reshape(6, 2)
    exact, low = ops.make_dot("float32"), ops.make_dot("fp8")
    assert not np.allclose(exact(x, w, ops.X_W), low(x, w, ops.X_W), rtol=1e-4)
    ge = jax.grad(lambda a: jnp.sum(exact(a, w, ops.X_W) ** 2))(x)
    gl = jax.grad(lambda a: jnp.sum(low(a, w, ops.X_W) ** 2))(x)
    assert np.allclose(ge, gl, rtol=0.3) and not np.allclose(ge, gl, rtol=1e-4)
