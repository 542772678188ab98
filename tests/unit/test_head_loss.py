"""The blocked head loss's gradient, taken in its forward (ops/
cross_entropy.py): both public functions against the plain materialised
computation over the shapes their callers send, the scalar that the backward
multiplies by, the count of products a chunk, and the batch sharded over a
mesh. The older tests of the same functions are beside this file
(test_memory_savers.py, marked slow; test_looped_stack.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.cross_entropy import (
    blocked_lm_head_loss,
    weighted_lm_head_loss,
)

VOCAB, WIDTH = 96, 32


def plain(states, table, weights, labels, ignore_values):
    """Per-position softmax cross-entropy written out, in float32."""
    states, table = states.astype(jnp.float32), table.astype(jnp.float32)
    logp = jax.nn.log_softmax(states @ table.T, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[None, ..., None], axis=-1)[..., 0]
    counted = jnp.ones(labels.shape, bool)
    for value in ignore_values:
        counted &= labels != value
    return (jnp.sum(jnp.sum(weights * nll, 0) * counted)
            / jnp.maximum(jnp.sum(counted), 1))


def blocked(states, table, weights, labels, ignore_values, block):
    """The function its caller would take: the plain one where it can."""
    if weights is None:
        return blocked_lm_head_loss(
            states[0], table, labels, block_rows=block,
            ignore_values=ignore_values)
    return weighted_lm_head_loss(
        states, table, labels, weights, block_rows=block,
        ignore_values=ignore_values)


def inputs(passes, seq, dtype, seed=0, batch=2):
    rng = np.random.default_rng(seed)
    states = jnp.asarray(rng.normal(size=(passes, batch, seq, WIDTH)), dtype)
    table = jnp.asarray(rng.normal(size=(VOCAB, WIDTH)) * 0.2, dtype)
    labels = jnp.asarray(rng.integers(0, VOCAB, (batch, seq)), jnp.int32)
    weights = jnp.asarray(
        rng.uniform(0.1, 2.0, size=(passes, batch, seq)), jnp.float32)
    return states, table, weights, labels


# (R, weighted, T, block, what the labels hold, ignore_values)
CASES = {
    "plain-padded-ignored": (1, False, 40, 16, "some", (-1, -100)),
    "plain-one-block": (1, False, 24, 512, "none", (-1, -100)),
    "plain-all-ignored": (1, False, 40, 16, "all", (-1, -100)),
    "plain-count-every-label": (1, False, 13, 8, "zeros", ()),
    "one-pass-weighted": (1, True, 40, 16, "some", (-1, -100)),
    "four-passes-padded": (4, True, 40, 16, "some", (-1, -100)),
    "four-passes-dividing": (4, True, 32, 16, "none", (-1, -100)),
    "four-passes-all-ignored": (4, True, 24, 16, "all", (-1, -100)),
    "block-diffusion": (1, True, 40, 16, "zeros", ()),
}


def case(name, dtype):
    passes, weighted, seq, block, holds, ignore_values = CASES[name]
    states, table, weights, labels = inputs(passes, seq, dtype)
    if holds == "some":
        labels = labels.at[1, 3].set(-100).at[0, -1].set(-1)
    elif holds == "all":
        labels = jnp.full_like(labels, -100)
    elif holds == "zeros":        # real label-0 targets, and padding of 0
        labels = labels.at[:, :3].set(0)

    def want(states, table, weights):
        return plain(states, table, weights, labels, ignore_values)

    def got(states, table, weights):
        return blocked(states, table, weights if weighted else None, labels,
                       ignore_values, block)

    if not weighted:
        weights = jnp.ones_like(weights)
    return want, got, (states, table, weights), weighted


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(CASES))
def test_gradient_is_the_plain_computations(name, dtype):
    """Loss and gradient to the states, the table and (weighted) the weights
    against float32 autodiff of the materialised computation: to 1e-5 in
    float32; in bfloat16 to the rounding of the logits plane and of ``g``
    (2^-8 of each array's largest entry, a few roundings added up)."""
    want, got, args, weighted = case(name, dtype)
    argnums = (0, 1, 2) if weighted else (0, 1)
    loss, grads = jax.value_and_grad(got, argnums)(*args)
    ref_loss, ref_grads = jax.value_and_grad(want, argnums)(*args)
    assert np.isfinite(np.asarray(loss, np.float32))
    exact = dtype == jnp.float32
    np.testing.assert_allclose(
        loss, ref_loss, rtol=1e-5 if exact else 2e-2, atol=1e-6)
    for g, ref, arg in zip(grads, ref_grads, args):
        assert g.shape == arg.shape and g.dtype == arg.dtype
        g, ref = np.asarray(g, np.float32), np.asarray(ref, np.float32)
        assert np.isfinite(g).all()
        if CASES[name][4] == "all":
            assert not g.any()            # den = 0: zeros, not 0 / 0
        np.testing.assert_allclose(
            g, ref, rtol=0, atol=1e-5 if exact else
            2.0 ** -6 * max(np.abs(ref).max(), 1e-3))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", ["plain-padded-ignored",
                                  "four-passes-padded"])
def test_a_loss_scale_scales_the_three_gradients_exactly(name, dtype):
    """What the backward adds is a scalar: the cotangent an fp16 scaler sends
    (loss x 2^15) comes out of all three gradients as 2^15, to the bit."""
    _want, got, args, weighted = case(name, dtype)
    argnums = (0, 1, 2) if weighted else (0, 1)
    grads = jax.grad(got, argnums)(*args)
    scaled = jax.grad(lambda *a: got(*a) * 2.0 ** 15, argnums)(*args)
    for g, s in zip(grads, scaled):
        assert np.asarray(g, np.float32).any()
        np.testing.assert_array_equal(
            np.asarray(s, np.float32), np.asarray(g, np.float32) * 2.0 ** 15)


def primitives(jaxpr, under=()):
    """[(primitive, the primitives around it)] of a jaxpr and every jaxpr
    among its equations' parameters."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, under))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += primitives(sub, under + (eqn.primitive.name,))
    return out


@pytest.mark.parametrize("name", ["plain-padded-ignored",
                                  "four-passes-padded", "block-diffusion"])
def test_a_chunk_runs_three_products_and_none_again(name):
    """The counter of ISSUE 42: the gradient's program holds 3
    ``dot_general``s, all in the one scan over the blocks (a checkpointed
    chunk ran 4: its product once more in the backward), nothing under a
    checkpoint, and the backward no scan at all; asked for its value alone
    the loss runs 1."""
    _want, got, args, weighted = case(name, jnp.bfloat16)
    argnums = (0, 1, 2) if weighted else (0, 1)

    def products(fn):
        found = primitives(jax.make_jaxpr(fn)(*args).jaxpr)
        assert not [p for p, under in found if "checkpoint" in (p,) + under
                    or "remat" in p]
        assert sum(p == "scan" for p, _ in found) == 1
        dots = [under for p, under in found if p == "dot_general"]
        assert all("scan" in under for under in dots)
        return len(dots)

    assert products(jax.grad(got, argnums)) == 3
    assert products(got) == 1
    # forward-only callers: a value's shape asks for no gradient either
    assert jax.eval_shape(got, *args).shape == ()


@pytest.mark.parametrize("name", ["plain-padded-ignored",
                                  "four-passes-padded"])
def test_gradients_under_a_sharded_batch_are_the_unsharded_ones(name):
    """Four CPU devices, the batch over the ``data`` axis as the dp engine
    shards it, the table replicated: the table's gradient contracts over the
    sharded batch inside the forward's scan and still comes out whole."""
    passes, weighted, seq, block, _holds, ignore_values = CASES[name]
    states, table, weights, labels = inputs(
        passes, seq, jnp.float32, seed=5, batch=4)
    labels = labels.at[3, 2].set(-100)
    argnums = (0, 1, 2) if weighted else (0, 1)

    def loss(states, table, weights, labels):
        return blocked(states, table, weights if weighted else None, labels,
                       ignore_values, block)

    want = jax.value_and_grad(loss, argnums)(states, table, weights, labels)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    rows = NamedSharding(mesh, P(None, "data"))
    whole = NamedSharding(mesh, P())
    placed = (jax.device_put(states, rows), jax.device_put(table, whole),
              jax.device_put(weights, rows),
              jax.device_put(labels, NamedSharding(mesh, P("data"))))
    got = jax.jit(jax.value_and_grad(loss, argnums))(*placed)
    assert got[1][0].sharding.is_equivalent_to(rows, states.ndim)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
