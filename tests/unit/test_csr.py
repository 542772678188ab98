"""CSR row-sparse tensor tests (reference tests/unit/test_csr.py:
round-trip; plus the TPU additions: capacity bounding and the sharded
sparse allreduce)."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.sparse import (
    CSRTensor,
    sparse_all_reduce_local,
    sparse_allreduce_average,
)


def _sparse_dense(rows=32, cols=8, nnz=5, seed=0):
    rng = np.random.default_rng(seed)
    dense = np.zeros((rows, cols), np.float32)
    idx = rng.choice(rows, nnz, replace=False)
    dense[idx] = rng.standard_normal((nnz, cols))
    return jnp.asarray(dense)


def test_csr_roundtrip():
    dense = _sparse_dense()
    csr = CSRTensor.from_dense(dense)
    np.testing.assert_array_equal(np.asarray(csr.to_dense()), np.asarray(dense))


def test_csr_capacity_bounded_roundtrip():
    dense = _sparse_dense(nnz=5)
    csr = CSRTensor.from_dense(dense, max_rows=8)  # capacity > nnz: lossless
    assert csr.values.shape == (8, 8)
    np.testing.assert_array_equal(np.asarray(csr.to_dense()), np.asarray(dense))


def test_csr_add_concatenates():
    a = CSRTensor.from_dense(_sparse_dense(seed=0), max_rows=4)
    b = CSRTensor.from_dense(_sparse_dense(seed=1), max_rows=4)
    expect = np.asarray(a.to_dense()) + np.asarray(b.to_dense())
    a.add(b)
    np.testing.assert_allclose(np.asarray(a.to_dense()), expect, rtol=1e-6)


def test_csr_reduction_factor_reported():
    csr = CSRTensor.from_dense(_sparse_dense(rows=64, nnz=4), max_rows=4)
    sparse_size, dense_size = csr.sparse_size()
    assert dense_size == 64 * 8
    assert sparse_size == 4 + 4 * 8
    assert "reduction_factor" in repr(csr)


def test_sparse_all_reduce_matches_dense_psum():
    mesh = build_mesh(data_parallel_size=8)
    # one distinct sparse grad per rank: global leading dim 8*k
    per_rank = [
        CSRTensor.from_dense(_sparse_dense(seed=s), max_rows=6) for s in range(8)
    ]
    glob = CSRTensor(
        indices=jnp.concatenate([c.indices for c in per_rank]),
        values=jnp.concatenate([c.values for c in per_rank]),
        dense_size=per_rank[0].dense_size,
    )
    out = sparse_allreduce_average(glob, mesh)
    expect = np.mean(
        [np.asarray(c.to_dense()) for c in per_rank], axis=0
    )
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6, atol=1e-7)


def test_sparse_all_reduce_local_inside_jit():
    mesh = build_mesh(data_parallel_size=8)
    dense = _sparse_dense()
    csr = CSRTensor.from_dense(dense, max_rows=6)
    # replicate the same csr on all ranks: sum = 8x single
    idx = jnp.tile(csr.indices, 8)
    val = jnp.tile(csr.values, (8, 1))
    from jax.sharding import PartitionSpec as P

    fn = jax.jit(
        jax.shard_map(
            lambda i, v: sparse_all_reduce_local(i, v, csr.dense_size),
            mesh=mesh,
            in_specs=(P("data"), P("data")),
            out_specs=P(),
            check_vma=False,
        )
    )
    out = fn(idx, val)
    np.testing.assert_allclose(
        np.asarray(out), 8 * np.asarray(dense), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# Engine wiring: sparse_gradients config routes embedding grads through the
# sparse all-reduce (reference deepspeed_light.py:177-184, 1037-1093)
# ---------------------------------------------------------------------------
def test_sparse_embedding_lookup_grad_matches_dense():
    from deepspeed_tpu.runtime.sparse import sparse_embedding_lookup

    mesh = build_mesh(data_parallel_size=8)
    table = jnp.asarray(np.random.default_rng(0).standard_normal((64, 16)), jnp.float32)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (8, 4)), jnp.int32)
    w = jnp.asarray(np.random.default_rng(2).standard_normal((8, 4, 16)), jnp.float32)

    def loss_sparse(t):
        return jnp.sum(sparse_embedding_lookup(t, ids, mesh) * w)

    def loss_dense(t):
        return jnp.sum(t[ids] * w)

    gs = jax.jit(jax.grad(loss_sparse))(table)
    gd = jax.grad(loss_dense)(table)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gd), atol=1e-5)


def test_engine_sparse_gradients_parity_with_dense():
    """engine config {sparse_gradients: true} must train identically to the
    dense path for a sparsely-touched embedding (engine-level wiring test:
    the engine injects the flag into the model config and the sparse
    collective runs inside the jitted step)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

    def make_engine(sparse):
        cfg = GPT2Config(
            vocab_size=256, n_positions=16, n_embd=32, n_layer=1, n_head=2,
            dropout=0.0,
        )
        model = GPT2LMHeadModel(cfg)
        ids0 = jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 16)), jnp.int32)
        params = model.init(
            {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
            ids0, ids0,
        )["params"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            model_parameters=params,
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "sparse_gradients": sparse,
                "steps_per_print": 10_000,
            },
            rng_seed=0,
        )
        if sparse:
            assert model.config.sparse_gradients, "engine did not inject flag"
            assert model.config.mesh is not None, "engine did not inject mesh"
        return engine

    rng = np.random.default_rng(7)
    batches = [rng.integers(0, 256, (8, 16)).astype(np.int32) for _ in range(5)]

    losses = {}
    params = {}
    for sparse in (False, True):
        e = make_engine(sparse)
        ls = []
        for ids in batches:
            loss = e(ids, ids)
            e.backward(loss)
            e.step()
            ls.append(float(loss))
        losses[sparse] = ls
        params[sparse] = jax.tree_util.tree_map(np.asarray, e.params)
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(params[True]),
        jax.tree_util.tree_leaves(params[False]),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)
