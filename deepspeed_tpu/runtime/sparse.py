"""Row-sparse (CSR-style) gradients for embedding tables.

Reference: deepspeed/pt/deepspeed_csr_tensor.py (CSRTensor: nonzero-row
indices + values, densify via scatter-add) and the engine's sparse
allreduce (deepspeed_light.py:1037-1093: size-padded all_gather of
indices/values across data-parallel ranks, then densify locally) — used to
cut communication volume for huge, sparsely-touched embedding tables.

TPU-first differences:
  * XLA traces once with static shapes, so the nonzero-row extraction is
    *capacity-bounded*: ``CSRTensor.from_dense(x, max_rows=k)`` keeps the
    top-k rows by presence (any k >= actual nnz rows is lossless) and pads
    the rest with id 0 / zero values (zero values make padding a harmless
    scatter-add no-op).
  * The cross-rank reduction is ``sparse_all_reduce`` — an
    ``all_gather`` of the (already fixed-size) index/value buffers over the
    data axis followed by a local scatter-add densify. Traffic is
    world*k*(cols+1) instead of rows*cols: a win whenever
    k << rows / world. It composes inside ``shard_map``; under plain GSPMD
    jit, dense ``psum`` is already optimal for dense grads, so this path is
    opt-in (``sparse_gradients`` config; reference deepspeed_light.py:177-184).
"""

import jax
import jax.numpy as jnp

from ..config import constants as C


class CSRTensor:
    """Row-sparse view of a [rows, cols] array (reference CSRTensor,
    deepspeed_csr_tensor.py:11-59). ``indices`` [k] row ids, ``values``
    [k, cols] rows; padding entries have zero values (id irrelevant)."""

    def __init__(self, indices=None, values=None, dense_size=None):
        self.indices = indices
        self.values = values
        self.dense_size = list(dense_size) if dense_size is not None else None

    @staticmethod
    def type():
        return "deepspeed.CSRTensor"

    @classmethod
    def from_dense(cls, dense, max_rows=None):
        """Capacity-bounded nonzero-row extraction (jit-compatible).

        ``max_rows`` defaults to the full row count (always lossless);
        smaller values bound memory/traffic and are lossless as long as at
        most ``max_rows`` rows are nonzero.
        """
        rows, _ = dense.shape
        k = rows if max_rows is None else min(max_rows, rows)
        presence = jnp.sum(jnp.abs(dense), axis=1)
        # top-k by presence; zero-presence rows may fill slack slots but
        # their values are zero, so densify is unaffected
        _, idx = jax.lax.top_k(presence, k)
        vals = jnp.take(dense, idx, axis=0)
        keep = (presence[idx] > 0)[:, None]
        vals = jnp.where(keep, vals, 0)
        obj = cls(indices=idx, values=vals, dense_size=dense.shape)
        obj.orig_dense_tensor = dense
        return obj

    def to_dense(self):
        out = jnp.zeros(self.dense_size, self.values.dtype)
        return out.at[self.indices].add(self.values)

    def sparse_size(self):
        index_size = int(self.indices.shape[0])
        value_size = int(self.values.shape[0] * self.values.shape[1])
        dense_size = int(self.dense_size[0] * self.dense_size[1])
        return index_size + value_size, dense_size

    def add(self, other):
        assert self.dense_size == other.dense_size, "dense sizes must match"
        self.indices = jnp.concatenate([self.indices, other.indices])
        self.values = jnp.concatenate([self.values, other.values])

    def __repr__(self):
        sparse_size, dense_size = self.sparse_size()
        return (
            f"deepspeed_tpu.CSRTensor(indices_size={self.indices.shape}, "
            f"values_size={self.values.shape}, dense_size={self.dense_size}, "
            f"reduction_factor={dense_size / max(sparse_size, 1):.2f})"
        )


def sparse_all_reduce_local(indices, values, dense_size, axis_name=C.DATA_AXIS):
    """SUM-allreduce a row-sparse gradient across ``axis_name`` — call
    inside shard_map. Gathers every rank's (fixed-size) indices/values and
    scatter-adds into the dense shape (reference csr_allreduce,
    deepspeed_light.py:1050-1093, minus the ragged-size padding dance:
    capacity bounding already fixed the sizes)."""
    all_idx = jax.lax.all_gather(indices, axis_name, axis=0, tiled=True)
    all_val = jax.lax.all_gather(values, axis_name, axis=0, tiled=True)
    out = jnp.zeros(tuple(dense_size), values.dtype)
    return out.at[all_idx].add(all_val)


def sparse_all_reduce(csr: CSRTensor, mesh, axis_name=C.DATA_AXIS):
    """Mesh-level wrapper: returns the DENSE summed gradient (replicated
    over ``axis_name``) from per-rank CSRTensors."""
    from jax.sharding import PartitionSpec as P

    def local_fn(idx, val):
        return sparse_all_reduce_local(
            idx, val, csr.dense_size, axis_name=axis_name
        )

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=P(),
        check_vma=False,
    )
    # stack per-rank csr onto a leading axis outside; here indices/values
    # are already global arrays whose leading dim is sharded over the axis
    return fn(csr.indices, csr.values)


def sparse_allreduce_average(csr: CSRTensor, mesh, axis_name=C.DATA_AXIS):
    """Averaged variant (gradient averaging semantics of DP allreduce)."""
    world = dict(mesh.shape).get(axis_name, 1)
    return sparse_all_reduce(csr, mesh, axis_name) / world


# ---------------------------------------------------------------------------
# Sparse-gradient embedding lookup (the engine-side wiring of the CSR path)
# ---------------------------------------------------------------------------
# The reference converts nn.Embedding grads to CSR and reduces them with a
# size-padded all_gather instead of a dense allreduce
# (deepspeed_light.py:177-184 marks the modules, :1037-1093 csr_allreduce).
# Under GSPMD the embedding grad would otherwise be a dense [vocab, H] psum
# over the data axis every step. This lookup's custom VJP replaces that with
# the sparse collective: each data shard contributes its (token ids, output
# cotangents) — the CSR (indices, values) pair, whose sparsity is KNOWN from
# the ids, no nonzero-scan needed — gathered over the data axis and
# scatter-added into the dense table shape on every shard. Traffic is
# world * B_local * S * (H + 1) instead of vocab * H: a win whenever the
# batch touches few vocab rows.
#
# CAVEAT (same as the reference's): the win requires the table's OTHER uses
# to be sparse too. A weight-TIED language-model head (logits = h @ table.T,
# models/gpt2.py / the BERT MLM decoder) produces a fully dense cotangent
# for the same table, so the dense reduction still runs and this path only
# adds traffic. The reference's CSR machinery likewise targeted untied
# embedding-bag models (deepspeed_light.py:177-184 converts nn.Embedding
# only). Enable ``sparse_gradients`` for untied tables.
import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sparse_lookup(table, ids, mesh, axis_name):
    return jnp.take(table, ids, axis=0)


def _sparse_lookup_fwd(table, ids, mesh, axis_name):
    # residuals must be arrays: a zero-width slice carries the table's row
    # count and dtype without holding any data
    marker = jnp.zeros((table.shape[0], 0), table.dtype)
    return jnp.take(table, ids, axis=0), (ids, marker)


def _sparse_lookup_bwd(mesh, axis_name, residuals, g):
    import numpy as np

    ids, marker = residuals
    table_shape = (marker.shape[0], g.shape[-1])
    dtype = marker.dtype
    flat_ids = ids.reshape(-1)
    flat_g = g.reshape(-1, g.shape[-1])

    csr = CSRTensor(indices=flat_ids, values=flat_g, dense_size=table_shape)
    dtable = sparse_all_reduce(csr, mesh, axis_name=axis_name)
    # integer primal -> float0 cotangent
    return dtable.astype(dtype), np.zeros(ids.shape, jax.dtypes.float0)


_sparse_lookup.defvjp(_sparse_lookup_fwd, _sparse_lookup_bwd)


def sparse_embedding_lookup(table, ids, mesh=None, axis_name=C.DATA_AXIS):
    """``table[ids]`` whose gradient flows through the sparse all-reduce
    when a data-parallel mesh is supplied (the ``sparse_gradients`` config
    path); plain gather (XLA scatter-add grad + dense psum) otherwise."""
    import math

    dp = 1 if mesh is None else dict(mesh.shape).get(axis_name, 1)
    if dp <= 1 or math.prod(ids.shape) % dp != 0:
        return jnp.take(table, ids, axis=0)
    return _sparse_lookup(table, ids, mesh, axis_name)
