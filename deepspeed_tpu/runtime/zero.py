"""ZeRO on a TPU mesh: partitioning as sharding specs.

The reference implements ZeRO with imperative machinery — flattened fp16
buffers split into rank ranges, backward hooks feeding bucketed reductions,
explicit reduce/reduce_scatter/all_gather calls (reference:
deepspeed/pt/deepspeed_zero_optimizer.py:102-1552 for stage 2,
zero_optimizer_stage1.py:112-996 for stage 1). On TPU the same *capability*
collapses into sharding declarations and XLA-inserted collectives:

  stage 0  — grads + optimizer state replicated; XLA all-reduces grads.
  stage 1  — optimizer state (fp32 master moments) sharded over the ``data``
             axis; XLA turns the grad all-reduce feeding the sharded update
             into reduce-scatter + all-gather of the param update
             (the reference's "partition-aware" comm,
             docs/_posts/2020-03-17-reduce-scatter.md).
  stage 2  — gradients ALSO carry the sharded layout (the accumulation
             buffer between micro-steps is stored sharded), so grad memory
             per chip drops by 1/dp and the reduce is a psum_scatter.
  stage 3  — parameters sharded too (the reference only defined the constant
             and raised NotImplementedError, deepspeed_constants.py:167,
             deepspeed_light.py:619-620; on a mesh it is one more spec).

Per-leaf partitioning rule: shard the largest unsharded dimension divisible
by the data-axis size; leaves with no divisible dimension stay replicated
(the reference's analogous edge case is `zero_empty_partition` — more ranks
than elements — tested in tests/unit/test_fp16.py). Engines with int8
moment storage ({'q','scale'} leaves, ops/quant.py) instead prefer the
EARLIEST divisible dimension (``prefer_leading=True``). Since PR 27 that
storage is no longer flat: ``q`` has the parameter's shape and takes the
spec the second moment has, ``scale`` holds one value per run of the minor
axis and per row and follows it (``ops.quant.scale_spec``). A shard cut
along a leading dimension keeps every run and every row whole, so the
update kernel (ops/pallas.py:adam_leaf_update, per shard under
``shard_map``) sees its own leaf in small; a cut through the minor axis is
taken only where whole runs fall to each shard, and the leaf falls back to
the plain XLA update otherwise. No individual tensor is flattened-and-
split, which would fight XLA's tiled memory format.
"""

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import constants as C
from ..parallel import mesh as mesh_lib
from ..telemetry.registry import count_suppressed
from ..utils.logging import warn_once


def has_axis(spec, axis_name=C.DATA_AXIS):
    """True when ``spec`` shards any dim over ``axis_name``."""
    return any(
        axis_name == e or (isinstance(e, tuple) and axis_name in e)
        for e in spec
    )


def strip_axis_entry(entry, axis_name=C.DATA_AXIS):
    """One PartitionSpec entry with ``axis_name`` removed (None / str /
    tuple forms all handled) — the per-dim piece of "this leaf's spec
    minus its ZeRO data sharding"."""
    if entry is None:
        return None
    if isinstance(entry, tuple):
        kept = tuple(e for e in entry if e != axis_name)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return None if entry == axis_name else entry


def gathered_spec(spec, axis_name=C.DATA_AXIS):
    """``spec`` with the data axis stripped from every dim: the layout a
    stage-3 leaf takes while a layer COMPUTES with it (model-parallel
    axes stay sharded; only the ZeRO partition gathers). Constraining a
    sharded leaf to this spec inside jit IS the just-in-time all-gather
    (models/stack.py)."""
    return PartitionSpec(*(strip_axis_entry(e, axis_name) for e in spec))


def leaf_partition_spec(shape, dp_size, axis_name=C.DATA_AXIS, existing_spec=None,
                        prefer_leading=False):
    """Choose the PartitionSpec sharding one dim of ``shape`` over the data axis.

    Respects ``existing_spec`` (e.g. a model-parallel sharding) by only
    placing the data axis on a currently-unsharded dimension.

    ``prefer_leading=True`` picks the EARLIEST divisible dimension instead
    of the largest: shards become contiguous row-major blocks that keep
    the rows and the quantization runs of int8 moment storage whole (see
    module docstring). Engines enable it exactly when such state exists;
    the fp32-state layout (largest dim) keeps the measured single/multi-
    chip memory profile of the AOT proofs.
    """
    existing = tuple(existing_spec) if existing_spec is not None else ()
    existing = existing + (None,) * (len(shape) - len(existing))
    if dp_size <= 1:
        return PartitionSpec(*existing) if existing_spec is not None else PartitionSpec()
    if has_axis(existing, axis_name):
        # already sharded over this axis (e.g. MoE expert weights over the
        # data axis): a spec may not repeat a mesh axis — the leaf is
        # already dp_size-way partitioned, which is what ZeRO wants
        return PartitionSpec(*existing)
    best_dim, best_size = None, 0
    for i, d in enumerate(shape):
        if existing[i] is not None or d % dp_size != 0:
            continue
        if prefer_leading:
            best_dim = i
            break
        if d > best_size:
            best_dim, best_size = i, d
    if best_dim is None:
        return PartitionSpec(*existing) if existing_spec is not None else PartitionSpec()
    new = list(existing)
    new[best_dim] = axis_name
    return PartitionSpec(*new)


def zero_param_specs(params, dp_size, stage, model_specs=None, prefer_leading=False):
    """Partition specs for *parameters* (sharded only at stage 3).

    Stage-3 leaves with NO dp-divisible free dimension stay replicated
    (warned once, never a crash): the analog of the reference's
    ``zero_empty_partition`` edge case — small norms/biases whose dims
    all resist the split simply keep full residency, and the memory
    accounting (engine zero3 gauges) reflects it.
    """

    def spec(path, leaf):
        ms = _lookup(model_specs, path)
        if stage >= C.ZERO_OPTIMIZATION_WEIGHTS:
            out = leaf_partition_spec(
                leaf.shape, dp_size, existing_spec=ms,
                prefer_leading=prefer_leading,
            )
            if (
                dp_size > 1
                and len(leaf.shape) > 0
                and not has_axis(out, C.DATA_AXIS)
            ):
                warn_once(
                    "zero3-replicated-leaves",
                    "ZeRO stage 3: parameter leaf %s %s has no free "
                    "dp%d-divisible dimension — it stays REPLICATED "
                    "(further such leaves are not logged)",
                    "/".join(str(_key_token(k)) for k in path),
                    tuple(leaf.shape), dp_size,
                )
            return out
        return ms if ms is not None else PartitionSpec()

    return _tree_map_with_path(spec, params)


def zero_grad_specs(params, dp_size, stage, model_specs=None, prefer_leading=False):
    """Partition specs for the gradient-accumulation buffer (stage >= 2 shards)."""

    def spec(path, leaf):
        ms = _lookup(model_specs, path)
        if stage >= C.ZERO_OPTIMIZATION_GRADIENTS:
            return leaf_partition_spec(
                leaf.shape, dp_size, existing_spec=ms,
                prefer_leading=prefer_leading,
            )
        return ms if ms is not None else PartitionSpec()

    return _tree_map_with_path(spec, params)


def zero_optstate_specs(params, dp_size, stage, model_specs=None, prefer_leading=False):
    """Partition specs for per-param optimizer state (moments, master copy);
    sharded from stage >= 1."""

    def spec(path, leaf):
        ms = _lookup(model_specs, path)
        if stage >= C.ZERO_OPTIMIZATION_OPTIMIZER_STATES:
            return leaf_partition_spec(
                leaf.shape, dp_size, existing_spec=ms,
                prefer_leading=prefer_leading,
            )
        return ms if ms is not None else PartitionSpec()

    return _tree_map_with_path(spec, params)


def specs_to_shardings(specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def constrain(tree, specs):
    """with_sharding_constraint over a pytree of PartitionSpecs (jit-safe)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s),
        tree,
        specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def optstate_specs_like(opt_state, param_specs, params, axis_sizes=None):
    """Map param specs onto an optax-style optimizer state pytree.

    Optimizer moments (``mu``/``nu``/master copies) are pytrees with the
    *same structure* as ``params``, so each moment leaf's path ends with the
    path of the param it belongs to.  Specs are therefore mapped **by tree
    path** (longest matching path suffix whose shape also matches), which
    keeps two same-shaped params that carry *different* model-parallel specs
    (e.g. an attention out-proj vs an FF matrix under TP) on their own
    layouts — the reference keeps optimizer state strictly per-param too
    (deepspeed/pt/deepspeed_zero_optimizer.py:256-263).

    Quantized moments (``{'q','scale'}`` leaves, ops/quant): ``q`` has its
    parameter's shape and takes its spec; ``scale`` takes the spec
    ``ops.quant.scale_spec`` derives from it, given the mesh's
    ``axis_sizes`` (``dict(mesh.shape)``; without them a cut through the
    minor axis leaves the scales whole).

    A shape-based fallback is used only when it is unambiguous (every param
    of that shape shares one spec); anything else is replicated.
    """
    param_paths = {}
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    for (path, p), s in zip(flat_p, flat_s):
        param_paths[tuple(_key_token(k) for k in path)] = (tuple(p.shape), s)

    shape_to_specs = {}
    for shape, s in param_paths.values():
        shape_to_specs.setdefault(shape, set()).add(s)

    def spec_for(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        toks = tuple(_key_token(k) for k in path)
        if len(toks) >= 2 and toks[-1] in ("q", "scale"):
            # quantized leaf: the PARENT path (without 'q'/'scale')
            # suffix-matches a param the usual way. (A real param that
            # happens to be NAMED 'q' never lands here: its parent prefix
            # is a subtree, not a param path, so this falls through to
            # the normal shape-checked matching below.)
            for i in range(len(toks) - 1):
                hit = param_paths.get(toks[i:-1])
                if hit is not None and toks[-1] == "q" and hit[0] == shape:
                    return hit[1]
                if hit is not None and toks[-1] == "scale":
                    from ..ops.quant import scale_spec

                    return scale_spec(hit[1], hit[0], axis_sizes or {})
        for i in range(len(toks)):  # longest suffix first
            hit = param_paths.get(toks[i:])
            if hit is not None and hit[0] == shape:
                return hit[1]
        cands = shape_to_specs.get(shape)
        if cands is not None and len(cands) == 1:
            return next(iter(cands))
        return PartitionSpec()

    return jax.tree_util.tree_map_with_path(spec_for, opt_state)


# ---------------------------------------------------------------------------
def _key_token(k):
    """Normalise a tree-path key (DictKey/SequenceKey/GetAttrKey) to a token."""
    for attr in ("key", "idx", "name"):
        v = getattr(k, attr, None)
        if v is not None:
            return v
    return str(k)


def _tree_map_with_path(fn, tree):
    return jax.tree_util.tree_map_with_path(fn, tree)


def _lookup(model_specs, path):
    if model_specs is None:
        return None
    try:
        node = model_specs
        for k in path:
            key = getattr(k, "key", getattr(k, "idx", None))
            node = node[key]
        return node if isinstance(node, PartitionSpec) else None
    except (KeyError, IndexError, TypeError):
        return None  # no spec at this path: replicate (normal layout gap)
    except Exception as e:
        # anything else is a malformed model_specs tree — still resolves
        # to "no spec", but counted and debug-logged (no silent swallows)
        count_suppressed("zero.model_specs_lookup", e)
        return None
