"""Pipeline parallelism: an SPMD GPipe schedule over the mesh's ``pipe`` axis.

The reference has no pipeline engine (SURVEY §2.4: absent in v0.2.0); this
is a beyond-reference capability, built the TPU way: instead of
point-to-point sends between stage processes (the GPU pattern), every
device runs the SAME program under ``jax.shard_map`` — manual over the
``pipe`` axis only, all other mesh axes (data/sequence/model) left in
GSPMD "auto" mode — and activations hop stages with ``lax.ppermute`` over
ICI. The schedule is a single ``lax.scan`` of ``M + P - 1`` ticks
(M microbatches, P stages): stage 0 injects a fresh microbatch each tick,
interior stages transform whatever arrived last hop, the final stage
collects results. On fill/drain ticks (microbatch index out of [0, M)) the
stage input is ZEROED before compute: SPMD lockstep means the FLOPs still
run, but bubble compute becomes input-INDEPENDENT — stage_fn only ever
evaluates at zeros during bubbles, never at stale data-dependent
activations, so a stage map that misbehaves on out-of-distribution inputs
cannot plant an inf/NaN in a saved residual (where it would turn the
masked-out gradient into NaN via inf * 0). ``jax.grad``
through the scan+ppermute yields the reverse pipeline automatically — no
hand-written backward schedule. See docs/parallelism.md for the
bubble/memory math and the GPipe-vs-1F1B design argument.

Memory: each tick's stage input is saved for backward (a scan carry
residual); wrap ``stage_fn``'s internals in ``jax.checkpoint`` (the
transformer layer's remat modes do this) to keep the per-tick residual at
one activation.

Bubble fraction is the GPipe (P-1)/(M+P-1); choose
``microbatches >= 4 * stages`` to keep it under ~20%.
"""

import jax
import jax.numpy as jnp

from . import mesh as mesh_lib


def _pvary(x, axis_name):
    """Mark ``x`` as device-varying over ``axis_name`` (VMA typing for the
    scan carry, which starts replicated but becomes stage-dependent)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def pipeline_stages(mesh):
    return dict(mesh.shape).get(mesh_lib.PIPE_AXIS, 1)


def gpipe_spmd(stage_fn, stage_params, microbatches, mesh,
               pipe_axis=mesh_lib.PIPE_AXIS, extras=(),
               last_stage_fn=None):
    """Run ``microbatches`` through a P-stage pipeline.

    Args:
      stage_fn: ``(local_params, x, tick, extras) -> y`` — one stage's
        compute on one microbatch. ``local_params`` is ``stage_params``
        with the leading stage axis sliced to this device's stage; ``tick``
        is the schedule tick (traced int32) — the microbatch index being
        processed is ``tick - lax.axis_index(pipe_axis)``, which stage_fn
        can use to derive per-microbatch dropout keys. Must return ``y``
        with x's shape/dtype (it feeds the next stage).
      stage_params: pytree whose leaves have leading axis P (one slice per
        stage). The caller shards this axis over ``pipe`` (partition specs);
        inside the body each device sees its own ``[1, ...]`` slice.
      microbatches: ``[M, mb, ...]`` array, replicated over ``pipe``; other
        mesh axes stay in GSPMD auto mode, so e.g. the ``mb`` dim may be
        data-sharded as usual.
      mesh: the device mesh (must contain ``pipe_axis``).
      extras: pytree replicated to every stage unsliced (dropout seeds,
        masks shared by all microbatches, ...).
      last_stage_fn: optional ``(y, mb_idx, extras) -> scalar`` applied on
        the FINAL stage to each microbatch's output (e.g. head + loss).
        When given, the per-stage activations stay LOCAL to their stage —
        only the ``[M]`` scalars cross the pipe axis, replacing the
        ``[M, mb, ...]`` activation broadcast with a collective ~1e5x
        smaller at transformer shapes (the 1F1B-style local-output
        pattern).

    Returns:
      ``[M, mb, ...]`` outputs of the final stage, replicated over pipe —
      or, with ``last_stage_fn``, the ``[M]`` scalars it produced.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = dict(mesh.shape).get(pipe_axis, 1)
    n_micro = microbatches.shape[0]
    # n_stages == 1 runs the same shard_map body (ppermute degenerates to
    # identity, there are no bubble ticks) so stage_fn may always call
    # lax.axis_index(pipe_axis) as the contract above promises.

    def body(params_local, x_mb, extras_local):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(pipe_axis)

        state0 = _pvary(jnp.zeros(x_mb.shape[1:], x_mb.dtype), pipe_axis)
        if last_stage_fn is None:
            out0 = _pvary(jnp.zeros_like(x_mb), pipe_axis)
        else:
            out0 = _pvary(jnp.zeros((n_micro,), jnp.float32), pipe_axis)

        def tick(carry, t):
            state, out = carry
            inject = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False
            )
            state = jnp.where(stage == 0, inject, state)
            # fill/drain masking: a stage whose microbatch index is outside
            # [0, M) this tick is computing a bubble — zero its input so
            # repeatedly re-transformed junk can't overflow to inf (inf in
            # a saved residual turns the masked-out gradient into NaN)
            mb_idx = t - stage
            valid = (mb_idx >= 0) & (mb_idx < n_micro)
            state = jnp.where(valid, state, jnp.zeros_like(state))
            y = stage_fn(params_local, state, t, extras_local)
            is_emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            if last_stage_fn is None:
                out = jnp.where(
                    is_emit,
                    jax.lax.dynamic_update_index_in_dim(
                        out, y, jnp.maximum(t - (n_stages - 1), 0), axis=0
                    ),
                    out,
                )
            else:
                # activations stay LOCAL: reduce to a scalar on the last
                # stage; only the [M] scalars ever cross the pipe axis
                scalar = last_stage_fn(y, mb_idx, extras_local)
                out = jnp.where(
                    is_emit,
                    jax.lax.dynamic_update_index_in_dim(
                        out, scalar.astype(jnp.float32),
                        jnp.maximum(t - (n_stages - 1), 0), axis=0,
                    ),
                    out,
                )
            nxt = jax.lax.ppermute(
                y, pipe_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (nxt, out), None

        (_, out), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(n_micro + n_stages - 1)
        )
        # only the last stage holds real outputs; sum-broadcast to all pipe
        # ranks (everyone else contributes zeros) so downstream (the LM
        # head, or the loss mean) sees a pipe-replicated value. Without
        # last_stage_fn this moves the [M, mb, ...] activations (~2(P-1)/P
        # x their bytes of ICI); with it, [M] floats.
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            pipe_axis,
        )
        return out

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(pipe_axis), P(), P()),
        out_specs=P(),
        axis_names={pipe_axis},  # manual over pipe; data/seq/model stay auto
    )(stage_params, microbatches, extras)
