"""Device-0 milliseconds per run of a jitted program during which a
collective under one ``jax.named_scope`` ran and nothing else did: as
``collective_time`` with ``part`` ``exposed``, over the collectives (sync
operations and async start..done spans) whose ``tf_op`` holds ``/scope/``.
The partitioner gives a collective the metadata of the operation that
PRODUCED the resharded value, so gradient reductions read
``.../window_fwd_bwd/...transpose(jvp...`` and the update's gather
``.../window_optimizer_update/update_apply/...``.

An earlier line (``collectives_by_scope``, once a run) lists EVERY
collective of the window by name: the head and the tail of its ``tf_op``,
calls and milliseconds a window, and the exposed part. None where no collective
carries the scope."""

from .. import harness, program_trace

PS_TO_MS = 1e3 * program_trace.PS


def brief(tf_op, head=3, tail=3):
    """``jit(f)/window_fwd_bwd/while/.../checkpoint/reduce_sum:``: the
    scopes at the head of the path and the operation at its end."""
    parts = tf_op.split("/")
    if len(parts) <= head + tail:
        return tf_op
    return "/".join(parts[:head] + ["..."] + parts[-tail:])


def read(ctx, result, module, scope):
    if "collectives_by_scope" not in ctx:
        found = program_trace.collective_rows(ctx["trace"], module)
        ctx["collectives_by_scope"] = found
        if found:
            rows, runs = found
            harness.say("collectives_by_scope", module=module, runs=runs,
                        collectives=[{
                            "name": r["name"],
                            "tf_op": brief(r["tf_op"]),
                            "calls_per_run": r["calls"] / runs,
                            "ms_per_run": PS_TO_MS * r["total_ps"] / runs,
                            "exposed_ms_per_run":
                                PS_TO_MS * r["exposed_ps"] / runs,
                        } for r in rows])
    if not ctx["collectives_by_scope"]:
        return None
    value = program_trace.exposed_in_scope(ctx["trace"], module, scope)
    return None if value is None else 1e3 * value
