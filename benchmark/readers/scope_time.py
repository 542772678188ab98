"""Device milliseconds per run of a jitted program spent in the operations
under one ``jax.named_scope`` (each operation's own time: a ``while`` does
not count its body twice)."""


def read(ctx, result, module, scope):
    tag = f"/{scope}/"
    value = ctx["trace"].per_run(
        module, lambda e: tag in str(e.meta.get("tf_op", "")), own_time=True)
    return None if value is None else 1e3 * value
