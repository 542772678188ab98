"""Device-0 milliseconds per run of a jitted program in all-reduce,
all-gather, reduce-scatter and collective-permute operations: ``part`` is
``total``, or ``exposed`` for the part during which nothing else ran there."""


def read(ctx, result, module, part):
    found = ctx["trace"].collectives(module)
    if found is None:
        return None
    return 1e3 * found[0 if part == "total" else 1]
