"""Device milliseconds per run of a jitted program spent in the named
Pallas kernels; None where none of them ran."""

from ..trace import base_name


def read(ctx, result, module, kernels):
    value = ctx["trace"].per_run(module, lambda e: base_name(e.name) in kernels)
    return 1e3 * value if value else None
