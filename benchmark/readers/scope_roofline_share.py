"""The share of its roofline of everything a window runs under one
``jax.named_scope``, in percent: the least time the chip could take for the
operations and bytes that ``costs/<cost>.py`` counts for ONE WINDOW of this
cell (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, ``peaks.json``) over the device time per window of the operations
under the scope. ``roofline_share`` reads one kernel's calls by the kernel's
name; this reads work that XLA compiles into many operations (a loop of
matrix products), which only the scope names. An earlier line says which of
the two bounds it. None where the scope is not in the trace."""

from .. import harness
from . import scope_time


def read(ctx, result, module, scope, cost):
    ms = scope_time.read(ctx, result, module, scope)
    if not ms or not ctx["peaks"]:
        return None
    flops, nbytes = harness.plugin("costs", cost).per_window(
        ctx["cell"], ctx["size"])
    peak = ctx["peaks"]
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    harness.say("roofline", scope=scope, flops_per_window=flops,
                bytes_per_window=nbytes,
                bound="compute" if t_flops >= t_bytes else "memory",
                least_s=max(t_flops, t_bytes), scope_s=1e-3 * ms)
    return 100.0 * max(t_flops, t_bytes) / (1e-3 * ms)
