"""A kernel's share of its roofline, in percent: the least time the chip
could take for one call (the larger of operations over peak FLOP/s and
bytes over peak bytes/s, both from ``costs/<cost>.py`` and this cell's
shapes, and ``peaks.json``) over the mean device time of the kernel's calls
in the trace. An earlier line says which of the two bounds it."""

from .. import harness


def read(ctx, result, kernel, cost):
    events = ctx["trace"].kernel_events(kernel)
    if not events or not ctx["peaks"]:
        return None
    flops, nbytes = harness.plugin("costs", cost).per_call(
        ctx["cell"], ctx["size"])
    peak = ctx["peaks"]
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    mean = 1e-12 * sum(e.duration_ps for e in events) / len(events)
    harness.say("roofline", kernel=kernel, calls=len(events),
                flops_per_call=flops, bytes_per_call=nbytes,
                bound="compute" if t_flops >= t_bytes else "memory",
                least_s=max(t_flops, t_bytes), mean_s=mean)
    return 100.0 * max(t_flops, t_bytes) / mean
