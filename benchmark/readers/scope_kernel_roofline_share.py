"""A kernel's share of its roofline over the calls it makes UNDER ONE
``jax.named_scope``, in percent: ``roofline_share``'s number (the least time
the chip could take for one call, from ``costs/<cost>.py`` and ``peaks.json``,
over the mean device time of the calls; its note too) where one kernel name
serves layers of different kinds in one program (a stack's windowed and full
attention layers both run ``flash_fwd``, at other head counts and under other
masks): the scope tells the calls apart, as ``scope_time`` tells operations
apart, by the ``tf_op`` path. None where the trace holds no call of the
kernel under the scope (a program that opens no such scope)."""

from . import roofline_share


class _Under:
    """A trace's kernel events, those under one scope only."""

    def __init__(self, trace, scope):
        self.trace, self.tag = trace, f"/{scope}/"

    def kernel_events(self, kernel):
        return [e for e in self.trace.kernel_events(kernel)
                if self.tag in str(e.meta.get("tf_op", ""))]


def read(ctx, result, kernel, scope, cost):
    return roofline_share.read(
        {**ctx, "trace": _Under(ctx["trace"], scope)}, result, kernel, cost)
