"""Median length, in milliseconds, of the benchmark's own host spans of one
name whose attributes pass ``where`` ({attribute: [low, high]}, bounds
inclusive, null for open), taken over the measured window and its drain."""

from .. import metrics


def read(ctx, result, span, where):
    def passes(attrs):
        for key, (lo, hi) in where.items():
            v = attrs.get(key)
            if v is None or (lo is not None and v < lo) \
                    or (hi is not None and v > hi):
                return False
        return True

    found = [1e3 * (t1 - t0) for _n, t0, t1, a in ctx["spans"].named(span)
             if passes(a)]
    return metrics.median(found) if found else None
