"""Seconds spent in the benchmark's own host spans of one name."""


def read(ctx, result, span):
    found = ctx["spans"].named(span)
    if not found:
        return None
    return sum(t1 - t0 for _n, t0, t1, _a in found)
