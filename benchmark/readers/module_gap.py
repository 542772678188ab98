"""Median idle gap, in milliseconds, on device 0 between the end of one run
of a jitted program and the start of the next."""

from .. import metrics


def read(ctx, result, module):
    found = ctx["trace"].gaps_between_runs(module)
    return 1e3 * metrics.median(found) if found else None
