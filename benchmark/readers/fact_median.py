"""Median, in milliseconds, of a list of seconds that the loop took itself
(``facts`` of its result), such as each request's wait for a slot."""

from .. import metrics


def read(ctx, result, fact):
    values = result["facts"].get(fact)
    return 1e3 * metrics.median(values) if values else None
