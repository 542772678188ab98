"""Percent of the traced window in which no operation ran on device 0."""


def read(ctx, result):
    return 100.0 * ctx["trace"].idle_share()
