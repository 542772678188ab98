"""The arithmetic of the end-to-end metrics, in one place."""

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics (numpy's default), over ALL the values given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return statistics.median(values)


def rate(count, seconds):
    return count / seconds


def iqr_share(values):
    """The spread the bounds are set from: the distance between the first
    and third quartile (``statistics.quantiles(values, n=4)``) as a share
    of the median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def model_flops_utilization(tokens_per_s_per_chip, n_params, peak_flops):
    """6 N operations a token for the forward and backward passes (weights
    only; recomputation does not count), over the chip's bf16 peak."""
    return 6.0 * n_params * tokens_per_s_per_chip / peak_flops


def worst_gap(ours, theirs):
    """The widest gap between two sets of per-leaf norms: |ours - theirs|
    over max(theirs, the median of all of theirs), since some leaves'
    gradients are all but zero. Returns (gap, where)."""
    import numpy as np

    floor = float(np.median(np.concatenate([v.ravel() for v in theirs.values()])))
    worst, where = 0.0, None
    for name, ref in theirs.items():
        gap = np.abs(ours[name].astype(np.float64) - ref) / np.maximum(ref, floor)
        i = int(np.argmax(gap))
        if gap[i] > worst or where is None:
            worst, where = float(gap[i]), f"{name}[{i}]"
    return worst, where
