import statistics

import numpy as np
import pytest

from benchmark import metrics


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = list(rng.exponential(1.0, 137))
    for q in (50, 90, 95, 99):
        assert metrics.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert metrics.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        metrics.percentile([], 90)


def test_rate_and_utilization():
    assert metrics.rate(6 * 65536, 24.0) == 16384.0
    # 16,384 tokens/s of a 774M model on a 197 TFLOP/s chip
    assert metrics.model_flops_utilization(16384, 774e6, 197e12) == \
        pytest.approx(6 * 774e6 * 16384 / 197e12)


def test_iqr_share_is_the_contracts_spread():
    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert metrics.iqr_share(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def test_worst_gap_floors_small_leaves_by_the_median():
    theirs = {"a": np.array([1.0, 2.0, 4.0]), "tiny": np.array([1e-9])}
    ours = {"a": np.array([1.0, 2.2, 4.0]), "tiny": np.array([2e-9])}
    gap, where = metrics.worst_gap(ours, theirs)
    # the tiny leaf is off by 100% of itself but by nothing of the median leaf
    assert where == "a[1]" and gap == pytest.approx(0.1)


def test_host_span_readers():
    """The readers of the benchmark's own host spans and of a loop's facts:
    no cell's metric uses the two medians yet (the serving metrics wait with
    their cell, PERF.md Open questions)."""
    from benchmark import harness
    from benchmark.readers import fact_median, span_median, span_total

    spans = harness.Spans()
    spans.records = [("bench.step", 0.0, 0.100, {"admitted": 0, "active": 3}),
                     ("bench.step", 0.1, 0.270, {"admitted": 1, "active": 3}),
                     ("bench.step", 0.3, 0.398, {"admitted": 0, "active": 2}),
                     ("bench.step", 0.4, 0.401, {"admitted": 0, "active": 0}),
                     ("bench.engine_build", 1.0, 3.5, {})]
    ctx = {"spans": spans}
    only_decoded = {"admitted": [0, 0], "active": [1, None]}
    assert span_median.read(ctx, None, "bench.step", only_decoded) == \
        pytest.approx(99.0)
    assert span_median.read(ctx, None, "bench.step", {"admitted": [1, None]}) \
        == pytest.approx(170.0)
    assert span_median.read(ctx, None, "no.such.span", {}) is None
    assert span_total.read(ctx, None, "bench.engine_build") == 2.5
    facts = {"facts": {"queue_wait_s": [0.010, 0.030, 0.080]}}
    assert fact_median.read(ctx, facts, "queue_wait_s") == pytest.approx(30.0)
    assert fact_median.read(ctx, {"facts": {}}, "queue_wait_s") is None
