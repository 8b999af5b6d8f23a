"""Percentile and weighted-gap arithmetic, worked out by hand."""
import pytest

from chipbench.harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 100) == 50.0
    # rank = 4 * 0.95 = 3.8 -> 40 + 0.8 * 10
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_percentile_matches_numpy():
    import numpy as onp
    rng = onp.random.default_rng(0)
    xs = rng.lognormal(3.0, 1.0, 257).tolist()
    for q in (5, 25, 50, 90, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(
            float(onp.percentile(xs, q)))


def test_weighted_percentile_is_percentile_of_the_expanded_sample():
    # three steps: 10 ms with 8 slots decoding, 50 ms with 1, 100 ms with 1
    gaps, weights = [10.0, 50.0, 100.0], [8, 1, 1]
    # expanded: eight 10s, one 50, one 100 -> 80% of tokens waited 10 ms
    assert stats.weighted_percentile(gaps, weights, 50) == 10.0
    assert stats.weighted_percentile(gaps, weights, 80) == 10.0
    assert stats.weighted_percentile(gaps, weights, 85) == 50.0
    assert stats.weighted_percentile(gaps, weights, 95) == 100.0
    # a step in which no slot decoded carries no token gap
    assert stats.weighted_percentile([10.0, 999.0], [4, 0], 99) == 10.0
    assert stats.weighted_percentile([], [], 95) is None


def test_spread_is_interquartile_distance_over_median():
    xs = [100.0, 101.0, 102.0, 103.0, 104.0]
    assert stats.spread(xs) == pytest.approx((103.0 - 101.0) / 102.0)
