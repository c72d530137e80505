"""Percentile arithmetic and its sample-count rule."""

import pytest
import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [4, 1, 3, 2]
    assert stats.percentile(xs, 0) == 1
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile(xs, 100) == 4
    assert stats.percentile(range(1, 202), 95) == 191
    assert stats.percentile([7], 95) == 7


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


@pytest.mark.parametrize("q, n", [(95, 200), (50, 20), (99, 1000), (5, 200)])
def test_ten_samples_lie_beyond_a_supported_percentile(q, n):
    assert stats.samples_needed(q) == n
    assert stats.supported(n, q) and not stats.supported(n - 1, q)


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 10.05)
