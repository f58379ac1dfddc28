"""Tail arithmetic: failed requests count as misses."""
import math

from bench.common import stats


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert math.isclose(stats.percentile(list(range(11)), 90), 9.0)
    assert stats.percentile([], 90) is None


def test_failed_requests_miss_every_limit():
    vals = stats.with_misses([0.1] * 8 + [None, None])
    assert vals.count(math.inf) == 2
    assert stats.percentile(vals, 50) == 0.1
    assert stats.percentile(vals, 90) == math.inf
    assert stats.percentile(stats.with_misses([0.1] * 19 + [None]), 90) \
        == 0.1


def test_spread_is_quartile_distance_over_median():
    assert math.isclose(stats.spread([10, 10, 10, 10]), 0.0)
    s = stats.spread([9, 10, 10, 11, 10, 10])
    assert 0 < s < 0.2
