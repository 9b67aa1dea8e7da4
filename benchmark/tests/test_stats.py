import statistics

import numpy as np
import pytest

from benchmark.harness import stats


def test_quantile_is_numpys_linear_rule():
    xs = list(np.random.default_rng(0).normal(size=333))
    for q in (0.0, 0.25, 0.5, 0.95, 1.0):
        assert stats.quantile(xs, q) == pytest.approx(np.quantile(xs, q))
    assert stats.median([3, 1, 2]) == 2


@pytest.mark.parametrize("n, want", [(20, 50), (99, 50), (100, 90),
                                     (199, 90), (200, 95), (999, 95),
                                     (1000, 99), (10000, 99.9)])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert stats.supported_tail(n) == want


def test_a_tail_without_ten_samples_beyond_is_left_out():
    assert stats.tail_or_none(list(range(199)), 95) is None
    assert stats.tail_or_none(list(range(201)), 95) == pytest.approx(190.0)
    assert stats.tail_or_none(list(range(5)), 50) is None
    with pytest.raises(ValueError):
        stats.supported_tail(19)


def test_spread_is_the_drivers_quartile_rule():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.7]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))
