import numpy as np

from benchmark.harness import stats


def test_median_matches_numpy():
    a = np.random.default_rng(0).exponential(1.0, 5000)
    assert stats.median(a) == np.median(a)


def test_quartile_spread():
    assert stats.quartile_spread([9.0, 10.0, 11.0, 10.0, 10.0]) == 0.0
    assert stats.quartile_spread([8.0, 9.0, 10.0, 11.0, 12.0]) == 0.2
