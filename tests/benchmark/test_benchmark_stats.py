"""benchmark/lib/stats.py against numpy, and the rule the bounds come from."""
import math

import numpy as np
import pytest

from bench_testlib import load

stats = load('lib/stats.py')


@pytest.mark.parametrize('q', [0, 25, 50, 90, 95, 99, 100])
def test_percentile_is_numpys(q):
    xs = list(np.random.RandomState(q).lognormal(0, 1, 137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_and_of_failures():
    assert stats.percentile([], 50) is None
    # failed requests are +inf: a median survives a few, a tail does not
    xs = [1.0, 2.0, 3.0, math.inf]
    assert stats.percentile(xs, 50) == 2.5
    assert math.isinf(stats.percentile(xs, 100))
    assert math.isinf(stats.percentile([math.inf] * 3, 50))


def test_spread_is_the_interquartile_distance_over_the_median():
    runs = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    assert stats.spread(runs) == pytest.approx(
        (np.percentile(runs, 75) - np.percentile(runs, 25)) / 100.0)
    assert stats.summary([0.001, 0.003], 1e3)['p50'] == pytest.approx(2.0)
