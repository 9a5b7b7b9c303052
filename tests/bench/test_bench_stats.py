"""Exact order statistics, with failed requests counted as infinite."""
import math

import pytest

import benchtiny  # noqa: F401  puts the repository on sys.path
from bench.stats import median, percentile, spread


def test_nearest_rank():
    v = list(range(1, 101))            # 1..100
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile(v, 100) == 100
    assert percentile([7.0], 95) == 7.0


def test_order_does_not_matter():
    assert percentile([5, 1, 4, 2, 3], 60) == 3


def test_failed_requests_miss_every_limit():
    ok = [0.1] * 95
    assert percentile(ok + [math.inf] * 5, 95) == 0.1
    assert percentile(ok + [math.inf] * 6, 95) == math.inf


def test_empty_and_spread():
    assert math.isnan(percentile([], 95))
    assert median([1, 2, 3]) == 2
    # statistics.quantiles(n=4) of 1..9 is (2.5, 5, 7.5)
    assert spread(range(1, 10)) == pytest.approx(1.0)
