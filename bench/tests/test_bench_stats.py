"""The arithmetic behind the end-to-end metrics."""

import math

import pytest

from bench.lib.stats import percentile, rate, share


def test_percentile_is_nearest_rank_over_all_samples():
    samples = list(range(1, 101))  # 1..100
    assert percentile(samples, 99) == 99
    assert percentile(samples, 50) == 50
    assert percentile(samples, 100) == 100
    assert percentile([7.0], 99) == 7.0


def test_a_failed_request_misses_every_limit():
    ok = [0.001] * 98
    assert percentile(ok + [math.inf] * 2, 99) == math.inf
    assert percentile(ok + [math.inf] * 1 + [0.002], 99) == 0.002
    assert percentile(ok + [math.inf] * 2, 50) == 0.001


def test_tail_is_of_every_sample_not_of_chunk_medians():
    # two chunks: one fast, one with a slow tail; the tail of all samples
    # is the slow one, whatever the chunks' medians say
    fast = [1.0] * 100
    slow = [1.0] * 97 + [50.0] * 3
    assert percentile(fast + slow, 99) == 50.0


def test_rate_is_taken_over_the_whole_window():
    # 3 submissions of 1000 docs that took 0.9, 1.2 and 0.9 s
    assert rate(3000, 3.0) == 1000.0
    with pytest.raises(ValueError):
        rate(1, 0.0)


def test_share():
    assert share(1, 4) == 25.0
    assert share(0, 4) == 0.0
    assert share(1, 0) is None
