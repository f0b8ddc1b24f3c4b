"""Metric arithmetic: percentiles over every request, failures counted
as missing, rates over the whole window."""
import math

import pytest

from chipbench import stats
from chipbench.client import Record


def rec(due, times, state="done"):
    return Record(idx=0, due=due, token_times=list(times), state=state)


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_and_unfinished_requests_miss_the_limit():
    recs = [rec(0.0, [0.1, 0.2]), rec(1.0, [1.3]), rec(2.0, [], None),
            rec(3.0, [3.2], "failed")]
    xs = stats.ttft_samples(recs, 0.0, 10.0)
    assert xs[:2] == pytest.approx([0.1, 0.3])
    assert xs[2:] == [math.inf, math.inf]
    # half the requests missed: the median is a finite one, p90 is not
    assert stats.percentile(xs, 50) == pytest.approx(0.3)
    assert stats.percentile(xs, 90) == math.inf


def test_window_membership_is_by_due_time():
    recs = [rec(-1.0, [0.5]), rec(0.0, [0.4]), rec(5.0, [5.1])]
    assert len(stats.ttft_samples(recs, 0.0, 5.0)) == 1


def test_itl_takes_every_gap_of_every_request():
    recs = [rec(0.0, [1.0, 1.5, 3.5]), rec(1.0, [2.0, 2.1]),
            rec(-2.0, [0.0, 9.0])]
    assert sorted(stats.itl_samples(recs, 0.0, 10.0)) == \
        pytest.approx([0.1, 0.5, 2.0])


def test_rate_counts_tokens_over_the_whole_window():
    recs = [rec(-1.0, [-0.5, 0.5, 1.5]), rec(0.0, [2.0, 9.9, 10.0])]
    # tokens received in [0, 10): 0.5, 1.5, 2.0, 9.9
    assert stats.tokens_in(recs, 0.0, 10.0) == 4
    assert stats.rate(stats.tokens_in(recs, 0.0, 10.0), 10.0) == 0.4
    assert stats.first_tokens_in(recs, 0.0, 10.0) == 1


def test_spread_is_interquartile_over_median():
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    s = stats.spread([9.0, 10.0, 10.0, 11.0, 10.0, 10.0])
    assert 0.0 < s < 0.2
