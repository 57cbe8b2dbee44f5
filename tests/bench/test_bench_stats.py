"""Tails over every request, stalled ones at their elapsed time, and
rates over the whole window."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import stats


def rec(due, tokens=(), admit=None):
    return SimpleNamespace(due=due, token_times=list(tokens), t_admit=admit)


def test_quantile_interpolates_like_numpy():
    xs = list(np.random.default_rng(0).exponential(size=101))
    for q in (0.0, 0.5, 0.95, 1.0):
        assert stats.quantile(xs, q) == pytest.approx(np.quantile(xs, q))
    assert stats.quantile([], 0.95) is None


def test_ttft_counts_every_request_due_in_the_window():
    rs = [rec(0.5, [0.9]),  # due before the window: left out
          rec(1.0, [1.2, 1.3]),
          rec(2.0, [2.5]),
          rec(3.0),  # no first token by the close: counts at 10 - 3
          rec(9.0, [10.5])]  # first token after the close: 10 - 9
    assert sorted(stats.ttft_samples(rs, 1.0, 10.0)) == pytest.approx(
        [0.2, 0.5, 1.0, 7.0])


def test_a_stalled_request_raises_the_tail():
    fast = [rec(1.0 + i * 0.01, [1.05 + i * 0.01]) for i in range(99)]
    fine = stats.quantile(stats.ttft_samples(fast, 1.0, 5.0), 0.95)
    stalled = fast + [rec(1.5 + i * 0.01) for i in range(10)]
    tail = stats.quantile(stats.ttft_samples(stalled, 1.0, 5.0), 0.95)
    assert fine == pytest.approx(0.05) and tail > 3.0


def test_gaps_whose_later_token_lands_in_the_window():
    rs = [rec(0.0, [0.5, 1.5, 1.7, 2.1, 3.5])]
    assert stats.gap_samples(rs, 1.0, 3.0) == pytest.approx([1.0, 0.2, 0.4])


def test_rate_is_over_the_whole_window_idle_time_included():
    rs = [rec(0.0, [1.0, 1.1, 1.2]), rec(0.0, [1.3, 9.0, 11.0])]
    assert stats.tokens_in(rs, 1.0, 10.0) == 5
    # 5 tokens over a 9 s window, though they came within 8 s
    assert stats.tokens_in(rs, 1.0, 10.0) / 9.0 == pytest.approx(5 / 9)


def test_queue_wait_counts_unadmitted_requests_at_elapsed_time():
    rs = [rec(1.0, admit=1.5), rec(2.0), rec(0.5, admit=3.0)]
    assert sorted(stats.queue_wait_samples(rs, 1.0, 4.0)) == \
        pytest.approx([0.5, 2.0])
