"""Tail arithmetic: over every sample of the window, from due times, with
requests still waiting at the close counted."""

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts bench/ on the path)
from harness import stats


def test_percentile_is_over_all_samples():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile(xs, 90) == pytest.approx(90.1)
    assert stats.percentile([], 90) is None


def test_ttft_runs_from_due_time_and_counts_the_waiting():
    due = [0.0, 1.0, 2.0, 3.0, 9.0]
    first = [0.5, 1.2, None, 3.1, 9.5]
    # window [0, 5): the request due at 9 is outside; the one due at 2 has
    # no first token when the harness stops waiting at 7 and counts with 5 s
    got = stats.ttft_samples(due, first, 0.0, 5.0, censor_at=7.0)
    assert got == pytest.approx([0.5, 0.2, 5.0, 0.1])


def test_a_stall_moves_the_tail():
    # an open loop: one request due every 10 ms, served 20 ms after it is due
    due = np.arange(0.0, 10.0, 0.01)
    first = due + 0.02
    base = stats.percentile(stats.ttft_samples(due, first, 0.0, 10.0, 20.0), 90)
    # a 2 s stall at t = 5: everything due in it waits for its end
    stalled = np.where((due >= 5.0) & (due < 7.0), 7.0 + 0.02, first)
    tail = stats.percentile(stats.ttft_samples(due, stalled, 0.0, 10.0, 20.0), 90)
    assert base == pytest.approx(20e-3)
    assert tail == pytest.approx(1.02, abs=0.02)  # the worst tenth waited 1-2 s


def test_itl_pools_gaps_with_both_tokens_in_the_window():
    times = [np.array([0.0, 0.1, 0.2, 0.9]), np.array([0.15, 0.35]), np.array([0.5])]
    gaps = stats.itl_samples(times, 0.05, 1.0)
    assert sorted(gaps) == pytest.approx([0.1, 0.2, 0.7])  # 0.0 -> 0.1 starts outside
    assert stats.tokens_in(times, 0.05, 1.0) == 6
    assert stats.percentile(gaps, 95) == pytest.approx(0.65)
