"""Percentiles, spreads, the seeded schedule and latency from the due time."""

import numpy as np
import pytest

from benchmark.harness import schedule, stats


def test_percentile_is_nearest_rank_and_measured():
    samples = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(samples, 0.5) == 3.0
    assert stats.percentile(samples, 0.99) == 5.0
    assert stats.percentile(samples, 0.2) == 1.0
    assert stats.percentile(samples, 0.21) == 2.0
    assert stats.percentile(list(range(1, 1001)), 0.99) == 990
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)
    with pytest.raises(ValueError):
        stats.percentile(samples, 0.0)


def test_samples_beyond_counts_the_tail():
    samples = list(range(1, 1001))
    assert stats.samples_beyond(samples, 0.99) == 10
    assert stats.samples_beyond(samples, 0.5) == 500
    # a "p99" over a dozen samples is the maximum: nothing lies beyond it
    assert stats.samples_beyond(list(range(12)), 0.99) == 0


def test_median_quartiles_and_spread():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 3, 2]) == 2.5
    runs = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, med, q3 = stats.quartiles(runs)
    assert (q1, med, q3) == tuple(np.percentile(runs, [25, 50, 75]))
    assert stats.spread(runs) == pytest.approx((q3 - q1) / med)


def test_schedule_is_a_function_of_the_seed():
    a = schedule.poisson_due_times(7, 200.0, 5.0)
    b = schedule.poisson_due_times(7, 200.0, 5.0)
    c = schedule.poisson_due_times(8, 200.0, 5.0)
    assert np.array_equal(a, b)
    assert len(a) != len(c) or not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[0] > 0 and a[-1] < 5.0
    # Poisson at 200/s over 5 s: 1000 +- a few sigma (sigma ~ 32)
    assert 850 < len(a) < 1150
    # a longer window extends the same stream, it does not redraw it
    longer = schedule.poisson_due_times(7, 200.0, 6.0)
    assert np.array_equal(longer[: len(a)], a)
    assert np.array_equal(
        schedule.payload_order(7, 100, 16), schedule.payload_order(7, 100, 16)
    )
    with pytest.raises(ValueError):
        schedule.poisson_due_times(7, 0.0, 5.0)


def test_latency_runs_from_the_due_time_not_from_the_send():
    # due at 1.0 s, the generator stalled and wrote it at 1.3 s, answered at
    # 1.5 s: the user waited 0.5 s, and the generator was 0.3 s late
    assert schedule.latency_s(1.0, 1.5) == pytest.approx(0.5)
    assert schedule.late_s(1.0, 1.3) == pytest.approx(0.3)
    assert schedule.late_s(1.0, 0.9999) == 0.0
