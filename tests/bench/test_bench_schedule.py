"""The open-loop schedule: a pure function of the seed, the same work for
every seed, and Poisson-like gaps."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import schedule  # noqa: E402

STEADY = {"period_s": 1.0, "phases": [[1.0, 1.0]]}


def test_same_seed_same_schedule():
    a = schedule.arrivals(123456789012, 80.0, 30.0, STEADY)
    b = schedule.arrivals(123456789012, 80.0, 30.0, STEADY)
    assert np.array_equal(a, b)


def test_seeds_share_count_and_gaps_not_order():
    a = schedule.arrivals(1, 80.0, 30.0, STEADY)
    b = schedule.arrivals(2, 80.0, 30.0, STEADY)
    assert len(a) == len(b) == 2400
    assert not np.array_equal(a, b)
    ga = np.sort(np.diff(np.append(a, 30.0)))
    gb = np.sort(np.diff(np.append(b, 30.0)))
    np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)


def test_arrivals_fill_the_window_like_poisson():
    a = schedule.arrivals(7, 50.0, 40.0, STEADY)
    assert a[0] == 0.0 and a[-1] < 40.0
    assert np.all(np.diff(a) >= 0)
    gaps = np.diff(a)
    # exponential gaps: mean 1/rate, coefficient of variation about 1
    assert abs(gaps.mean() - 1 / 50.0) < 0.05 / 50.0
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


def test_phases_shape_the_rate():
    burst = {"period_s": 2.0, "phases": [[0.25, 4.0], [0.75, 0.0]]}
    a = schedule.arrivals(3, 40.0, 20.0, burst)
    assert len(a) == 800
    assert np.all((a % 2.0) <= 0.5 + 1e-9)


def test_pool_is_cycled_in_order():
    assert schedule.pool_order(5, 2).tolist() == [0, 1, 0, 1, 0]


def test_no_request_offered_is_an_error():
    with pytest.raises(ValueError):
        schedule.arrivals(1, 0.01, 1.0, STEADY)
