"""Percentile and rate arithmetic."""
import pytest

import benchtest  # noqa: F401  (paths)
from benchlib.stats import percentile, rate_over_window


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))            # 1..100
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100
    assert percentile([7.0], 99) == 7.0
    assert percentile([3, 1, 2], 50) == 2


def test_percentile_counts_missing_as_their_wait():
    # 2 of 100 requests never answered, carried as a 60 s wait: the p99
    # lands on one of them.
    xs = [0.01] * 98 + [60.0, 60.0]
    assert percentile(xs, 99) == 60.0
    assert percentile(xs, 50) == 0.01


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        percentile([], 50)


def test_rate_counts_the_unit_in_flight_at_the_deadline():
    # Window starts at 0 and closes at 10 s; units of 4 s each began at
    # 0, 4 and 8 (the last in flight at the deadline, ending at 12).
    units = [(0.0, 4.0, 100), (4.0, 8.0, 100), (8.0, 12.0, 100)]
    rate, span, n = rate_over_window(units, 0.0)
    assert (rate, span, n) == (25.0, 12.0, 3)


def test_rate_of_one_long_unit_is_its_own():
    rate, span, n = rate_over_window([(1.0, 27.0, 9365)], 1.0)
    assert n == 1 and span == 26.0 and rate == pytest.approx(9365 / 26)


def test_rate_needs_work():
    with pytest.raises(ValueError):
        rate_over_window([], 0.0)
