"""The benchmark's arithmetic on a window: percentile and rate."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf.stats import percentile, rate  # noqa: E402


@pytest.mark.parametrize("p, want", [
    (0, 1.0), (50, 50.5), (99, 99.01), (100, 100.0), (25, 25.75)])
def test_percentile_interpolates_between_closest_ranks(p, want):
    assert percentile(range(1, 101), p) == pytest.approx(want)


def test_percentile_of_one_value_and_of_none():
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_a_stall_shows_in_the_tail_and_in_the_rate():
    # 10 ms requests back to back for 10 s, with one 2 s stall in the middle:
    # the client completes 800 requests + the stalled one, not 1,000
    t, done, walls = 0.0, [], []
    while t < 10.0:
        wall = 2.0 if 4.0 <= t < 4.01 else 0.010
        t += wall
        done.append(t)
        walls.append(wall * 1e3)
    assert rate(done, 0.0, 10.0) == pytest.approx(80.0, abs=0.2)
    assert percentile(walls, 50) == pytest.approx(10.0)
    assert max(walls) == 2000.0
    # one slow request in 801 sits beyond the 99th percentile but is never
    # trimmed away: the maximum is a value of the same list
    assert percentile(walls, 100) == 2000.0
    assert percentile(walls, 99) == pytest.approx(10.0)
    assert percentile(walls, 99.95) > 1000.0


def test_rate_counts_only_completions_inside_the_window():
    assert rate([0.5, 1.0, 1.5, 2.5], 1.0, 1.0) == 2.0
    assert rate([], 0.0, 5.0) == 0.0
