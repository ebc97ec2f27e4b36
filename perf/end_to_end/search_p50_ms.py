"""Median client wall of all the window's requests (an unanswered request
counts with the time it was waited for)."""

from perf.stats import percentile


def read(run):
    return percentile(run.window.wall_ms(), 50)
