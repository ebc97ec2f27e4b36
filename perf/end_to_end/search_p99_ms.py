"""99th percentile of the client wall of all the window's requests."""

from perf.stats import percentile


def read(run):
    return percentile(run.window.wall_ms(), 99)
