"""Searches answered 200 by every shard inside the window, over the whole
of the window's seconds: all the work over all the time."""

from perf.stats import rate


def read(run):
    done = [t for t, ok in zip(run.window.t_done, run.judged["ok"]) if ok]
    return rate(done, run.window.t_open, run.window.seconds)
