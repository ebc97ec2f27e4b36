"""HTTP front end seen from the client under 32 closed-loop clients: the
99th percentile of the traced run's client walls. Queueing by Little's law
(clients / qps) plus the tail, so it is a layer's reading, not a bound."""

from perf.stats import percentile


def read(run):
    if run.mix.get("loop") != "closed" or int(run.mix.get("clients", 1)) < 2:
        return None
    return percentile(run.window.wall_ms(), 99)
