"""HTTP front end: mean wait for one of the search pool's workers, handed to
the pool -> a worker starts the handler (`http.pool_wait`'s `wait_ns`,
stamped on the loop thread, recorded by the worker; program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "http.pool_wait_ms")
