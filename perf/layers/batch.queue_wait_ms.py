"""Dispatch batcher: mean time an entry waited in its bucket, enqueue -> taken
by a leader (`batch.wait`'s `queue_wait_ns`, in ns resolution; program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "batch.queue_wait_ms")
