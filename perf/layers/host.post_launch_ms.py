"""Mesh program / per-shard ANN: mean host time per launch after the fence —
the host copies of the launch's further outputs (`launch.fetch`), then launch
accounting and decoding the winners (`launch.host_post`; program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "host.post_launch_ms")
