"""Mesh program / per-shard ANN: mean host time per launch before the program
call — bundle lookup, mask, pad and upload of the queries, host probe
select (`launch.host_pre`; program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "launch.host_pre_ms")
