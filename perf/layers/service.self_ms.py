"""Search service: mean self time per request of `search` and its `search.*`
children — parse, query phase, collect, reduce, fetch, response — less the
`batch.wait` and `launch` below them (program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "service.self_ms")
