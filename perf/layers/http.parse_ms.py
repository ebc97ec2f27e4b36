"""HTTP front end: mean duration of `http.parse` per captured request —
routing, the body's `json.loads`, lane classification (program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "http.parse_ms")
