"""HTTP front end: mean duration of `http.respond` — `json.dumps` of the
payload, the write and `drain` (program span)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "http.respond_ms")
