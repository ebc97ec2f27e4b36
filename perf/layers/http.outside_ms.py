"""HTTP front end: mean client wall of the requests inside the capture, less
the mean of (`http_request` opening -> `http.respond` closing): socket,
loopback, the asyncio read before any span, and the client itself."""

from perf.hostspans import metric


def read(run):
    return metric(run, "http.outside_ms")
