"""The program's own request spans, read after the run: find the capture the
traced part left behind, and reduce it to the host-side per-layer metrics.

While a `jax.profiler` session runs, the node records every span of the
requests that open under it (`opensearch_tpu/telemetry/tracing.py`) and,
once the session has ended, writes them to
`<data>/telemetry/capture-<n>.json`: records of
(name, trace_id, span_id, parent_id, thread, start_ns, end_ns, attributes)
on `time.perf_counter_ns()`, which on Linux is the system-wide
CLOCK_MONOTONIC the harness's own stamps (`perf/traffic.py`,
`run.counters["trace"]`) are taken on. A reader sees only `run`, after the
node is gone, so the data directory is rebuilt here the way `run.py` builds
it (a later benchmark PR should hand readers `run.home`).

Everything below `capture_of` is arithmetic on that JSON and is checked by
`tests/perf/test_perf_hostspans.py` on a small hand-written capture. A
program without these spans (the parent commit) leaves no capture, and
every metric reads None.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
EDGE_NS = 250_000_000   # left out at each end, as run.py's TRACE_EDGE_S:
                        # starting and stopping the profiler stalls the node
ROOT, RESPOND = "http_request", "http.respond"


def telemetry_dir(run) -> Path:
    """`<cache-dir>/<config>/corpus-<seed>-docs-<docs>/node/telemetry`, as
    `run.py` lays the data directory out."""
    cache = HERE / ".cache"
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--cache-dir" and i + 1 < len(argv):
            cache = Path(argv[i + 1])
        elif arg.startswith("--cache-dir="):
            cache = Path(arg.split("=", 1)[1])
    conf = run.config
    return (cache / conf["name"]
            / f"corpus-{conf['corpus_seed']}-docs-{run.docs}"
            / "node" / "telemetry")


def load_capture(directory: Path, t_lo: float, t_hi: float) -> dict | None:
    """The capture under `directory` that was open during [t_lo, t_hi]
    (seconds of `time.perf_counter()`), its records as dicts; None if there
    is none."""
    for path in sorted(Path(directory).glob("capture-*.json"), reverse=True):
        try:
            doc = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        opened = doc["opened"]["perf_counter_ns"]
        closed = doc["closed"]["perf_counter_ns"]
        if opened <= t_hi * 1e9 and closed >= t_lo * 1e9:
            fields = doc["fields"]
            doc["spans"] = [dict(zip(fields, r)) for r in doc.pop("records")]
            return doc
    return None


def capture_of(run) -> dict | None:
    """The traced part's capture, loaded once per run. None without a device
    trace (`--trace 0`, and every CPU run: a CPU's times are no result) and
    where the program left no capture."""
    if not run.trace or "trace" not in run.counters:
        return None
    if not hasattr(run, "_host_capture"):
        before, after = run.counters["trace"]
        run._host_capture = load_capture(
            telemetry_dir(run), before["t"], after["t"])
    return run._host_capture


# -- arithmetic on a capture ---------------------------------------------------


def steady(capture: dict) -> tuple[float, float]:
    """The span of the capture that the metrics read: EDGE_NS in from its
    open and its close."""
    return (capture["opened"]["perf_counter_ns"] + EDGE_NS,
            capture["closed"]["perf_counter_ns"] - EDGE_NS)


def requests(spans: list) -> dict:
    """trace_id -> (root opening, response written), for the searches that
    have both ends (the harness's own `_nodes/stats` reads are requests
    too, and are not what the metrics are about)."""
    opened = {s["trace_id"]: s["start_ns"] for s in named(spans, ROOT)
              if (s["attributes"] or {}).get("path", "").endswith("/_search")}
    return {s["trace_id"]: (opened[s["trace_id"]], s["end_ns"])
            for s in named(spans, RESPOND) if s["trace_id"] in opened}


def inside(capture: dict) -> list:
    """The spans of the requests that lie whole inside the steady span (a
    request cut by either edge is left out with all it holds), and the
    spans of no request (`runtime.gc`) that lie inside it themselves."""
    lo, hi = steady(capture)
    whole = {trace for trace, (start, end)
             in requests(capture["spans"]).items()
             if start >= lo and end <= hi}
    return [s for s in capture["spans"]
            if s["trace_id"] in whole or (
                s["trace_id"] is None
                and s["start_ns"] >= lo and s["end_ns"] <= hi)]


def duration_ms(span: dict) -> float:
    return (span["end_ns"] - span["start_ns"]) / 1e6


def mean(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) if values else None


def covered_ns(lo: float, hi: float, intervals) -> float:
    """Length of [lo, hi] that the union of `intervals` covers."""
    total, at = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, at), min(b, hi)
        if b > a:
            total += b - a
            at = b
    return total


def self_times_ms(spans: list) -> dict:
    """span_id -> duration less what its children cover of it."""
    children: dict = {}
    for s in spans:
        if s["parent_id"] is not None:
            children.setdefault(s["parent_id"], []).append(
                (s["start_ns"], s["end_ns"]))
    return {s["span_id"]: (s["end_ns"] - s["start_ns"] - covered_ns(
                s["start_ns"], s["end_ns"], children.get(s["span_id"], ())))
            / 1e6
            for s in spans if s["span_id"] is not None}


def mean_self_ms_by_name(capture: dict) -> dict:
    """name -> (spans, mean self time in ms): which span to split next."""
    spans = inside(capture)
    own = self_times_ms(spans)
    by_name: dict = {}
    for s in spans:
        if s["span_id"] is not None:
            by_name.setdefault(s["name"], []).append(own[s["span_id"]])
    return {name: (len(v), sum(v) / len(v)) for name, v in by_name.items()}


def named(spans: list, name: str) -> list:
    return [s for s in spans if s["name"] == name]


def mean_duration_ms(capture: dict, name: str) -> float | None:
    return mean(duration_ms(s) for s in named(inside(capture), name))


def mean_attribute_ms(capture: dict, name: str, key: str) -> float | None:
    """Mean of a nanosecond attribute: a wait that another thread ended is
    carried by the waiter's span as an attribute."""
    return mean(s["attributes"][key] / 1e6
                for s in named(inside(capture), name)
                if s["attributes"] and key in s["attributes"])


def per_launch_ms(capture: dict, *names: str) -> float | None:
    """Mean over launches of the time their children of these names take
    (a launch may hold several of one name)."""
    spans = inside(capture)
    totals = {s["span_id"]: 0.0 for s in named(spans, "launch")}
    for s in spans:
        if s["name"] in names and s["parent_id"] in totals:
            totals[s["parent_id"]] += duration_ms(s)
    return mean(totals.values())


def outside_ms(capture: dict, window) -> float | None:
    """Mean client wall of the requests inside the capture, less the mean
    of (`http_request` opening -> `http.respond` closing): socket, loopback,
    the asyncio read before any span, and the client itself. Means on both
    sides, so no request has to be matched to its span."""
    lo, hi = steady(capture)
    walls = [(b - a) * 1e3 for a, b in zip(window.t_from, window.t_done)
             if a * 1e9 >= lo and b * 1e9 <= hi]
    served = [(end - start) / 1e6
              for start, end in requests(inside(capture)).values()]
    if not walls or not served:
        return None
    return mean(walls) - mean(served)


def service_self_ms(capture: dict) -> float | None:
    """Per request: the self time of `search` and its `search.*` children
    (`batch.wait` and `launch` are children of theirs, so already out)."""
    spans = inside(capture)
    own = self_times_ms(spans)
    per_request: dict = {}
    for s in spans:
        if s["name"] == "search" or s["name"].startswith("search."):
            per_request[s["trace_id"]] = (
                per_request.get(s["trace_id"], 0.0) + own[s["span_id"]])
    return mean(per_request.values())


def between_launch_ms(capture: dict) -> float | None:
    """Mean time from one `launch.device` closing to the next opening,
    union over threads: the host's view of the device's idle gap."""
    spans = sorted((s["start_ns"], s["end_ns"])
                   for s in named(inside(capture), "launch.device"))
    gaps, end = [], None
    for a, b in spans:
        if end is not None and a > end:
            gaps.append((a - end) / 1e6)
        end = b if end is None else max(end, b)
    return mean(gaps)


def resident_bytes(capture: dict) -> int | None:
    return (capture["counters"]["close"] or {}).get("device_resident_bytes")


METRICS = {
    "http.parse_ms": lambda c, run: mean_duration_ms(c, "http.parse"),
    "http.pool_wait_ms":
        lambda c, run: mean_attribute_ms(c, "http.pool_wait", "wait_ns"),
    "http.respond_ms": lambda c, run: mean_duration_ms(c, RESPOND),
    "http.outside_ms": lambda c, run: outside_ms(c, run.window),
    "service.self_ms": lambda c, run: service_self_ms(c),
    "batch.queue_wait_ms":
        lambda c, run: mean_attribute_ms(c, "batch.wait", "queue_wait_ns"),
    "launch.host_pre_ms":
        lambda c, run: per_launch_ms(c, "launch.host_pre"),
    "host.post_launch_ms":
        lambda c, run: per_launch_ms(c, "launch.fetch", "launch.host_post"),
    "host.between_launch_ms": lambda c, run: between_launch_ms(c),
    "device.resident_bytes": lambda c, run: resident_bytes(c),
}


def metric(run, name: str):
    """`perf/layers/<name>.py`'s `read(run)`."""
    capture = capture_of(run)
    if capture is None:
        return None
    return METRICS[name](capture, run)


if __name__ == "__main__":
    # python perf/hostspans.py <capture.json>: mean self time by span name
    doc = json.loads(Path(sys.argv[1]).read_text())
    doc["spans"] = [dict(zip(doc["fields"], r)) for r in doc.pop("records")]
    table = mean_self_ms_by_name(doc)
    for span_name, (count, own_ms) in sorted(table.items(),
                                             key=lambda kv: -kv[1][1]):
        print(f"{span_name:22s} n={count:6d} mean self {own_ms:9.4f} ms")
    print(f"dropped {doc['dropped']}")
