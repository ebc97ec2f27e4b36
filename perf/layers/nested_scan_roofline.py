"""Kernels, nested path: the least time the chip could take for what the
traced span's nested kNN launches NEED (perf/nested_work.py: the rows once
a launch, perf/peaks.json) over ALL the time the device was busy,
whatever scans the rows and reduces them to documents. The launches and
the requests they served are the batcher's counters taken just inside the
traced span (`dispatches`, `merged_queries`); the rows a request searched
are the program's own counters (`search.knn.nested.children` over
`.requests`) in the capture's opening and closing snapshots. Both sides
are rates, so the spans need not match to the millisecond. None where the
program counts no nested request (the parent)."""

from perf import hostspans, work
from perf.nested_work import nested_scan_work


def read(run):
    spec = run.config.get("work", {})
    capture = hostspans.capture_of(run)
    delta = run.counter_delta("trace")
    if (spec.get("kind") != "nested_scan" or capture is None
            or not run.peaks or not run.trace or not delta):
        return None
    counters = capture["counters"]
    before = (counters["open"] or {}).get("knn_nested")
    after = (counters["close"] or {}).get("knn_nested")
    if not before or not after:
        return None
    requests = after["requests"] - before["requests"]
    launches, served = delta["dispatches"], delta["merged_queries"]
    if requests <= 0 or launches <= 0 or delta["seconds"] <= 0:
        return None
    rows = (after["children"] - before["children"]) / requests
    ops, moved = nested_scan_work(
        rows, run.config["dims"], run.config["request"]["knn"]["k"],
        launches, served, spec["stored_bytes"])
    least, _ = work.least_seconds(ops, moved, run.peaks)
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    return 100.0 * (least / delta["seconds"]) / busy_rate
