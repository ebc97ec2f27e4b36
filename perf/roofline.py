"""Roofline share of a traced span: least seconds of its launches' work
per second of span, over device-busy seconds per second of trace.

The launches and the queries they served are the `knn_batch` counter
deltas taken just inside the traced span; the busy time is the union of
all device operations of the trace. Both are rates, so the two spans need
not match to the millisecond."""

from __future__ import annotations

from perf import work


def share(run, kind: str) -> float | None:
    spec = run.config.get("work", {})
    delta = run.counter_delta("trace")
    if spec.get("kind") != kind or not run.trace or not delta:
        return None
    launches, queries = delta.get("dispatches", 0), delta.get("merged_queries", 0)
    if launches <= 0 or delta["seconds"] <= 0:
        return None
    n, d = run.docs, run.config["dims"]
    if kind == "exact_scan":
        ops, moved = work.exact_scan_work(
            n, d, run.config["request"]["knn"]["k"], launches, queries,
            spec["stored_bytes"])
    else:
        ops, moved = work.ivfpq_scan_work(
            n, d, spec["nlist"], spec["m"], spec["ks"], spec["nprobe"],
            spec["pool"], launches, queries)
    least, _ = work.least_seconds(ops, moved, run.peaks)
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    return 100.0 * (least / delta["seconds"]) / busy_rate
