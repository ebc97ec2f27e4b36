"""Kernels, filtered exact path: the least time the chip could take for
what the traced span's queries NEED (their eligible rows alone:
perf/filtered_work.py, perf/peaks.json) over ALL the time the device was
busy in it, whatever evaluates the filter and implements the scan. The
queries served are the `knn_batch` counter's delta just inside the traced
span; their mean eligible rows are the kind's (`eligible_rows_mean`, over
the window's judged replies)."""

from perf import work
from perf.filtered_work import filtered_scan_work


def read(run):
    spec = run.config.get("work", {})
    delta = run.counter_delta("trace")
    eligible = run.numbers.get("eligible_rows_mean")
    if (spec.get("kind") != "filtered_scan" or not run.trace or not delta
            or not run.peaks or not eligible):
        return None
    queries = delta.get("merged_queries", 0)
    if queries <= 0 or delta["seconds"] <= 0:
        return None
    ops, moved = filtered_scan_work(
        eligible, run.config["dims"], run.config["request"]["knn"]["k"],
        queries, spec["stored_bytes"])
    least, _ = work.least_seconds(ops, moved, run.peaks)
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    return 100.0 * (least / delta["seconds"]) / busy_rate
