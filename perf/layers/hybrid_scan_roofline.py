"""Kernels, hybrid path: the least time the chip could take for what the
capture's hybrid requests NEED (perf/hybrid_work.py: the vector column
once a request, their terms' posting entries, perf/peaks.json) over ALL
the time the device was busy, whatever scores the words and scans the
vectors. The requests and their posting entries are the program's own
counters (`search.hybrid.requests`, `search.bm25.postings`), snapshotted
at the open and the close of the traced part's capture; both sides are
rates, so the capture's span and the trace's need not match to the
millisecond. None where the program counts neither (the parent)."""

from perf import hostspans, work
from perf.hybrid_work import hybrid_scan_work


def read(run):
    spec = run.config.get("work", {})
    capture = hostspans.capture_of(run)
    if (spec.get("kind") != "hybrid_scan" or capture is None
            or not run.peaks):
        return None
    counters = capture["counters"]
    before = (counters["open"] or {}).get("lexical")
    after = (counters["close"] or {}).get("lexical")
    seconds = (capture["closed"]["perf_counter_ns"]
               - capture["opened"]["perf_counter_ns"]) / 1e9
    if not before or not after or seconds <= 0:
        return None
    requests = after["hybrid_requests"] - before["hybrid_requests"]
    postings = after["bm25_postings"] - before["bm25_postings"]
    if requests <= 0:
        return None
    ops, moved = hybrid_scan_work(
        run.docs, run.config["dims"], run.config["request"]["knn"]["k"],
        requests, postings, spec["stored_bytes"], spec["posting_bytes"])
    least, _ = work.least_seconds(ops, moved, run.peaks)
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    return 100.0 * (least / seconds) / busy_rate
