"""What a FILTERED exact scan has to do at least, from shapes alone: the
work function of `filtered_scan_roofline` (`perf/work.py`'s two know a
whole-column scan and an IVF-PQ one). A floor that no implementation can
beat, so that the share reads the same whatever serves the filter: a scan
of the whole column behind a mask today, a gather of the eligible rows
tomorrow. Per query: the ELIGIBLE rows' stored bytes in, the query in, k
(score, id) pairs out, one multiply-add per dimension of every eligible
row. What evaluates the filter is counted as no work at all."""

from __future__ import annotations


def filtered_scan_work(eligible: float, d: int, k: int, queries: int,
                       stored_bytes: int = 4) -> tuple[float, float]:
    """(operations, bytes) of `queries` filtered scans whose filters leave
    `eligible` rows each (a mean will do: both are linear in it)."""
    ops = 2.0 * queries * eligible * d
    moved = queries * (eligible * d * stored_bytes + d * 4 + k * 8)
    return ops, float(moved)
