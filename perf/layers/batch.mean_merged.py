"""Dispatch batcher: queries served per device launch over the window,
from `_nodes/stats/knn_batch` (counts; exact)."""


def read(run):
    d = run.counter_delta("window")
    if not d or d.get("dispatches", 0) <= 0:
        return None
    return d["merged_queries"] / d["dispatches"]
