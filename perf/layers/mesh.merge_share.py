"""Mesh program: the share of device-busy time that is not the scan kernel
(`all_gather`, the final top-k, id arithmetic, the valid mask's convert),
over all device planes of the traced span, from the reduced trace that
`run.py` leaves under `<cache-dir>/trace/<cell>/`. None where no op of the
scan kernel's name ran (another lowering: nothing to tell apart)."""

import json

from perf import hostspans, trace

SCAN = "knn_fused"      # `ops/pallas_knn.py`'s kernel, as the trace names it
EDGE_S = 0.25           # left out at each end, as run.py's TRACE_EDGE_S


def merge_share(reduced: dict) -> float | None:
    busy = scan = 0.0
    for events in reduced.get("devices", {}).values():
        events = trace.clip(events, EDGE_S * 1e9)
        busy += trace.busy_union(events)[0]
        scan += trace.busy_union([e for e in events if SCAN in e[0]])[0]
    if scan <= 0:
        return None
    return 100.0 * (1.0 - scan / busy)


def read(run):
    if not run.trace:
        return None
    cache = hostspans.telemetry_dir(run).parents[3]
    try:
        reduced = json.loads(
            (cache / "trace" / run.cell["name"] / "reduced.json").read_text())
    except (OSError, ValueError):
        return None
    return merge_share(reduced)
