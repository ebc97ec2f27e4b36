"""The three readers of the four-chip cell (`perf/layers/mesh*.py`) on a
hand-written reduced trace of four planes and a hand-written capture
snapshot: arithmetic alone, no node and no device."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perf import trace, work  # noqa: E402


def layer(name: str):
    """`perf/layers/<name>.py` as a module (run.py loads it the same way)."""
    spec = importlib.util.spec_from_file_location(
        "perf_layer_under_test", REPO / "perf" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CONFIG = json.loads(
    (REPO / "perf/configs/sift1m-exact-4shard.json").read_text())
SCAN_MS, LAUNCHES, QUERIES, SPAN_S = 0.25, 100, 400, 1.0


def planes(merge_ms: float = 0.0) -> dict:
    """Four chips, each running LAUNCHES launches 10 ms apart: the scan
    kernel, then (if any) an all-gather and a sort of `merge_ms` between
    them."""
    devices = {}
    for chip in range(4):
        events = []
        for i in range(LAUNCHES):
            t = i * 10e6
            events.append(["pallas_knn_fused.1", t, SCAN_MS * 1e6])
            if merge_ms:
                events.append(["all-gather.3", t + SCAN_MS * 1e6,
                               merge_ms * 0.5e6])
                events.append(["sort.7", t + (SCAN_MS + merge_ms * 0.5) * 1e6,
                               merge_ms * 0.5e6])
        devices[f"/device:TPU:{chip}"] = events
    return {"devices": devices}


def run_of(reduced: dict, config: dict = CONFIG, chips: int = 4):
    return SimpleNamespace(
        config=config, docs=config["docs"], peaks=PEAKS,
        cell={"name": "sift1m-exact-4shard.c32", "chips": chips},
        trace=trace.reduce_trace(reduced),
        counter_delta=lambda span: {
            "dispatches": LAUNCHES, "merged_queries": QUERIES,
            "seconds": SPAN_S})


def test_a_chip_that_reads_only_its_share_cannot_pass_100_percent():
    read = layer("mesh_scan_roofline").read
    run = run_of(planes())
    # a chip's floor: its 250,000 rows of the column once a launch
    _ops, moved = work.exact_scan_work(250_000, 128, 10, LAUNCHES, QUERIES)
    floor_s = moved / PEAKS["bytes_per_s"]
    busy_s = LAUNCHES * SCAN_MS / 1e3
    window_s = run.trace["window_s"]
    assert run.trace["chips"] == 4
    assert run.trace["busy_s"] == pytest.approx(busy_s)
    want = 100.0 * (floor_s / SPAN_S) / (busy_s / window_s)
    assert read(run) == pytest.approx(want)
    assert 60.0 < want < 65.0       # 0.156 ms of floor in 0.25 ms of scan
    # the fastest a chip can be at its own bytes reads 100, not 400: the
    # whole index's bytes against one chip's time would
    at_floor = planes()
    for events in at_floor["devices"].values():
        for e in events:
            e[2] = floor_s / LAUNCHES * 1e9
    fastest = run_of(at_floor)
    assert read(fastest) == pytest.approx(
        100.0 * fastest.trace["window_s"] / SPAN_S)
    assert read(fastest) <= 100.0
    # one shard on one chip is `exact_scan_roofline`'s, not this metric's
    one = json.loads((REPO / "perf/configs/sift1m-exact.json").read_text())
    assert read(run_of(planes(), config=one, chips=1)) is None


def test_merge_share_is_what_the_scan_kernel_does_not_cover():
    share = layer("mesh.merge_share").merge_share
    assert share(planes()) == pytest.approx(0.0)
    # 0.25 ms of scan and 0.05 ms of all-gather + sort a launch, less the
    # edges that are cut alike on both: one part in six
    assert share(planes(merge_ms=0.05)) == pytest.approx(100.0 / 6, rel=1e-2)
    no_kernel = {"devices": {"/device:TPU:0": [["fusion.2", 0.0, 1e6],
                                                ["fusion.2", 9e8, 1e6]]}}
    assert share(no_kernel) is None
    assert share({"devices": {}}) is None


def test_merge_share_reads_the_reduced_trace_run_py_leaves(tmp_path,
                                                           monkeypatch):
    read = layer("mesh.merge_share").read
    cell_dir = tmp_path / "trace" / "sift1m-exact-4shard.c32"
    cell_dir.mkdir(parents=True)
    (cell_dir / "reduced.json").write_text(json.dumps(planes(0.05)))
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir", str(tmp_path)])
    run = run_of(planes(0.05))
    assert read(run) == pytest.approx(100.0 / 6, rel=1e-2)
    run.trace = None                        # an untraced run
    assert read(run) is None
    run = run_of(planes())
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir",
                                      str(tmp_path / "nowhere")])
    assert read(run) is None


def test_resident_skew_is_the_fullest_chip_over_the_mean():
    reader = layer("mesh.resident_skew").read
    skew = layer("mesh.resident_skew").skew
    even = {f"TPU_{i}": 271_000_000 for i in range(4)}
    assert skew(even, 4) == pytest.approx(1.0)
    # the parent's layout: every segment column on chip 0
    staged = {"TPU_0": 700, "TPU_1": 140, "TPU_2": 140, "TPU_3": 140}
    assert skew(staged, 4) == pytest.approx(2.5)
    # a chip that holds nothing has no row and still counts in the mean
    assert skew({"TPU_0": 100, "TPU_1": 100}, 4) == pytest.approx(2.0)
    assert skew(None, 4) is None and skew({}, 4) is None

    def run_with(closing):
        run = run_of(planes())
        run.counters = {"trace": ({"t": 1.0}, {"t": 2.0})}
        run._host_capture = {"counters": {"open": {}, "close": closing}}
        return run

    assert reader(run_with({"device_resident_by_device": staged})) == \
        pytest.approx(2.5)
    # a tree whose ledger has no chip-by-chip figures (the parent)
    assert reader(run_with({"device_resident_bytes": 1120})) is None
    assert reader(run_with(None)) is None
    untraced = run_with({"device_resident_by_device": even})
    untraced.trace = None
    assert reader(untraced) is None
