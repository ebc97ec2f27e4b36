"""The reduction from a device trace to busy time, idle share, top ops and
idle gaps — on intervals made by hand and on a small trace recorded on the
chip (tests/perf/data/trace_small.json, a cut of a `--trace 1` run)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf import trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "trace_small.json"


def test_busy_union_counts_nested_and_overlapping_ops_once():
    events = [["loop", 0, 100], ["fusion", 10, 20], ["fusion", 40, 20],
              ["copy", 90, 30], ["scan", 200, 50], ["zero", 300, 0]]
    busy, merged = trace.busy_union(events)
    assert merged == [[0, 120], [200, 250]]
    assert busy == 170


def test_reduce_gives_busy_window_ops_and_gaps():
    reduced = {"devices": {"/device:TPU:0": [
        ["scan", 1_000_000_000, 400_000_000],
        ["topk", 1_400_000_000, 100_000_000],
        ["scan", 2_000_000_000, 400_000_000],
        ["topk", 2_400_000_000, 100_000_000]]}}
    got = trace.reduce_trace(reduced)
    assert got["busy_s"] == pytest.approx(1.0)
    assert got["window_s"] == pytest.approx(1.5)
    assert got["chips"] == 1
    assert got["device_ops"] == [["scan", pytest.approx(0.8)],
                                 ["topk", pytest.approx(0.2)]]
    assert got["idle_gaps"] == [["unattributed", pytest.approx(0.5)]]
    idle_share = 100.0 * (1.0 - got["busy_s"] / got["window_s"])
    assert idle_share == pytest.approx(100.0 / 3.0)


def test_edges_are_left_out_and_ops_that_cross_them_are_cut():
    # 0.0-0.1 busy, a 0.3 s stall (the profiler starting), then 1 s of
    # launches back to back, a stall, and a last op
    evs = [["first", 0, 100_000_000]]
    evs += [["scan", 400_000_000 + i * 100_000_000, 90_000_000]
            for i in range(10)]
    evs += [["last", 1_700_000_000, 100_000_000]]
    whole = trace.reduce_trace({"devices": {"/device:TPU:0": evs}})
    assert whole["window_s"] == pytest.approx(1.8)
    assert whole["idle_gaps"][0][1] == pytest.approx(0.31)
    cut = trace.reduce_trace({"devices": {"/device:TPU:0": evs}}, edge_s=0.45)
    # 0.45 .. 1.35: the scan under way at 0.45 is cut to its last 40 ms
    assert cut["window_s"] == pytest.approx(0.9)
    assert cut["busy_s"] == pytest.approx(0.04 + 8 * 0.09 + 0.05)
    assert cut["idle_gaps"][0][1] == pytest.approx(0.01)
    assert [name for name, _ in cut["device_ops"]] == ["scan"]


def test_reduce_averages_over_the_chips_that_ran_something():
    reduced = {"devices": {
        "/device:TPU:0": [["a", 0, 1_000_000_000], ["a", 3_000_000_000, 1_000_000_000]],
        "/device:TPU:1": [["a", 0, 2_000_000_000], ["a", 3_000_000_000, 1_000_000_000]],
        "/device:TPU:2": []}}
    got = trace.reduce_trace(reduced)
    assert got["chips"] == 2
    assert got["busy_s"] == pytest.approx(2.5)
    assert got["window_s"] == pytest.approx(4.0)


@pytest.mark.parametrize("reduced", [
    {}, {"devices": {}}, {"devices": {"/device:TPU:0": []}},
    {"devices": {"/device:TPU:0": [["zero", 5, 0]]}}])
def test_nothing_on_the_device_reads_as_nothing(reduced):
    # a reader then leaves its metric out: never a 0% share
    assert trace.reduce_trace(reduced) is None


def test_at_most_ten_ops_and_ten_gaps():
    evs = [[f"op{i}", i * 1000, 500] for i in range(40)]
    got = trace.reduce_trace({"devices": {"/device:TPU:0": evs}})
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) == 10


def test_recorded_trace_from_the_chip():
    reduced = json.loads(RECORDED.read_text())
    (plane, events), = reduced["devices"].items()
    assert plane.startswith("/device:TPU:")
    got = trace.reduce_trace(reduced)
    want = reduced["expected"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < got["busy_s"] < got["window_s"]
    # the by-hand reading of the same events: sort, merge, sum
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    busy, end = 0.0, spans[0][0]
    for lo, hi in spans:
        busy += max(0.0, hi - max(lo, end))
        end = max(end, hi)
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert got["device_ops"][0][0] == want["top_op"]
