"""`perf/hostplanes.py`: device idle gaps named by the program's host spans —
on intervals made by hand (a gap fully covered, partly covered, and not
covered at all) and on a small trace recorded on the chip
(tests/perf/data/hostplanes_small.json, a cut of PR 26's first by-hand
profile of `sift1m-exact.seq` on the v5e: the device ops and host annotations
of three requests, on the profiler session's one clock)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf import hostplanes, trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "hostplanes_small.json"

# one worker thread: a launch whose device part ends at 100, then host work
# up to 160; the loop thread writes the response from 170 to 180
HOST = {
    "/host:CPU#1 python": [
        ["launch", 0, 130, "s1"],
        ["launch.device", 10, 90, "s2"],
        ["launch.host_post", 100, 30, "s3"],
        ["search.fetch", 130, 30, "s4"],
    ],
    "/host:CPU#2 python": [
        ["http.respond", 170, 10, "s5"],
    ],
}


def test_a_gap_fully_covered_names_the_innermost_spans_by_covered_time():
    (names,) = hostplanes.attribute([[100, 160]], HOST)
    # `launch` is open until 130 too, but `launch.host_post` is inside it
    assert names == [["launch.host_post", pytest.approx(30e-9)],
                     ["search.fetch", pytest.approx(30e-9)]]


def test_a_gap_partly_covered_keeps_the_rest_unattributed():
    (names,) = hostplanes.attribute([[140, 200]], HOST)
    assert names == [["search.fetch", pytest.approx(20e-9)],
                     ["http.respond", pytest.approx(10e-9)],
                     ["unattributed", pytest.approx(30e-9)]]


def test_an_uncovered_gap_stays_unattributed_as_in_a_trace_without_spans():
    assert hostplanes.attribute([[300, 400]], HOST) == [
        [["unattributed", pytest.approx(100e-9)]]]
    assert hostplanes.attribute([[100, 160]], {}) == [
        [["unattributed", pytest.approx(60e-9)]]]


def test_two_threads_in_a_span_at_once_count_twice_and_cover_once():
    host = {"a": [["batch.wait", 0, 100, "x"]],
            "b": [["batch.wait", 50, 100, "y"]]}
    (names,) = hostplanes.attribute([[0, 200]], host)
    assert names == [["batch.wait", pytest.approx(200e-9)],
                     ["unattributed", pytest.approx(50e-9)]]


def test_idle_gaps_are_the_spaces_between_merged_busy_intervals():
    events = [["scan", 0, 100], ["copy", 90, 30], ["scan", 200, 50],
              ["scan", 400, 50]]
    assert hostplanes.idle_gaps(events) == [[120, 200], [250, 400]]
    got = hostplanes.longest_gaps({"devices": {"/device:TPU:0": events},
                                   "host": HOST}, top=1)
    assert got == {"/device:TPU:0": [{
        "gap_s": pytest.approx(150e-9), "start_ns": 250,
        "covered_by": [["unattributed", pytest.approx(150e-9)]]}]}


def test_the_span_names_are_the_programs():
    from opensearch_tpu.telemetry import spans

    assert hostplanes.SPAN_NAMES == set(spans.ALL)


def test_recorded_gaps_between_launches_are_named_by_the_hosts_spans():
    reduced = json.loads(RECORDED.read_text())
    (plane, events), = reduced["devices"].items()
    assert plane.startswith(trace.DEVICE_PLANE_PREFIX)
    gaps = hostplanes.longest_gaps(reduced, edge_s=0.0, top=2)[plane]
    assert len(gaps) == 2
    for gap in gaps:
        # one gap per request, between two launches: host work, named
        assert 3e-3 < gap["gap_s"] < 9e-3
        names = dict(gap["covered_by"])
        assert {"launch.device", "launch.host_post", "http.respond",
                "search.fetch", "http.parse", "launch.host_pre"} <= set(names)
        # what no span covers is the time the node spent outside any
        # request: the reply on its way, the client, the next request's read
        assert 0.3e-3 < names["unattributed"] < 0.25 * gap["gap_s"]
        # the recorded program (this PR's first chip call) kept the host
        # copies of the outputs inside launch.device: 2.4 ms of each gap
        assert 2.0e-3 < names["launch.device"] < 3.0e-3
    # host and device share the session's clock: every busy stretch of the
    # device lies inside one launch.device span of the host
    _busy, merged = trace.busy_union(events)
    device_spans = [(ev[1], ev[1] + ev[2])
                    for line in reduced["host"].values() for ev in line
                    if ev[0] == "launch.device"]
    assert len(device_spans) == 3 and len(merged) > 3
    for lo, hi in merged:
        assert any(a <= lo and hi <= b for a, b in device_spans), (lo, hi)


def test_launch_latency_is_what_separates_the_hosts_gap_from_the_devices():
    # two launches on one thread and one on another that overlaps nothing;
    # the device's ops start 5 after a window opens and end 20 before it
    # closes, and one op (at 500) is no launch's
    host = {"a": [["launch.device", 0, 100, "x"],
                  ["launch.device", 200, 100, "y"]],
            "b": [["launch.device", 400, 90, "z"], ["search", 0, 600, "s"]]}
    events = [["scan", 5, 40], ["sort", 50, 30], ["scan", 205, 75],
              ["scan", 405, 65], ["mask", 500, 10]]
    got = hostplanes.launch_latency(
        {"devices": {"/device:TPU:0": events}, "host": host})["/device:TPU:0"]
    assert got == {
        "launches": 3, "busy_outside": 1,
        "dispatch_ms": pytest.approx(5e-6), "fence_ms": pytest.approx(20e-6),
        "host_gap_ms": pytest.approx(100e-6),
        "device_gap_ms": pytest.approx(125e-6)}
    assert got["device_gap_ms"] == pytest.approx(
        got["host_gap_ms"] + got["dispatch_ms"] + got["fence_ms"])
    # a trace without the program's spans has no windows, and no device
    # plane gives no row
    bare = hostplanes.launch_latency({"devices": {"d": events}, "host": {}})
    assert bare == {"d": {"launches": 0, "busy_outside": 5,
                          "dispatch_ms": None, "fence_ms": None,
                          "host_gap_ms": None, "device_gap_ms": None}}
    assert hostplanes.launch_latency({"devices": {"d": []}, "host": host}) == {}


def test_recorded_launches_start_late_and_are_seen_to_end_late():
    got, = hostplanes.launch_latency(
        json.loads(RECORDED.read_text())).values()
    assert got["launches"] == 3 and got["busy_outside"] == 0
    # the jit dispatch, and the device's last op to the host copy's return
    # (the recorded program copied all three outputs inside launch.device)
    assert 0.1 < got["dispatch_ms"] < 0.5
    assert 1.5 < got["fence_ms"] < 3.5
    assert got["device_gap_ms"] == pytest.approx(
        got["host_gap_ms"] + got["dispatch_ms"] + got["fence_ms"], rel=0.05)
    assert 4.0 < got["device_gap_ms"] < 7.0
