"""The reductions of `perf/hostspans.py` — self time, each host-side
per-layer metric, "no capture -> None" — on a small hand-written capture
(tests/perf/data/capture_small.json: four whole searches, one inside the
profiler's starting stall, one cut by the session's end, one collection),
and on the capture a CPU dry run leaves behind: the rehearsal of what the
traced chip run does, since on a CPU the readers themselves stay silent."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from perf import hostspans  # noqa: E402
from _perf_dry import DOCS, REPO, dry_run  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = sorted(hostspans.METRICS)


@pytest.fixture()
def capture(tmp_path):
    (tmp_path / "telemetry").mkdir()
    shutil.copy(DATA / "capture_small.json",
                tmp_path / "telemetry" / "capture-7.json")
    return hostspans.load_capture(tmp_path / "telemetry", 1.5, 2.5)


def window(*walls_ms):
    return SimpleNamespace(t_from=[a / 1e3 for a, _ in walls_ms],
                           t_done=[b / 1e3 for _, b in walls_ms])


def test_the_capture_is_found_by_the_overlap_of_its_stamps(tmp_path, capture):
    assert capture["tracer"] == "t0" and len(capture["spans"]) == 91
    assert capture["spans"][0]["name"] == "http.parse"
    directory = tmp_path / "telemetry"
    assert hostspans.load_capture(directory, 0.1, 0.9) is None   # before it
    assert hostspans.load_capture(directory, 3.1, 4.0) is None   # after it
    assert hostspans.load_capture(directory, 2.9, 3.5) is not None
    assert hostspans.load_capture(tmp_path / "nowhere", 1.5, 2.5) is None
    (directory / "capture-8.json").write_text("{ cut short")
    assert hostspans.load_capture(directory, 1.5, 2.5)["tracer"] == "t0"


def test_only_whole_searches_inside_the_steady_span_are_read(capture):
    kept = hostspans.inside(capture)
    assert {s["trace_id"] for s in kept} == {
        "trace-a", "trace-b", "trace-e", "trace-f", None}
    assert sorted(hostspans.requests(kept)) == [
        "trace-a", "trace-b", "trace-e", "trace-f"]
    # a request that is not a search is nobody's sample
    stats = dict(capture, spans=[
        dict(s, attributes={**s["attributes"], "path": "/_nodes/stats"})
        if s["name"] == "http_request" else s for s in capture["spans"]])
    assert hostspans.requests(stats["spans"]) == {}


def test_self_time_is_duration_less_what_the_children_cover(capture):
    own = hostspans.self_times_ms(hostspans.inside(capture))
    assert own["a-qp"] == pytest.approx(0.8)       # 6.0 - wait 0.2 - launch 5
    assert own["a-search"] == pytest.approx(0.4)   # 8.0 - phases 7.6
    assert own["a-launch"] == pytest.approx(0.0)   # pre + device + post
    assert own["a-dev"] == pytest.approx(3.5)      # a leaf keeps all of it
    # http.respond follows its parent's close: it covers none of the root
    assert own["a-root"] == pytest.approx(10.0 - 0.2 - 0.01 - 8.0)
    table = hostspans.mean_self_ms_by_name(capture)
    assert table["search.query_phase"] == (4, pytest.approx(0.8))
    assert table["launch.host_post"] == (4, pytest.approx(1.5))
    assert table["runtime.gc"][0] == 0 if "runtime.gc" in table else True
    # overlapping children count once
    assert hostspans.covered_ns(0, 10, [(1, 4), (3, 6), (8, 12)]) == 7


@pytest.mark.parametrize("name,want", [
    ("http.parse_ms", 0.2),
    ("http.pool_wait_ms", 0.2),          # (0.15 + 0.25) / 2, from wait_ns
    ("http.respond_ms", 0.6),
    ("http.outside_ms", 12.35 - 11.2),   # client walls less root -> written
    ("service.self_ms", 2.8),
    ("batch.queue_wait_ms", 0.2),        # from queue_wait_ns
    ("launch.host_pre_ms", 0.5),
    ("host.post_launch_ms", 1.5),
    ("host.between_launch_ms", 16.5),    # e and f overlap: one gap, not two
    ("device.resident_bytes", 537001024),
])
def test_each_metric_from_the_hand_written_capture(capture, name, want):
    run = SimpleNamespace(window=window(
        (500.0, 510.0), (1099.0, 1125.0),            # before, and the edge
        (1299.5, 1311.2), (1319.5, 1332.5), (1339.5, 1351.2),
        (1341.0, 1354.0), (2744.0, 2760.0)))         # ..., and cut short
    assert hostspans.METRICS[name](capture, run) == pytest.approx(want)


def test_post_launch_counts_the_fetch_with_the_bookkeeping(capture):
    """What follows the fence: the first 0.5 ms of each `launch.host_post`
    made a `launch.fetch` leaves the metric where it was."""
    spans = []
    for s in capture["spans"]:
        if s["name"] == "launch.host_post":
            cut = s["start_ns"] + 500_000
            spans.append(dict(s, name="launch.fetch", end_ns=cut,
                              span_id=s["span_id"] + "-fetch"))
            s = dict(s, start_ns=cut)
        spans.append(s)
    split = dict(capture, spans=spans)
    assert hostspans.mean_duration_ms(
        split, "launch.host_post") == pytest.approx(1.0)
    assert hostspans.mean_duration_ms(
        split, "launch.fetch") == pytest.approx(0.5)
    assert hostspans.METRICS["host.post_launch_ms"](
        split, None) == pytest.approx(1.5)


def test_every_new_metric_has_its_reader_file_and_manifest_entry():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert (REPO / "perf" / "layers" / f"{name}.py").is_file()
        assert entries[name]["source"] == (
            "program_counter" if name == "device.resident_bytes"
            else "program_span")
        assert "workloads" not in entries[name]      # both cells report it
    assert len(NEW) == 10


@pytest.mark.parametrize("name", NEW)
def test_no_capture_or_no_device_trace_reads_none(tmp_path, monkeypatch,
                                                 name):
    config = {"name": "c", "corpus_seed": 1}
    counters = {"trace": ({"t": 1.5}, {"t": 2.5})}
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir", str(tmp_path)])
    # --trace 0: no trace, no counters
    assert hostspans.metric(SimpleNamespace(
        trace=None, counters={}, config=config, docs=8), name) is None
    # a CPU's traced run: a capture may be there, a device trace is not
    home = tmp_path / "c" / "corpus-1-docs-8" / "node" / "telemetry"
    home.mkdir(parents=True)
    shutil.copy(DATA / "capture_small.json", home / "capture-1.json")
    cpu = SimpleNamespace(trace=None, counters=counters, config=config,
                          docs=8, window=window())
    assert hostspans.metric(cpu, name) is None
    # a device trace and the parent's program: no telemetry directory
    parent = SimpleNamespace(trace={"busy_s": 1.0}, counters=counters,
                             config=config, docs=9, window=window())
    assert hostspans.metric(parent, name) is None
    # a device trace and a capture: the metric reads (found through
    # --cache-dir=, the other spelling)
    monkeypatch.setattr(sys, "argv", ["run.py", f"--cache-dir={tmp_path}"])
    chip = SimpleNamespace(
        trace={"busy_s": 1.0}, counters=counters, config=config, docs=8,
        window=window((1299.5, 1311.2)))
    assert hostspans.metric(chip, name) is not None
    assert chip._host_capture["tracer"] == "t0"      # loaded once, kept


@pytest.fixture(scope="module")
def dry(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("perf_hostspans")
    first = dry_run(tmp, "sift1m-exact.seq", "--trace", "0")
    assert first[0].returncode == 0, first[0].stderr[-3000:]
    # 7 s: the traced 3 s start 2 s in, and the first search after them
    # hands the capture to its writer (the later --seconds wins)
    proc, last = dry_run(tmp, "sift1m-exact.seq", "--trace", "1",
                         "--seconds", "7")
    assert proc.returncode == 0, proc.stderr[-3000:]
    home = (tmp / "cache" / "sift1m-exact"
            / f"corpus-20260930-docs-{DOCS}" / "node" / "telemetry")
    return tmp, last, home


def test_a_traced_cpu_dry_run_leaves_a_capture_and_reports_no_host_metric(
        dry):
    tmp, last, home = dry
    conf = json.loads((REPO / "perf/configs/sift1m-exact.json").read_text())
    assert home.parent.parent.name == (
        f"corpus-{conf['corpus_seed']}-docs-{DOCS}")
    # the untraced run left nothing; the traced one its one capture
    assert [p.name for p in home.iterdir()] == ["capture-1.json"]
    assert set(last["metrics"]) == {"batch.mean_merged"}
    assert last["dry_run"] is True and "breakdown" not in last


def test_the_reductions_run_on_the_dry_runs_capture(dry):
    """Not results (a CPU's times are none): that every reduction finds its
    spans in what the program really writes, in plausible relation."""
    tmp, last, home = dry
    capture = hostspans.load_capture(home, 0.0, 1e12)
    assert capture["dropped"] == 0
    lo, hi = hostspans.steady(capture)
    assert (hi - lo) / 1e9 > 2.0                    # 3 s less the edges
    searches = hostspans.requests(hostspans.inside(capture))
    assert len(searches) > 20
    run = SimpleNamespace(window=window(*(
        (start / 1e6 - 0.2, end / 1e6 + 0.3)
        for start, end in searches.values())))
    got = {name: hostspans.METRICS[name](capture, run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["http.outside_ms"] == pytest.approx(0.5)
    assert got["device.resident_bytes"] > DOCS * 128 * 4
    for name in NEW:
        assert got[name] >= 0, (name, got[name])
    # one client: nothing queues, and the time between launches is most
    # of a request less its device part
    assert got["http.pool_wait_ms"] < 5 and got["batch.queue_wait_ms"] < 5
    walls = [(end - start) / 1e6 for start, end in searches.values()]
    assert 0 < got["host.between_launch_ms"] < max(walls)
    table = hostspans.mean_self_ms_by_name(capture)
    assert {"http_request", "http.respond", "search", "search.fetch",
            "batch.wait", "launch", "launch.device"} <= set(table)
    own = hostspans.self_times_ms(hostspans.inside(capture))
    assert min(own.values()) >= 0
