"""Request-detail spans along the served `_search` path (PR 26): off unless
a `jax.profiler` session runs, a whole request tree when one does, written
twice (the profiler's trace, the tracer's capture), and the capture on disk
once the session has ended, without a shutdown.

Every profiler session here starts and stops inside a test of this file's
own process; nothing touches the profiler at import time."""

from __future__ import annotations

import asyncio
import glob
import json
import random
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from opensearch_tpu.node import TpuNode
from opensearch_tpu.rest.http import HttpServer
from opensearch_tpu.telemetry import spans as span_names
from opensearch_tpu.telemetry import tracing
from opensearch_tpu.telemetry.export import (
    MemorySink,
    SpanExporter,
    parse_otlp,
)

DIMS = 8
QUERY = {"size": 5, "query": {"knn": {"vec": {"vector": [0.25] * DIMS,
                                              "k": 5}}}}
# one served kNN `_search`, root first (search.collect and launch.fetch
# belong to the per-shard ANN path and are not on the exact path's tree)
REQUEST_TREE = {
    span_names.HTTP_REQUEST: None,
    span_names.HTTP_PARSE: span_names.HTTP_REQUEST,
    span_names.HTTP_POOL_WAIT: span_names.HTTP_REQUEST,
    span_names.SEARCH: span_names.HTTP_REQUEST,
    span_names.SEARCH_PARSE: span_names.SEARCH,
    span_names.SEARCH_QUERY_PHASE: span_names.SEARCH,
    span_names.BATCH_WAIT: span_names.SEARCH_QUERY_PHASE,
    span_names.LAUNCH: span_names.SEARCH_QUERY_PHASE,
    span_names.LAUNCH_HOST_PRE: span_names.LAUNCH,
    span_names.LAUNCH_DEVICE: span_names.LAUNCH,
    span_names.LAUNCH_HOST_POST: span_names.LAUNCH,
    span_names.SEARCH_REDUCE: span_names.SEARCH,
    span_names.SEARCH_FETCH: span_names.SEARCH,
    span_names.SEARCH_RESPOND: span_names.SEARCH,
    span_names.HTTP_RESPOND: span_names.HTTP_REQUEST,
}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


SERVER = {}     # "port": chosen by the `node` fixture, free in this process


def _req(method, path, body=None, ndjson=None):
    data, ctype = None, "application/json"
    if ndjson is not None:
        data = ("\n".join(json.dumps(x) for x in ndjson) + "\n").encode()
        ctype = "application/x-ndjson"
    elif body is not None:
        data = json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{SERVER['port']}{path}", data=data, method=method,
        headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    node = TpuNode(tmp_path_factory.mktemp("span-node"))
    SERVER["port"] = _free_port()
    srv = HttpServer(node, "127.0.0.1", SERVER["port"])
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(srv.serve_forever())
        except RuntimeError:
            pass  # loop.stop() at teardown interrupts serve_forever

    threading.Thread(target=run, daemon=True).start()
    for _ in range(100):
        try:
            _req("GET", "/")
            break
        except OSError:
            time.sleep(0.05)
    _req("PUT", "/vecs", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {
            "vec": {"type": "knn_vector", "dimension": DIMS}}}})
    rng = np.random.default_rng(7)
    lines = []
    for i in range(256):
        lines.append({"index": {"_id": str(i)}})
        lines.append({"vec": [float(x) for x in rng.normal(size=DIMS)]})
    assert _req("POST", "/vecs/_bulk", ndjson=lines)[1]["errors"] is False
    _req("POST", "/vecs/_refresh")
    assert _req("POST", "/vecs/_search", QUERY)[0] == 200   # warm: compiled
    yield node
    loop.call_soon_threadsafe(loop.stop)
    node.close()


def _capture_stats() -> dict:
    stats = _req("GET", "/_nodes/stats/telemetry")[1]
    return next(iter(stats["nodes"].values()))["telemetry"]["capture"]


def _traced(node, tmp_path, work) -> tuple[dict, str]:
    """Run `work()` under a profiler session with the launcher's options;
    (the capture the session left on disk, the trace directory). The
    session ends and the node is NOT shut down: the first request after it
    hands the capture to its writer."""
    import jax

    before = set(glob.glob(str(node.data_path / "telemetry" / "*.json")))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        work()
        assert _capture_stats()["open"] is True
    finally:
        jax.profiler.stop_trace()
    assert _capture_stats()["open"] is False    # this request closed it
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        new = set(glob.glob(
            str(node.data_path / "telemetry" / "*.json"))) - before
        if new:
            doc = json.loads(open(new.pop()).read())
            doc["spans"] = [dict(zip(doc["fields"], r))
                            for r in doc["records"]]
            return doc, trace_dir
        time.sleep(0.05)
    raise AssertionError("no capture file after the session ended")


def test_off_by_default_no_capture_no_detail_and_the_two_spans_as_before(
        node):
    tracer = node.telemetry.tracer
    tracer.clear()
    assert tracing.profiler_session_on() is False
    assert _req("POST", "/vecs/_search", QUERY)[0] == 200
    ring = tracer.finished_spans()
    assert [s.name for s in ring] == ["search", "http_request"]
    search, root = ring
    assert root.parent_id is None and search.parent_id == root.span_id
    assert search.trace_id == root.trace_id
    assert root.attributes == {"method": "POST", "path": "/vecs/_search",
                               "lane": "interactive", "status": 200}
    assert root.detail is None and search.detail is None
    # a span site outside a detailed request is the shared no-op
    assert tracing.detail(span_names.LAUNCH) is tracing.detail(
        span_names.BATCH_WAIT)
    with tracing.detail(span_names.LAUNCH) as span:
        span.set_attribute("ignored", 1)
        assert span.span_id is None and span.detail is None
    stats = _capture_stats()
    assert stats == {"open": False, "records": 0, "dropped": 0,
                     "captures": 0, "last_file": None}
    assert not (node.data_path / "telemetry").exists()
    # to_dict carries the start, so a ring span can be put on a time line
    assert root.to_dict()["start_ns"] == root.start_ns > 0


def test_one_search_under_a_profiler_session_yields_the_whole_tree(
        node, tmp_path, monkeypatch):
    from opensearch_tpu.search import distributed_serving

    node.telemetry.tracer.clear()
    # when the launch's rows are on the host: `unpack` is handed them
    on_host = []
    real_unpack = distributed_serving.unpack

    def unpack(packed, k_final, s):
        on_host.append((time.perf_counter_ns(), type(packed)))
        return real_unpack(packed, k_final, s)

    monkeypatch.setattr(distributed_serving, "unpack", unpack)
    t_before = time.perf_counter_ns(), time.time_ns()
    doc, trace_dir = _traced(
        node, tmp_path,
        lambda: _req("POST", "/vecs/_search", QUERY))
    t_after = time.perf_counter_ns(), time.time_ns()
    searches = [s for s in doc["spans"] if s["name"] == "http_request"
                and s["attributes"]["path"] == "/vecs/_search"]
    assert len(searches) == 1
    trace_id = searches[0]["trace_id"]
    tree = [s for s in doc["spans"] if s["trace_id"] == trace_id]
    by_id = {s["span_id"]: s for s in tree}
    # every span of the table, once, under the parent the table gives it
    # (the mesh program's one packed output is copied inside launch.device,
    # as the fence: a mesh launch holds no launch.fetch)
    assert sorted(s["name"] for s in tree) == sorted(REQUEST_TREE)
    for s in tree:
        parent = by_id.get(s["parent_id"])
        assert (parent["name"] if parent else None) == REQUEST_TREE[
            s["name"]], s
        assert s["end_ns"] >= s["start_ns"]
        if parent is None:
            continue
        if s["name"] == span_names.HTTP_RESPOND:
            # the write follows the root's close
            assert s["start_ns"] >= parent["end_ns"]
        else:
            assert parent["start_ns"] <= s["start_ns"]
            assert s["end_ns"] <= parent["end_ns"]
    # spans open and close on the thread that does the work: per thread
    # they nest or are disjoint, never straddle
    for thread in {s["thread"] for s in doc["spans"]}:
        mine = sorted((s for s in doc["spans"] if s["thread"] == thread),
                      key=lambda s: (s["start_ns"], -s["end_ns"]))
        stack = []
        for s in mine:
            while stack and stack[-1]["end_ns"] <= s["start_ns"]:
                stack.pop()
            assert not stack or s["end_ns"] <= stack[-1]["end_ns"], s
            stack.append(s)
    # the loop thread hands over, a pool worker serves
    names = {s["name"]: s for s in tree}
    # the device span ends with the fence: the launch's one transfer has
    # put the rows on the host before it closes, and nothing is copied after
    (rows_at, rows_type), = on_host
    assert rows_type is np.ndarray
    assert (names["launch.device"]["start_ns"] < rows_at
            <= names["launch.device"]["end_ns"])
    assert (names["launch.device"]["end_ns"]
            <= names["launch.host_post"]["start_ns"])
    assert names["http_request"]["thread"] != names["search"]["thread"]
    assert names["http.pool_wait"]["thread"] == names["search"]["thread"]
    wait = names["http.pool_wait"]["attributes"]
    assert wait["wait_ns"] > 0 and wait["workers"] >= 1
    assert names["batch.wait"]["attributes"]["queue_wait_ns"] >= 0
    assert names["launch"]["attributes"]["merged"] == 1
    assert names["launch"]["attributes"]["host_copies"] == 1
    assert names["launch.device"]["attributes"] == {"retraced": False}
    assert names["http.respond"]["attributes"]["bytes"] > 0
    # both clock pairs, taken together, around the session
    for pair, lo, hi in (("opened", t_before, t_after),
                         ("closed", t_before, t_after)):
        assert lo[0] <= doc[pair]["perf_counter_ns"] <= hi[0]
        assert lo[1] <= doc[pair]["time_ns"] <= hi[1]
    assert doc["opened"]["perf_counter_ns"] < doc["closed"]["perf_counter_ns"]
    # both counter snapshots: one launch between them
    opened, closed = doc["counters"]["open"], doc["counters"]["close"]
    assert (closed["knn_batch"]["dispatches"]
            - opened["knn_batch"]["dispatches"]) == 1
    assert closed["device_resident_bytes"] > 0
    assert len(opened["gc"]) == len(closed["gc"]) == 3
    assert doc["dropped"] == 0 and doc["fields"] == list(
        tracing.CAPTURE_FIELDS)
    stats = _capture_stats()
    assert stats["captures"] == 1 and stats["last_file"].endswith(".json")
    assert stats["records"] == len(doc["spans"])
    # the always-on pair still reached the ring, detail spans did not
    ring = {s.name for s in node.telemetry.tracer.finished_spans()}
    assert ring == {"search", "http_request"}
    # the same spans are in the profiler's own trace, ids as stats
    from jax.profiler import ProfileData

    xplane = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")[-1]
    annotated = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in REQUEST_TREE:
                        annotated[dict(ev.stats).get("span_id")] = ev.name
    for s in tree:
        assert annotated.get(s["span_id"]) == s["name"]


def test_the_ann_closure_opens_the_same_launch_spans(node, tmp_path):
    """The per-shard IVF-PQ launch (`search/executor.py`'s closure over
    `ops/ivfpq.select_probes` / `search_probed`): the four launch spans one
    after the other under the batcher's `launch`, then `search.collect`,
    which says whether the request built an n_pad-wide array (`dense`)."""
    _req("PUT", "/ann", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"vec": {
            "type": "knn_vector", "dimension": DIMS, "space_type": "l2",
            "method": {"name": "ivf_pq", "parameters": {
                "nlist": 4, "m": 4, "ks": 16}}}}}})
    rng = np.random.default_rng(11)
    lines = []
    for i in range(512):
        lines.append({"index": {"_id": str(i)}})
        lines.append({"vec": [float(x) for x in rng.normal(size=DIMS)]})
    assert _req("POST", "/ann/_bulk", ndjson=lines)[1]["errors"] is False
    _req("POST", "/ann/_refresh")
    assert _req("POST", "/ann/_search", QUERY)[0] == 200    # warm: compiled
    ann_before = node.knn_batcher.stats["ann_dispatches"]
    doc, _ = _traced(node, tmp_path,
                     lambda: _req("POST", "/ann/_search", QUERY))
    assert node.knn_batcher.stats["ann_dispatches"] == ann_before + 1
    root = next(s for s in doc["spans"] if s["name"] == "http_request"
                and s["attributes"]["path"] == "/ann/_search")
    tree = [s for s in doc["spans"] if s["trace_id"] == root["trace_id"]]
    launch = next(s for s in tree if s["name"] == "launch")
    steps = sorted((s for s in tree if s["parent_id"] == launch["span_id"]),
                   key=lambda s: s["start_ns"])
    assert [s["name"] for s in steps] == [
        "launch.host_pre", "launch.device", "launch.fetch",
        "launch.host_post"]
    for earlier, later in zip(steps, steps[1:]):
        assert earlier["end_ns"] <= later["start_ns"]
    assert launch["start_ns"] <= steps[0]["start_ns"]
    assert steps[-1]["end_ns"] <= launch["end_ns"]
    by_id = {s["span_id"]: s for s in tree}
    # both sites, the launch's row and the hits: the winners stayed short
    collects = [s for s in tree if s["name"] == "search.collect"]
    assert len(collects) == 2
    for collect in collects:
        assert by_id[collect["parent_id"]]["name"] == "search.query_phase"
        assert collect["start_ns"] >= launch["end_ns"]
        assert collect["attributes"] == {"dense": 0}
    # the same request with aggregations indexes by document: the site
    # that builds the n_pad-wide view says so, and the counters agree
    with_aggs = dict(QUERY, aggs={"n": {"value_count": {"field": "_id"}}})
    doc, _ = _traced(node, tmp_path / "aggs",
                     lambda: _req("POST", "/ann/_search", with_aggs))
    dense = [s["attributes"]["dense"] for s in sorted(
        (s for s in doc["spans"] if s["name"] == "search.collect"),
        key=lambda s: s["start_ns"])]
    assert dense == [0, 1]
    opened, closed = doc["counters"]["open"], doc["counters"]["close"]
    assert closed["knn_collect"]["dense"] == opened["knn_collect"]["dense"] + 1
    assert closed["knn_collect"]["sparse"] == opened["knn_collect"]["sparse"]


def test_a_follower_names_the_leaders_launch(node, tmp_path):
    batcher = node.knn_batcher
    batcher.reset()
    batcher.configure(max_wait_ms=5_000, max_batch_size=2)
    try:
        def two_at_once():
            threads = [threading.Thread(
                target=_req, args=("POST", "/vecs/_search", QUERY))
                for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive()

        doc, _ = _traced(node, tmp_path, two_at_once)
    finally:
        batcher.configure(max_wait_ms=2, max_batch_size=32)
        batcher.reset()
    launches = [s for s in doc["spans"] if s["name"] == "launch"]
    assert len(launches) == 1 and launches[0]["attributes"] == {
        "merged": 2, "reason": "size",
        # from the mesh program: the launch's shape (one shard, one
        # device), its one device -> host transfer, and no filter
        "devices": 1, "shards": 1, "b_pad": 2, "host_copies": 1,
        "filtered": 0}
    waits = {s["attributes"]["reason"]: s for s in doc["spans"]
             if s["name"] == "batch.wait"}
    assert set(waits) == {"size", "follower"}
    follower, leader = waits["follower"], waits["size"]
    assert follower["attributes"]["leader"] == launches[0]["span_id"]
    assert follower["attributes"]["merged"] == 2
    # the launch belongs to the leader's trace, not the follower's
    assert launches[0]["trace_id"] == leader["trace_id"]
    assert follower["trace_id"] != leader["trace_id"]
    # the follower waited through the launch; the leader's wait ended
    # when it took the batch
    assert follower["end_ns"] >= launches[0]["end_ns"] - 1
    assert leader["end_ns"] <= launches[0]["start_ns"]
    assert 0 <= follower["attributes"]["queue_wait_ns"] <= (
        follower["end_ns"] - follower["start_ns"])


def test_a_collection_during_a_capture_is_a_runtime_gc_span(node, tmp_path):
    import gc

    def work():
        _req("POST", "/vecs/_search", QUERY)
        gc.collect()
        _req("POST", "/vecs/_search", QUERY)

    doc, _ = _traced(node, tmp_path, work)
    collections = [s for s in doc["spans"] if s["name"] == "runtime.gc"]
    assert any(s["attributes"]["generation"] == 2 for s in collections)
    for s in collections:
        assert s["trace_id"] is None and s["parent_id"] is None
        assert s["attributes"]["collected"] >= 0
    assert node.telemetry.tracer._on_gc not in gc.callbacks   # hook is gone
    assert (doc["counters"]["close"]["gc"][2]["collections"]
            > doc["counters"]["open"]["gc"][2]["collections"])


def test_a_capture_outlives_its_session_until_its_requests_have_ended(
        monkeypatch):
    """A request of seconds (a filtered kNN search under 32 clients) is
    held whole: the capture closes when the last request that opened under
    the session ends, and details the requests that open meanwhile."""
    session = {"on": True}
    monkeypatch.setattr(tracing, "profiler_session_on",
                        lambda: session["on"])
    monkeypatch.setattr(tracing, "_annotate", lambda span: None)
    tracer = tracing.Tracer(name="drain")
    slow = tracer.begin_span("search")
    assert slow.detail is not None and tracer.capture_stats()["open"]
    session["on"] = False
    # the session is over and `slow` is in flight: still open, still detail
    with tracer.start_span("search") as meanwhile:
        assert meanwhile.detail is slow.detail
    assert tracer.capture_stats()["open"] is True
    capture = slow.detail
    tracer.end_span(slow)
    assert tracer.capture_stats()["open"] is False
    assert capture.closed[0] >= slow.end_ns
    assert {r[2] for r in capture.records} == {slow.span_id,
                                               meanwhile.span_id}
    # nothing is detailed once it has closed
    with tracer.start_span("search") as after:
        assert after.detail is None


def test_a_capture_drains_for_a_bounded_time(monkeypatch):
    session = {"on": True}
    monkeypatch.setattr(tracing, "profiler_session_on",
                        lambda: session["on"])
    monkeypatch.setattr(tracing, "_annotate", lambda span: None)
    monkeypatch.setattr(tracing, "CAPTURE_DRAIN_S", 0.0)
    tracer = tracing.Tracer(name="stuck")
    stuck = tracer.begin_span("search")
    session["on"] = False
    with tracer.start_span("search") as later:
        assert later.detail is None     # overdue: closed, not drained
    assert tracer.capture_stats()["open"] is False
    tracer.end_span(stuck)              # a late end changes nothing


def test_past_the_cap_records_are_counted_as_dropped_never_kept(monkeypatch):
    tracer = tracing.Tracer(name="cap")
    monkeypatch.setattr(tracing, "CAPTURE_MAX_RECORDS", 3)
    capture = tracing.Capture(tracer)
    for i in range(5):
        capture.add("launch", "t", f"s{i}", None, i, i + 1, None)
    assert len(capture.records) == 3 and capture.dropped == 2
    assert [r[2] for r in capture.records] == ["s0", "s1", "s2"]


def test_only_the_newest_four_capture_files_are_kept(tmp_path, monkeypatch):
    monkeypatch.setattr(tracing, "CAPTURE_GRACE_S", 0.0)
    tracer = tracing.Tracer(name="keep")
    tracer.capture_dir = tmp_path / "telemetry"
    for _ in range(6):
        capture = tracing.Capture(tracer)
        capture.add("launch", "t", "s", None, 1, 2, {"merged": 1})
        capture.closed = tracing.clock_pair()
        capture.counters_close = tracer.read_capture_counters()
        tracer._write_capture(capture)
    names = sorted(p.name for p in tracer.capture_dir.iterdir())
    assert names == [f"capture-{n}.json" for n in (3, 4, 5, 6)]
    doc = json.loads((tracer.capture_dir / "capture-6.json").read_text())
    assert doc["records"] == [["launch", "t", "s", None,
                               threading.get_ident(), 1, 2, {"merged": 1}]]


def test_exported_spans_carry_unix_time_and_round_trip():
    sink = MemorySink()
    tracer = tracing.Tracer(name="n1")
    tracer.exporter = SpanExporter(
        sink, service_name="n1", synchronous=True, sample_ratio=0.0,
        slow_threshold_ms=0, rng=random.Random(0))
    with tracer.start_span("root") as root:
        root.add_event("noted", {"n": 1})
        with tracer.start_span("child", {"k": "v"}):
            pass
    taken = time.time_ns()
    tracer.exporter.flush()
    (doc,) = sink.docs
    exported = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
    for span in exported:
        assert abs(int(span["startTimeUnixNano"]) - taken) < 1_000_000_000
        assert int(span["endTimeUnixNano"]) >= int(span["startTimeUnixNano"])
    # parse_otlp takes the anchor back: the ring's tree, stamp for stamp
    back = {s.span_id: s for s in parse_otlp(json.loads(json.dumps(doc)))}
    ring = tracer.finished_spans()
    assert len(back) == len(ring) == 2
    for span in ring:
        assert back[span.span_id].to_dict() == span.to_dict()
        assert back[span.span_id].end_ns == span.end_ns
