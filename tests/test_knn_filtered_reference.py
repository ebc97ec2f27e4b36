"""Filtered exact kNN through the served path against a brute force written
here (PR 35): `knn.<field>.filter` = `bool.filter` of one or two `term`s on
a keyword field of tags, at the shapes of the benchmark's `knn-filtered`
(192-d integer-valued vectors, a bag of tags a document drawn from a skewed
law) and a tests' size. A node built the normal way answers as numpy over
the eligible rows does, on the mesh road (one shard, the mesh program) and
on the per-shard road (the mesh program switched off), two segments each.
The span also says how much the filter worked (PR 36): `postings`, the sum of
the request's tags' posting lengths, far under the field's (tag, row) pairs.

The mechanism that makes a filtered request visible: the counters
`knn.filter.requests` / `knn.filter.mask_bytes` (registered with the node,
so a 0 shows), the batcher's `dispatches` (a filtered mesh launch is one,
since this PR), and under a profiler session one `filter.mask` span a
filtered request with `eligible` = the brute force's count, and `filtered`
on `launch`.

Every profiler session here starts and stops inside a test of this file's
own process; nothing touches the profiler at import time."""

from __future__ import annotations

import glob
import json
import time

import numpy as np
import pytest

from opensearch_tpu.node import TpuNode
from opensearch_tpu.rest.handlers import nodes_stats, prometheus_metrics
from opensearch_tpu.search import distributed_serving, executor
from opensearch_tpu.telemetry import spans as span_names

DIMS = 192
DOCS = 3000
SEGMENTS = ((0, 1800), (1800, DOCS))
VOCAB = 300
K = 10
RARE = "rare"           # carried by RARE_ROWS alone: fewer than K eligible
RARE_ROWS = (7, 1801, 2999)
ROADS = ("mesh", "per-shard")


def _data():
    rng = np.random.default_rng(35)
    centres = rng.gamma(2.0, 18.0, (32, DIMS))
    vectors = np.clip(np.rint(centres[rng.integers(0, 32, DOCS)]
                              + rng.normal(0, 9.0, (DOCS, DIMS))), 0, 255)
    law = 1.0 / np.arange(1, VOCAB + 1) ** 1.1
    law /= law.sum()
    bags = [{f"t{t}" for t in rng.choice(VOCAB, int(rng.integers(1, 9)),
                                         p=law)} for _ in range(DOCS)]
    for i in RARE_ROWS:
        bags[i].add(RARE)
    queries = (centres[rng.integers(0, 32, 6)]
               + rng.normal(0, 9.0, (6, DIMS))).astype(np.float32)
    return vectors.astype(np.float32), bags, queries


VECTORS, BAGS, QUERIES = _data()


def _tags_of(row: int, n: int) -> tuple:
    """`n` tags of one row's bag (a query takes its tags from a row, as the
    benchmark's kind does), the commonest first."""
    return tuple(sorted(BAGS[row] - {RARE}, key=lambda t: int(t[1:])))[:n]


# (query number, tags): one and two terms, common and rarer words
CASES = [(0, _tags_of(11, 1)), (1, _tags_of(402, 1)), (2, _tags_of(1900, 1)),
         (3, _tags_of(25, 2)), (4, _tags_of(1234, 2)), (5, _tags_of(2500, 2))]


def _eligible(tags) -> list[int]:
    return [i for i in range(DOCS) if all(t in BAGS[i] for t in tags)]


def _brute_force(query: np.ndarray, tags, k: int):
    """(ids, scores) of the k nearest rows that carry every tag, nearest
    first, ties by id; score = 1 / (1 + squared l2), in float64."""
    rows = _eligible(tags)
    diff = VECTORS[rows].astype(np.float64) - query.astype(np.float64)
    d2 = np.einsum("nd,nd->n", diff, diff)
    order = sorted(range(len(rows)), key=lambda j: (d2[j], rows[j]))[:k]
    return [rows[j] for j in order], [1.0 / (1.0 + d2[j]) for j in order]


def _body(query: np.ndarray, tags, k: int = K) -> dict:
    return {"size": k, "query": {"knn": {"v": {
        "vector": [float(x) for x in query], "k": k,
        "filter": {"bool": {"filter": [
            {"term": {"tags": t}} for t in tags]}}}}}}


def _filter_counts(node) -> tuple[float, float]:
    counters = node.telemetry.metrics.stats()["counters"]
    return counters["knn.filter.requests"], counters["knn.filter.mask_bytes"]


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    node = TpuNode(tmp_path_factory.mktemp("knn-filtered"))
    # registered with the node: a 0 is shown as a 0, before any request
    assert _filter_counts(node) == (0, 0)
    node.create_index("f", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": DIMS,
                  "space_type": "l2"},
            "tags": {"type": "keyword"}}}})
    for lo, hi in SEGMENTS:
        node.bulk([("index", {"_index": "f", "_id": str(i)},
                    {"v": [int(x) for x in VECTORS[i]],
                     "tags": sorted(BAGS[i])})
                   for i in range(lo, hi)], refresh=True)
    assert _filter_counts(node) == (0, 0)
    yield node
    node.close()


@pytest.fixture(params=ROADS)
def road(request, node):
    """The mesh program serves a one-shard index's kNN; off, the per-shard
    fused branch does. Yields (road, how many launches that road made)."""
    mesh_was = distributed_serving.enabled
    distributed_serving.enabled = request.param == "mesh"

    def launches() -> int:
        if request.param == "mesh":
            return distributed_serving.stats["distributed_searches"]
        return executor.knn_path_stats["fused"]

    yield request.param, launches
    distributed_serving.enabled = mesh_was


@pytest.mark.parametrize("case", range(len(CASES)))
def test_a_filtered_knn_answers_as_brute_force_over_the_eligible_rows(
        node, road, case):
    _name, launches = road
    qi, tags = CASES[case]
    assert len(_eligible(tags)) >= K
    want_ids, want_scores = _brute_force(QUERIES[qi], tags, K)
    before = launches()
    resp = node.search("f", _body(QUERIES[qi], tags))
    assert launches() > before      # the road the test is named for
    assert resp["_shards"]["failed"] == 0
    hits = resp["hits"]["hits"]
    assert [int(h["_id"]) for h in hits] == want_ids
    for h, want in zip(hits, want_scores):
        assert set(tags) <= BAGS[int(h["_id"])]
        assert h["_score"] == pytest.approx(want, rel=1e-4)


def test_fewer_than_k_eligible_rows_are_returned_and_no_more(node, road):
    want_ids, want_scores = _brute_force(QUERIES[0], (RARE,), K)
    assert sorted(want_ids) == list(RARE_ROWS)
    resp = node.search("f", _body(QUERIES[0], (RARE,)))
    hits = resp["hits"]["hits"]
    assert [int(h["_id"]) for h in hits] == want_ids
    assert [h["_score"] for h in hits] == pytest.approx(want_scores, rel=1e-4)
    # both tags required: a common word AND the rare one
    both = (_tags_of(RARE_ROWS[1], 1)[0], RARE)
    want_ids, _ = _brute_force(QUERIES[1], both, K)
    assert 1 <= len(want_ids) < K
    resp = node.search("f", _body(QUERIES[1], both))
    assert [int(h["_id"]) for h in resp["hits"]["hits"]] == want_ids


def test_the_filter_counters_move_by_the_requests_sent(node, road):
    name, _launches = road
    requests0, bytes0 = _filter_counts(node)
    dispatches0 = node.knn_batcher.stats["dispatches"]
    merged0 = node.knn_batcher.stats["merged_queries"]
    for qi, tags in CASES[:3]:
        node.search("f", _body(QUERIES[qi], tags))
    requests, mask_bytes = _filter_counts(node)
    assert requests == requests0 + 3
    # a mask is as wide as what the launch scans: the padded rows, a byte
    # each, on either road
    assert mask_bytes - bytes0 >= 3 * DOCS
    # a filtered launch is a dispatch of one query, on the mesh road too
    segments = 1 if name == "mesh" else len(SEGMENTS)
    assert node.knn_batcher.stats["dispatches"] == dispatches0 + 3 * segments
    assert node.knn_batcher.stats["merged_queries"] == merged0 + 3 * segments
    # an unfiltered search moves neither counter
    node.search("f", {"size": K, "query": {"knn": {"v": {
        "vector": [float(x) for x in QUERIES[0]], "k": K}}}})
    assert _filter_counts(node) == (requests, mask_bytes)
    # `_nodes/stats` and Prometheus show both
    _status, stats = nodes_stats(node, {}, {}, None)
    shown = next(iter(stats["nodes"].values()))["telemetry"]["counters"]
    assert shown["knn.filter.requests"] == requests
    assert shown["knn.filter.mask_bytes"] == mask_bytes
    _status, text = prometheus_metrics(node, {}, {}, None)
    assert "opensearch_tpu_knn_filter_requests" in text
    assert "opensearch_tpu_knn_filter_mask_bytes" in text


def _traced(node, tmp_path, work) -> dict:
    """Run `work()` under a profiler session with the benchmark launcher's
    options; the capture the session left on disk, spans as dicts. The
    first request after the session hands the capture to its writer."""
    import jax

    before = set(glob.glob(str(node.data_path / "telemetry" / "*.json")))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    node.search("f", {"size": 1, "query": {"match_all": {}}})
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        new = set(glob.glob(
            str(node.data_path / "telemetry" / "*.json"))) - before
        if new:
            doc = json.loads(open(new.pop()).read())
            doc["spans"] = [dict(zip(doc["fields"], r))
                            for r in doc["records"]]
            return doc
        time.sleep(0.05)
    raise AssertionError("no capture file after the session ended")


def test_every_filtered_request_holds_one_filter_mask_span(
        node, road, tmp_path):
    name, _launches = road
    for qi, tags in CASES:      # warm: every program compiled
        node.search("f", _body(QUERIES[qi], tags))
    plain = {"size": K, "query": {"knn": {"v": {
        "vector": [float(x) for x in QUERIES[0]], "k": K}}}}
    node.search("f", plain)

    def work():
        for qi, tags in CASES:
            node.search("f", _body(QUERIES[qi], tags))
        node.search("f", plain)

    doc = _traced(node, tmp_path, work)
    roots = sorted((s for s in doc["spans"] if s["name"] == "search"
                    and s["parent_id"] is None),
                   key=lambda s: s["start_ns"])
    assert len(roots) == len(CASES) + 1
    for root, (_qi, tags) in zip(roots, CASES):
        tree = [s for s in doc["spans"] if s["trace_id"] == root["trace_id"]]
        masks = [s for s in tree if s["name"] == span_names.FILTER_MASK]
        assert len(masks) == 1
        got = masks[0]["attributes"]
        assert got["eligible"] == len(_eligible(tags))
        assert got["clauses"] == len(tags)
        assert got["rows"] >= DOCS
        # the work followed the tags' posting lists, not the field's pairs
        assert got["postings"] == sum(
            sum(t in bag for bag in BAGS) for t in tags)
        assert got["postings"] * 3 < sum(len(bag) for bag in BAGS)
        # the mesh road uploads the mask it flattened on the host; the
        # per-shard road makes it where it is used
        assert got["upload_bytes"] == (got["rows"] if name == "mesh" else 0)
        launches = [s for s in tree if s["name"] == span_names.LAUNCH]
        assert launches and all(
            s["attributes"]["filtered"] == 1 for s in launches)
        # the mask is made before the launch that uses it: on the mesh road
        # inside `launch.host_pre`, on the per-shard road before any launch
        first = min(s["start_ns"] for s in tree if s["name"] == (
            span_names.LAUNCH_DEVICE if name == "mesh"
            else span_names.LAUNCH))
        assert masks[0]["end_ns"] <= first
    tree = [s for s in doc["spans"] if s["trace_id"] == roots[-1]["trace_id"]]
    assert not [s for s in tree if s["name"] == span_names.FILTER_MASK]
    assert [s["attributes"]["filtered"] for s in tree
            if s["name"] == span_names.LAUNCH] == [0] * (
                1 if name == "mesh" else len(SEGMENTS))
    # the capture's counter snapshots carry the filter counters
    opened, closed = doc["counters"]["open"], doc["counters"]["close"]
    assert (closed["knn_filter"]["requests"]
            == opened["knn_filter"]["requests"] + len(CASES))


def test_filter_mask_is_a_detail_span_kept_beside_all():
    assert span_names.FILTER_MASK == "filter.mask"
    assert span_names.FILTER_MASK not in span_names.ALL
