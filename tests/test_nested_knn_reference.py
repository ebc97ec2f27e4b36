"""A `nested` `knn` over a multi-vector field (a `knn_vector` under a
`nested` path: one row a nested object, each row with its document)
through the served `_search` against a plain numpy reference, on seeded
random vectors at a tests' size and the benchmark's width (768-d, l2,
k = 10, score mode max): the 10 best DOCUMENTS, each scored 1 / (1 + d2)
of its nearest chunk, equal to brute force over every chunk, then the best
chunk a document.

Both roads a top-level `knn` takes: the mesh program through the batcher
(the default) and the per-shard executor (the mesh switched off), each with
the XLA twin of the scan (the CPU's) and with the Pallas kernel itself in
interpret mode. Documents of one and of a hundred chunks, a document whose
chunks crowd the flat top 10, two segments; deletes, a merge and a restart
that recovers from disk; k over the documents there are; `hits.total` and
`_count` counting documents; the bfloat16 control outside the
configuration's limits; what is refused with a 400; the `knn.nested` span,
the launch's `nested` and the two counters.

Every profiler session here starts and stops inside a test of this file's
own process; nothing touches the profiler at import time."""

from __future__ import annotations

import glob
import json
import time
from pathlib import Path

import numpy as np
import pytest

from opensearch_tpu.common.errors import IllegalArgumentException
from opensearch_tpu.node import TpuNode
from opensearch_tpu.rest import handlers
from opensearch_tpu.telemetry import spans as span_names

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "perf/configs/knn-nested-chunks.json").read_text())
INDEX = "chunked"
PATH, FIELD = "passage_chunk_embedding", "passage_chunk_embedding.knn"
D = 768
DOCS = 480
FIRST_SEGMENT = 260     # two `_bulk` requests, a refresh after each
CROWDED, SINGLE = 7, 11     # 100 chunks near query 0; one chunk
QUERIES = 8
K = 10
COUNTERS = ("search.knn.nested.requests", "search.knn.nested.children")
# the program scores from |q|^2 - 2 q.c + |c|^2 in float32, as every exact
# kNN here: at 768-d a difference of numbers a hundred times its size, whose
# float32 sums carry some units of error (up to 2.5e-4 of a score here)
REL = 1e-3
MAPPING = {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
           "mappings": {"properties": {PATH: {
               "type": "nested", "properties": {FIELD.split(".")[1]: {
                   "type": "knn_vector", "dimension": D,
                   "space_type": "l2"}}}}}}


def _data(seed: int = 2**31 + 41, docs: int = DOCS):
    """(chunks float32 [rows, D] integer-valued 0-255, ptr [docs + 1],
    queries float32 [QUERIES, D]). Query 0 sits among CROWDED's 100 chunks,
    so the 100 rows nearest to it are all that document's; every other query
    is a chunk of a document plus noise."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, 6, docs)
    planted = docs > SINGLE
    if planted:
        counts[CROWDED], counts[SINGLE] = 100, 1
    ptr = np.zeros(docs + 1, np.int64)
    np.cumsum(counts, out=ptr[1:])
    centres = rng.integers(10, 110, (docs, D)).astype(np.float64)
    owner = np.repeat(np.arange(docs), counts)
    chunks = centres[owner] + rng.normal(0, 6, (len(owner), D))
    picks = rng.integers(0, len(owner), QUERIES)
    queries = chunks[picks] + rng.normal(0, 5, (QUERIES, D))
    if planted:
        chunks[ptr[CROWDED]:ptr[CROWDED + 1]] = (
            centres[CROWDED] + rng.normal(0, 4, (100, D)))
        queries[0] = centres[CROWDED] + rng.normal(0, 4, D)
        queries[1] = chunks[ptr[SINGLE]] + rng.normal(0, 5, D)
    chunks = np.clip(np.rint(chunks), 0, 255).astype(np.float32)
    return chunks, ptr, queries.astype(np.float32)


def reference(chunks, ptr, q, k: int = K, dead=()):
    """Brute force in float64: every chunk's squared distance, the least a
    document, the documents by it (dead ones left out), ties by id."""
    d2 = np.sum((chunks.astype(np.float64) - q.astype(np.float64)) ** 2,
                axis=1)
    best = np.minimum.reduceat(d2, ptr[:-1])
    best[list(dead)] = np.inf
    order = np.lexsort((np.arange(len(best)), best))[:k]
    order = order[np.isfinite(best[order])]
    return order.tolist(), (1.0 / (1.0 + best[order])).tolist()


def _round_bf16(x: np.ndarray) -> np.ndarray:
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def _bulk(node, chunks, ptr, lo: int, hi: int, index: str = INDEX):
    lines = []
    for doc in range(lo, hi):
        lines.append({"index": {"_id": str(doc)}})
        lines.append({PATH: [{"knn": row.tolist()}
                             for row in chunks[ptr[doc]:ptr[doc + 1]]]})
    status, resp = handlers.bulk(node, {"index": index}, {}, lines)
    assert status == 200 and resp["errors"] is False, resp
    node.refresh(index)


def _body(q, k: int = K, size: int = K, score_mode: str = "max") -> dict:
    return {"size": size, "_source": {"excludes": [PATH]},
            "query": {"nested": {"path": PATH, "score_mode": score_mode,
                                 "query": {"knn": {FIELD: {
                                     "vector": q.tolist(), "k": k}}}}}}


def _search(node, body: dict, index: str = INDEX) -> dict:
    status, resp = handlers.search(node, {"index": index}, {}, body)
    assert status == 200 and resp["_shards"]["failed"] == 0
    return resp


def _hits(resp: dict):
    hits = resp["hits"]["hits"]
    return [int(h["_id"]) for h in hits], [h["_score"] for h in hits]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    chunks, ptr, queries = _data()
    node = TpuNode(tmp_path_factory.mktemp("nested") / "node")
    # registered with the node: a 0 is shown as a 0, before any request
    shown = node.telemetry.metrics.stats()["counters"]
    assert tuple(shown[name] for name in COUNTERS) == (0, 0)
    node.create_index(INDEX, MAPPING)
    _bulk(node, chunks, ptr, 0, FIRST_SEGMENT)
    _bulk(node, chunks, ptr, FIRST_SEGMENT, DOCS)
    yield node, chunks, ptr, queries
    node.close()


@pytest.fixture(params=["mesh", "shard"])
def road(request, monkeypatch):
    """The mesh program (the default road), or the per-shard executor."""
    from opensearch_tpu.search import distributed_serving

    monkeypatch.setattr(distributed_serving, "enabled",
                        request.param == "mesh")
    return request.param


@pytest.fixture
def interpreted(monkeypatch):
    """The Pallas kernel itself, in interpret mode, in the XLA twin's place
    (the one rule sends a forced "pallas" on the CPU there)."""
    from opensearch_tpu.search.ann import default_config

    monkeypatch.setattr(default_config, "exact_kernel", "pallas")


@pytest.mark.parametrize("i", range(QUERIES))
def test_the_served_top_10_is_the_references(world, road, i):
    node, chunks, ptr, queries = world
    got_ids, got_scores = _hits(_search(node, _body(queries[i])))
    want_ids, want_scores = reference(chunks, ptr, queries[i])
    assert got_ids == want_ids
    assert got_scores == pytest.approx(want_scores, rel=REL)


@pytest.mark.parametrize("i", [0, 1, 4])
def test_the_pallas_kernel_answers_as_the_reference(world, road, interpreted,
                                                    i):
    node, chunks, ptr, queries = world
    got_ids, got_scores = _hits(_search(node, _body(queries[i])))
    want_ids, want_scores = reference(chunks, ptr, queries[i])
    assert got_ids == want_ids
    assert got_scores == pytest.approx(want_scores, rel=REL)


def test_chunks_that_crowd_the_flat_top_10_leave_room_for_nine_others(
        world, road):
    """The 100 rows nearest to query 0 are all one document's: the top 10
    CHUNKS would be one document ten times; the answer is ten documents,
    that one first, at its best chunk."""
    node, chunks, ptr, queries = world
    d2 = np.sum((chunks.astype(np.float64) - queries[0]) ** 2, axis=1)
    owner = np.repeat(np.arange(DOCS), np.diff(ptr))
    assert set(owner[np.argsort(d2)[:100]].tolist()) == {CROWDED}
    got_ids, got_scores = _hits(_search(node, _body(queries[0])))
    assert got_ids[0] == CROWDED and len(set(got_ids)) == K
    assert got_scores[0] == pytest.approx(1.0 / (1.0 + d2.min()), rel=REL)
    assert got_ids == reference(chunks, ptr, queries[0])[0]


def test_a_document_of_one_chunk_and_one_of_a_hundred(world, road):
    node, chunks, ptr, queries = world
    assert np.diff(ptr)[SINGLE] == 1 and np.diff(ptr)[CROWDED] == 100
    assert _hits(_search(node, _body(queries[1])))[0][0] == SINGLE
    assert _hits(_search(node, _body(queries[0])))[0][0] == CROWDED


def test_hits_and_count_count_documents_and_sources_hold_no_vector(
        world, road):
    node, _chunks, _ptr, queries = world
    assert node.count(INDEX)["count"] == DOCS
    resp = _search(node, _body(queries[2]))
    assert resp["hits"]["total"] == {"value": K, "relation": "eq"}
    assert all(h["_source"] == {} for h in resp["hits"]["hits"])
    # a page past the first: the next documents, never a chunk twice
    page = _search(node, {**_body(queries[2], k=20, size=10), "from": 10})
    ids = [int(h["_id"]) for h in page["hits"]["hits"]]
    chunks, ptr = world[1], world[2]
    assert ids == reference(chunks, ptr, queries[2], k=20)[0][10:]


def test_k_over_the_documents_there_are(tmp_path, road):
    chunks, ptr, queries = _data(seed=5, docs=3)
    node = TpuNode(tmp_path / "node")
    try:
        node.create_index(INDEX, MAPPING)
        _bulk(node, chunks, ptr, 0, 3)
        resp = _search(node, _body(queries[2]))
        got_ids, got_scores = _hits(resp)
        want_ids, want_scores = reference(chunks, ptr, queries[2])
        assert got_ids == want_ids and len(got_ids) == 3
        assert got_scores == pytest.approx(want_scores, rel=REL)
        assert resp["hits"]["total"]["value"] == 3
    finally:
        node.close()


def test_deleted_documents_never_answer_and_a_merge_and_a_restart_keep_it(
        tmp_path):
    from opensearch_tpu.search import distributed_serving

    chunks, ptr, queries = _data()
    node = TpuNode(tmp_path / "node")
    roads = (True, False)

    def answers(node):
        out = []
        for mesh in roads:
            distributed_serving.enabled = mesh
            try:
                out.append([_hits(_search(node, _body(q))) for q in queries])
            finally:
                distributed_serving.enabled = True
        return out

    try:
        node.create_index(INDEX, MAPPING)
        _bulk(node, chunks, ptr, 0, FIRST_SEGMENT)
        _bulk(node, chunks, ptr, FIRST_SEGMENT, DOCS)
        first = {int(reference(chunks, ptr, q)[0][0]) for q in queries[:4]}
        dead = first | {CROWDED}
        for doc in dead:
            status, _resp = handlers.delete_doc(
                node, {"index": INDEX, "id": str(doc)}, {}, None)
            assert status == 200
        node.refresh(INDEX)
        assert node.count(INDEX)["count"] == DOCS - len(dead)
        served = answers(node)
        for by_road in served:
            for q, (got_ids, got_scores) in zip(queries, by_road):
                want_ids, want_scores = reference(chunks, ptr, q, dead=dead)
                assert got_ids == want_ids and not dead & set(got_ids)
                assert got_scores == pytest.approx(want_scores, rel=REL)
        # a merge re-packs the live documents' rows into one segment
        handlers.forcemerge(node, {"index": INDEX}, {"max_num_segments": "1"},
                            None)
        assert answers(node) == served
    finally:
        node.close()
    # a restart recovers the index from its data directory
    again = TpuNode(tmp_path / "node")
    try:
        mapping = again.get_mapping(INDEX)[INDEX]["mappings"]["properties"]
        assert mapping[PATH]["type"] == "nested"
        assert again.count(INDEX)["count"] == DOCS - len(dead)
        assert answers(again) == served
    finally:
        again.close()


def test_the_bfloat16_control_is_outside_the_configurations_limits(world):
    """The same brute force one precision down (the query in bfloat16, one
    float32 pass) moves the scores by more than `score_gap` allows."""
    _node, chunks, ptr, queries = world
    norms = np.einsum("nd,nd->n", chunks, chunks, dtype=np.float64)
    gap = 0.0
    for q in queries:
        approx = norms.astype(np.float32) - 2.0 * (chunks @ _round_bf16(q)) \
            + np.float32(q @ q)
        best = np.minimum.reduceat(approx, ptr[:-1])
        order = np.lexsort((np.arange(len(best)), best))[:K]
        control = 1.0 / (1.0 + np.maximum(best[order], 0.0))
        exact = 1.0 / (1.0 + np.array([
            np.min(np.sum((chunks[ptr[d]:ptr[d + 1]].astype(np.float64)
                           - q) ** 2, axis=1)) for d in order]))
        gap = max(gap, float(np.max(np.abs(control - exact) / exact)))
    assert gap > CONFIG["limits"]["score_gap"]["max"]


@pytest.mark.parametrize("body", [
    {"nested": {"path": PATH, "query": {"match_all": {}}}},
    {"nested": {"path": PATH, "query": {"exists": {"field": FIELD}}}},
    {"nested": {"path": PATH, "score_mode": "sum", "query": {"knn": {
        FIELD: {"vector": [1.0] * D, "k": 3}}}}},
    {"nested": {"path": PATH, "query": {"knn": {FIELD: {
        "vector": [1.0] * D, "k": 3,
        "filter": {"match_all": {}}}}}}},
    {"knn": {FIELD: {"vector": [1.0] * D, "k": 3}}},
], ids=["match_all", "exists", "score_mode_sum", "knn_filter", "flat_knn"])
def test_what_a_multi_vector_field_cannot_answer_is_a_400(world, road, body):
    node = world[0]
    with pytest.raises(IllegalArgumentException) as refused:
        handlers.search(node, {"index": INDEX}, {},
                        {"size": 3, "query": body})
    assert refused.value.status == 400


def test_a_nested_path_without_vectors_keeps_the_flattened_semantics(
        tmp_path):
    node = TpuNode(tmp_path / "node")
    try:
        node.create_index("flat", {"mappings": {"properties": {"kids": {
            "type": "nested", "properties": {"tag": {"type": "keyword"}}}}}})
        status, resp = handlers.bulk(node, {"index": "flat"}, {}, [
            {"index": {"_id": "a"}}, {"kids": [{"tag": "x"}, {"tag": "y"}]},
            {"index": {"_id": "b"}}, {"kids": [{"tag": "z"}]}])
        assert resp["errors"] is False
        node.refresh("flat")
        resp = _search(node, {"query": {"nested": {
            "path": "kids", "query": {"term": {"kids.tag": "y"}}}}}, "flat")
        assert [h["_id"] for h in resp["hits"]["hits"]] == ["a"]
    finally:
        node.close()


def _counters(node) -> tuple:
    shown = node.telemetry.metrics.stats()["counters"]
    return tuple(shown[name] for name in COUNTERS)


def test_the_counters_move_by_the_requests_and_the_rows(world, road):
    node, _chunks, ptr, queries = world
    before = _counters(node)
    for q in queries[:3]:
        _search(node, _body(q))
    assert _counters(node) == (before[0] + 3, before[1] + 3 * ptr[-1])
    # a request of another kind moves neither
    after = _counters(node)
    _search(node, {"query": {"match_all": {}}})
    assert _counters(node) == after


def _traced(node, tmp_path, work) -> dict:
    """Run `work()` under a profiler session with the benchmark launcher's
    options; the capture the session left on disk, spans as dicts."""
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
    node.search(INDEX, {"size": 1, "query": {"match_all": {}}})
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


def test_a_capture_holds_one_knn_nested_span_a_request(world, road,
                                                       tmp_path):
    node, _chunks, ptr, queries = world
    sent = queries[:3]
    for q in sent:          # warm: every program compiled
        _search(node, _body(q))
    doc = _traced(node, tmp_path,
                  lambda: [_search(node, _body(q)) for q in sent])
    roots = [s for s in doc["spans"]
             if s["name"] == "search" and s["parent_id"] is None]
    assert len(roots) == len(sent)
    for root in roots:
        tree = [s for s in doc["spans"] if s["trace_id"] == root["trace_id"]]
        (searched,) = [s for s in tree if s["name"] == span_names.KNN_NESTED]
        segments = 1 if road == "mesh" else 2
        assert searched["attributes"] == {
            "children": int(ptr[-1]), "parents": DOCS, "road": road,
            "launches": segments}
        launches = [s for s in tree if s["name"] == span_names.LAUNCH]
        assert launches and all(s["attributes"]["nested"] == 1
                                for s in launches)
    opened, closed = (doc["counters"][k]["knn_nested"]
                      for k in ("open", "close"))
    assert closed["requests"] == opened["requests"] + len(sent)
    assert closed["children"] == opened["children"] + len(sent) * ptr[-1]


def test_the_new_span_is_a_detail_span_kept_beside_all():
    assert span_names.KNN_NESTED == "knn.nested"
    assert span_names.KNN_NESTED not in span_names.ALL
