"""The per-shard kNN selection travels as k winners (PR 32): `(docs, scores)`
short arrays from the launch to the hits, the n_pad-wide view only for a
consumer that indexes by document.

Parity: every query shape around a `knn` answers as numpy brute force does,
on an ANN-indexed index (the IVF-PQ branch) and on a plain one with the mesh
program switched off (the per-shard fused branch), each with two segments
and deleted documents. The mechanism: a bare `knn` counts
`knn.collect.sparse` and allocates nothing n_pad wide; the same `knn` under
a compound parent counts `knn.collect.dense`."""

from __future__ import annotations

import tracemalloc

import numpy as np
import pytest

from opensearch_tpu.index.device import to_device
from opensearch_tpu.index.engine import SearcherSnapshot
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder
from opensearch_tpu.node import TpuNode
from opensearch_tpu.rest.handlers import nodes_stats, prometheus_metrics
from opensearch_tpu.search import distributed_serving, executor, query_dsl

DIM = 8
SEG = 60                # docs a segment: under the rescore pool's floor of
                        # 64, so IVF-PQ with every list probed rescores every
                        # document exactly and brute force is its reference
DELETED = (3, 17, 64, 101)
TAGS = ("a", "b", "c")
QUERY = [0.3, -0.2, 0.5, 0.1, -0.4, 0.25, 0.0, 0.6]

VECTOR_FIELD = {
    "ann": {"type": "knn_vector", "dimension": DIM, "space_type": "l2",
            "method": {"name": "ivf_pq", "parameters": {
                "nlist": 4, "m": 4, "ks": 16, "nprobe": 4,
                "min_train": 32}}},
    "plain": {"type": "knn_vector", "dimension": DIM, "space_type": "l2"},
}


def _corpus():
    rng = np.random.default_rng(32)
    vecs = rng.normal(size=(2 * SEG, DIM)).astype(np.float32).round(3)
    return [{"x": [float(v) for v in vecs[i]], "tag": TAGS[i % 3],
             "price": int((i * 37) % 101)} for i in range(2 * SEG)]


CORPUS = _corpus()


@pytest.fixture(scope="module", params=["ann", "plain"])
def served(request, tmp_path_factory):
    """(node, kind): 120 documents in two segments of 60, four deleted."""
    kind = request.param
    node = TpuNode(tmp_path_factory.mktemp(f"sparse-{kind}"))
    node.create_index("v", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {
            "x": VECTOR_FIELD[kind], "tag": {"type": "keyword"},
            "price": {"type": "long"}}}})
    for lo in (0, SEG):
        node.bulk([("index", {"_index": "v", "_id": str(i)}, CORPUS[i])
                   for i in range(lo, lo + SEG)], refresh=True)
    for i in DELETED:
        node.delete_doc("v", str(i))
    node.refresh("v")
    # the mesh program serves a plain index's bare kNN; off, the per-shard
    # fused branch (where the mesh declines) answers every case here
    mesh_was = distributed_serving.enabled
    distributed_serving.enabled = False
    yield node, kind
    distributed_serving.enabled = mesh_was
    node.close()


def _reference(k: int) -> list[tuple[str, float]]:
    """Brute force: the k nearest live documents, (id, score) best first,
    score = 1 / (1 + squared l2) in float32."""
    live = [i for i in range(2 * SEG) if i not in DELETED]
    x = np.asarray([CORPUS[i]["x"] for i in live], np.float32)
    d2 = ((x - np.asarray(QUERY, np.float32)) ** 2).sum(axis=1,
                                                       dtype=np.float32)
    scores = (np.float32(1.0) / (np.float32(1.0) + d2)).astype(np.float32)
    order = sorted(range(len(live)), key=lambda j: (-scores[j], live[j]))
    return [(str(live[j]), float(scores[j])) for j in order[:k]]


def _knn(k: int, **inside) -> dict:
    return {"knn": {"x": {"vector": QUERY, "k": k, **inside}}}


def _case_bare():
    ref = _reference(5)
    return {"query": _knn(5), "size": 5}, ref, {}


def _case_min_score():
    ref = _reference(5)
    cut = (ref[1][1] + ref[2][1]) / 2
    return ({"query": _knn(5), "size": 5, "min_score": cut}, ref[:2], {})


def _case_boost():
    ref = [(i, float(np.float32(s) * np.float32(2.0)))
           for i, s in _reference(5)]
    return {"query": _knn(5, boost=2.0), "size": 5}, ref, {}


def _case_bool_must_with_term_filter():
    # the knn picks its k a shard, the bool's filter cuts them afterwards
    ref = [(i, s) for i, s in _reference(12)
           if CORPUS[int(i)]["tag"] == "a"]
    body = {"query": {"bool": {"must": [_knn(12)],
                               "filter": [{"term": {"tag": "a"}}]}},
            "size": 12}
    return body, ref, {}


def _case_terms_aggregation():
    ref = _reference(9)
    counts: dict[str, int] = {}
    for i, _ in ref:
        tag = CORPUS[int(i)]["tag"]
        counts[tag] = counts.get(tag, 0) + 1
    body = {"query": _knn(9), "size": 9,
            "aggs": {"tags": {"terms": {"field": "tag"}}}}
    return body, ref, {"tags": counts}


def _case_sort_by_a_numeric_field():
    ref = sorted(_reference(7),
                 key=lambda r: (CORPUS[int(r[0])]["price"], int(r[0])))
    # under a sort the reply carries no scores: ids, order, sort values
    body = {"query": _knn(7), "size": 7, "sort": [{"price": "asc"}]}
    return body, [(i, None) for i, _ in ref], {}


def _case_k_larger_than_a_segment():
    ref = _reference(80)
    return {"query": _knn(80), "size": 80}, ref, {}


def _case_k_one():
    return {"query": _knn(1), "size": 1}, _reference(1), {}


CASES = {
    "bare": _case_bare,
    "min_score": _case_min_score,
    "boost": _case_boost,
    "bool_must_with_term_filter": _case_bool_must_with_term_filter,
    "terms_aggregation": _case_terms_aggregation,
    "sort_by_a_numeric_field": _case_sort_by_a_numeric_field,
    "k_larger_than_a_segment": _case_k_larger_than_a_segment,
    "k_one": _case_k_one,
}


@pytest.mark.parametrize("case", list(CASES))
def test_every_shape_around_a_knn_answers_as_brute_force_does(served, case):
    node, kind = served
    body, ref, aggs = CASES[case]()
    path = "ann" if kind == "ann" else "fused"
    before = executor.knn_path_stats[path]
    resp = node.search("v", body)
    # the branch under test answered: IVF-PQ, or the per-shard fused scan
    assert executor.knn_path_stats[path] > before
    hits = resp["hits"]["hits"]
    assert [h["_id"] for h in hits] == [i for i, _ in ref]
    for h, (_, score) in zip(hits, ref):
        if score is None:
            assert h["_score"] is None
            assert h["sort"] == [CORPUS[int(h["_id"])]["price"]]
        else:
            assert np.float32(h["_score"]) == pytest.approx(
                np.float32(score), rel=2e-6)
    assert resp["hits"]["total"]["value"] == len(ref)
    best = max((s for _, s in ref if s is not None), default=None)
    assert resp["hits"]["max_score"] == (
        best if best is None else pytest.approx(best, rel=2e-6))
    for name, counts in aggs.items():
        got = {b["key"]: b["doc_count"]
               for b in resp["aggregations"][name]["buckets"]}
        assert got == counts


def _collect_counts(node) -> tuple[float, float]:
    counters = node.telemetry.metrics.stats()["counters"]
    return counters["knn.collect.dense"], counters["knn.collect.sparse"]


def test_a_bare_knn_counts_sparse_and_a_compound_parent_counts_dense(served):
    node, _ = served
    # both are registered with the node: a 0 is shown as a 0
    dense0, sparse0 = _collect_counts(node)
    node.search("v", {"query": _knn(5), "size": 5})
    assert _collect_counts(node) == (dense0, sparse0 + 1)
    node.search("v", {"query": _knn(5), "size": 5, "min_score": 0.01})
    assert _collect_counts(node) == (dense0, sparse0 + 2)
    # the same knn inside a bool is indexed by document: the dense view
    node.search("v", {"query": {"bool": {"must": [_knn(5)]}}, "size": 5})
    assert _collect_counts(node) == (dense0 + 1, sparse0 + 2)
    # so is one under aggregations, once a request whatever its segments
    node.search("v", {"query": _knn(5), "size": 5,
                      "aggs": {"t": {"terms": {"field": "tag"}}}})
    assert _collect_counts(node) == (dense0 + 2, sparse0 + 2)
    # `_nodes/stats` and Prometheus show both, beside knn.dispatch.*
    _status, stats = nodes_stats(node, {}, {}, None)
    shown = next(iter(stats["nodes"].values()))["telemetry"]["counters"]
    assert shown["knn.collect.dense"] == dense0 + 2
    assert shown["knn.collect.sparse"] == sparse0 + 2
    _status, text = prometheus_metrics(node, {}, {}, None)
    assert "opensearch_tpu_knn_collect_dense" in text
    assert "opensearch_tpu_knn_collect_sparse" in text


def test_the_dense_view_is_one_scatter_of_the_winners_built_on_first_touch():
    ctx = executor.ShardContext(SearcherSnapshot(segments=[], generation=0),
                                MapperService({}))
    docs = np.asarray([7, 2, 11], np.int32)
    scores = np.asarray([0.9, 0.0, -0.5], np.float32)
    result = executor.HostNodeResult(ctx, 16, docs, scores)
    assert result.scoring is True and ctx.knn_dense is False
    assert result.docs is docs and result.doc_scores is scores
    mask = result.host_mask
    assert ctx.knn_dense is True
    assert mask.dtype == bool and mask.shape == (16,)
    # a winner whose score is 0.0 (or below) is still selected
    assert np.nonzero(mask)[0].tolist() == [2, 7, 11]
    dense = result.host_scores
    assert dense.dtype == np.float32 and dense[7] == np.float32(0.9)
    assert dense[11] == np.float32(-0.5) and dense[2] == 0.0
    assert np.count_nonzero(dense) == 2
    # built once, and the device copies are of the same arrays
    assert result.host_mask is mask and result.host_scores is dense
    assert np.array_equal(np.asarray(result.mask), mask)
    assert np.array_equal(np.asarray(result.scores), dense)
    assert result.scores is result.scores


def test_a_bare_knn_over_a_large_segment_allocates_nothing_n_pad_wide():
    """2^18 rows: from the query node to the hits (the launch between them)
    the host allocates less than n_pad BYTES. The dense form's float32
    scores alone were 4 x n_pad, its masks n_pad each."""
    n = 1 << 18
    mapper = MapperService({"properties": {
        "x": {"type": "knn_vector", "dimension": 4, "space_type": "l2"}}})
    builder = SegmentBuilder(mapper, "large")
    vecs = np.random.default_rng(5).normal(size=(n, 4)).astype(np.float32)
    for i, row in enumerate(vecs.tolist()):
        builder.add(mapper.parse_document(str(i), {"x": row}), seq_no=i)
    host = builder.build()
    dev = to_device(host)
    try:
        assert dev.n_pad >= n
        snapshot = SearcherSnapshot(segments=[(host, dev)], generation=0)
        query = [0.1, 0.2, 0.3, 0.4]

        def search(**kw):
            node = query_dsl.parse_query(
                {"knn": {"x": {"vector": query, "k": 10}}})
            return executor.execute_query_phase(snapshot, mapper, node, 10,
                                                **kw)

        search()                    # warm: compiled, imports done
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            tracemalloc.reset_peak()
            result = search()
            sparse_peak = tracemalloc.get_traced_memory()[1] - base
            base = tracemalloc.get_traced_memory()[0]
            tracemalloc.reset_peak()
            with_masks = search(need_masks=True)
            dense_peak = tracemalloc.get_traced_memory()[1] - base
        finally:
            tracemalloc.stop()
        assert sparse_peak < dev.n_pad
        # the yardstick sees an n_pad-wide build when there is one
        assert dense_peak > 4 * dev.n_pad
        d2 = ((vecs - np.asarray(query, np.float32)) ** 2).sum(axis=1)
        best = np.argsort(d2, kind="stable")[:10]
        assert [h.doc for h in result.hits] == best.tolist()
        assert result.total == 10 and result.masks == []
        assert result.max_score == pytest.approx(
            1.0 / (1.0 + float(d2[best[0]])), rel=1e-5)
        assert np.nonzero(with_masks.masks[0])[0].tolist() == sorted(
            best.tolist())
        assert [h.doc for h in with_masks.hits] == best.tolist()
    finally:
        dev.free_allocations(reason="test")
