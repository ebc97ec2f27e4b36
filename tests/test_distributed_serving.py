"""The on-device cross-shard merge in the REAL serving path.

A multi-shard knn _search must execute the shard_map program
(parallel/distributed.build_knn_serving_step: per-shard scoring + top-k on
each device, all_gather + top_k across the data axis) and return results
identical to the host k-way merge (SearchPhaseController.mergeTopDocs:224
semantics: score desc, shard asc, segment asc, doc asc).
"""

from __future__ import annotations

import numpy as np
import pytest

from opensearch_tpu.node import TpuNode
from opensearch_tpu.search import distributed_serving


@pytest.fixture(autouse=True)
def _clear():
    distributed_serving.clear_caches()
    for key in distributed_serving.stats:
        distributed_serving.stats[key] = 0
    distributed_serving.enabled = True
    yield
    distributed_serving.enabled = True


def _mk_node(tmp_path, n_shards=4, n_docs=80, dims=8, similarity="l2",
             seed=0, extra_mappings=None):
    node = TpuNode(tmp_path / "data")
    props = {
        "v": {"type": "knn_vector", "dimension": dims,
              "space_type": similarity},
        "n": {"type": "long"},
    }
    props.update(extra_mappings or {})
    node.create_index("vecs", {
        "settings": {"number_of_shards": n_shards},
        "mappings": {"properties": props},
    })
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_docs):
        ops.append(("index", {"_index": "vecs", "_id": f"d{i}"},
                    {"v": rng.standard_normal(dims).round(3).tolist(),
                     "n": i}))
    node.bulk(ops, refresh=True)
    return node


def _knn_body(vector, k, size=10):
    return {"query": {"knn": {"v": {"vector": vector, "k": k}}},
            "size": size}


@pytest.mark.parametrize("similarity", ["l2", "cosinesimil", "innerproduct"])
def test_distributed_matches_host_merge(tmp_path, similarity):
    node = _mk_node(tmp_path, similarity=similarity)
    rng = np.random.default_rng(42)
    for trial in range(3):
        q = rng.standard_normal(8).round(3).tolist()
        body = _knn_body(q, k=5, size=10)

        before = distributed_serving.stats["distributed_searches"]
        dist = node.search("vecs", body)
        assert distributed_serving.stats["distributed_searches"] == before + 1, \
            "distributed serving path did not run"

        distributed_serving.enabled = False
        host = node.search("vecs", body)
        distributed_serving.enabled = True

        dh, hh = dist["hits"], host["hits"]
        assert dh["total"] == hh["total"]
        assert [h["_id"] for h in dh["hits"]] == [h["_id"] for h in hh["hits"]]
        dscores = [h["_score"] for h in dh["hits"]]
        hscores = [h["_score"] for h in hh["hits"]]
        assert np.allclose(dscores, hscores, rtol=1e-6, atol=0), \
            (dscores, hscores)
        assert dh["max_score"] == pytest.approx(hh["max_score"], rel=1e-6)


def test_distributed_after_refresh_and_delete(tmp_path):
    """The bundle cache must invalidate on refresh; deletes must be honored
    (live mask) in the flattened slabs."""
    node = _mk_node(tmp_path, n_docs=40)
    q = [0.1] * 8
    body = _knn_body(q, k=40, size=40)
    first = node.search("vecs", body)
    ids0 = {h["_id"] for h in first["hits"]["hits"]}
    assert len(ids0) == 40

    victim = next(iter(ids0))
    node.delete_doc("vecs", victim)
    node.refresh("vecs")
    after = node.search("vecs", body)
    ids1 = {h["_id"] for h in after["hits"]["hits"]}
    assert victim not in ids1
    assert len(ids1) == 39


def test_delete_and_recreate_index_does_not_alias_cache(tmp_path):
    """A deleted+recreated index restarts generations at 0 — the bundle
    cache must key on engine identity, not just (name, generations)."""
    node = _mk_node(tmp_path, n_docs=20, seed=1)
    q = [0.3] * 8
    node.search("vecs", _knn_body(q, k=5))     # populate the cache

    node.delete_index("vecs")
    node.create_index("vecs", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": 8, "space_type": "l2"},
        }},
    })
    rng = np.random.default_rng(99)
    node.bulk([
        ("index", {"_index": "vecs", "_id": f"x{i}"},
         {"v": rng.standard_normal(8).round(3).tolist()})
        for i in range(20)
    ], refresh=True)

    resp = node.search("vecs", _knn_body(q, k=5))
    ids = [h["_id"] for h in resp["hits"]["hits"]]
    assert ids and all(i.startswith("x") for i in ids), ids


def test_unrefreshed_delete_matches_host_semantics(tmp_path):
    """Deletes are invisible until refresh on the host path (dev.live is
    published at refresh) — the distributed path must agree."""
    node = _mk_node(tmp_path, n_docs=30)
    q = [0.1] * 8
    body = _knn_body(q, k=30, size=30)
    baseline_ids = {h["_id"] for h in node.search("vecs", body)["hits"]["hits"]}
    victim = next(iter(baseline_ids))
    node.delete_doc("vecs", victim)            # NO refresh

    dist = node.search("vecs", body)
    distributed_serving.enabled = False
    host = node.search("vecs", body)
    distributed_serving.enabled = True
    assert [h["_id"] for h in dist["hits"]["hits"]] == \
           [h["_id"] for h in host["hits"]["hits"]]


def test_fallback_shapes_keep_host_path(tmp_path):
    """Aggs, sort, non-knn — shapes the device merge cannot reproduce must
    use the host path. (Filters and single-shard, formerly on this list,
    now take the device path — see the dedicated tests below.)"""
    node = _mk_node(tmp_path)
    q = [0.5] * 8
    before = distributed_serving.stats["distributed_searches"]

    # aggs -> fallback
    node.search("vecs", {
        **_knn_body(q, 5), "aggs": {"m": {"max": {"field": "n"}}},
    })
    # sort -> fallback
    node.search("vecs", {**_knn_body(q, 5), "sort": [{"n": "asc"}]})
    # non-knn -> fallback
    node.search("vecs", {"query": {"match_all": {}}})
    assert distributed_serving.stats["distributed_searches"] == before


def test_filtered_knn_takes_device_path(tmp_path):
    """A knn query WITH a filter must run the device merge (the filter mask
    folds into the program's valid mask) and match the host path exactly —
    including the pre-filter semantics (filter restricts candidates BEFORE
    top-k, not after)."""
    node = _mk_node(tmp_path)
    q = [0.5] * 8
    body = {"query": {"knn": {"v": {
        "vector": q, "k": 5, "filter": {"range": {"n": {"lt": 30}}},
    }}}, "size": 20}

    before_d = distributed_serving.stats["distributed_searches"]
    before_f = distributed_serving.stats["filtered"]
    dist = node.search("vecs", body)
    assert distributed_serving.stats["distributed_searches"] == before_d + 1
    assert distributed_serving.stats["filtered"] == before_f + 1

    distributed_serving.enabled = False
    host = node.search("vecs", body)
    distributed_serving.enabled = True

    assert [h["_id"] for h in dist["hits"]["hits"]] == \
           [h["_id"] for h in host["hits"]["hits"]]
    assert np.allclose(
        [h["_score"] for h in dist["hits"]["hits"]],
        [h["_score"] for h in host["hits"]["hits"]], rtol=1e-6, atol=0)
    for h in dist["hits"]["hits"]:
        assert h["_source"]["n"] < 30
    # pre-filter: with k=5 over 4 shards, ≤ 20 filtered candidates total
    assert dist["hits"]["total"]["value"] <= 4 * 5


def test_single_shard_knn_takes_device_path(tmp_path):
    """s == 1 runs the same program on a 1-device mesh."""
    node = _mk_node(tmp_path, n_shards=1, n_docs=30)
    q = [0.2] * 8
    body = _knn_body(q, k=7, size=7)
    before_d = distributed_serving.stats["distributed_searches"]
    before_s = distributed_serving.stats["single_shard"]
    dist = node.search("vecs", body)
    assert distributed_serving.stats["distributed_searches"] == before_d + 1
    assert distributed_serving.stats["single_shard"] == before_s + 1

    distributed_serving.enabled = False
    host = node.search("vecs", body)
    distributed_serving.enabled = True
    assert [h["_id"] for h in dist["hits"]["hits"]] == \
           [h["_id"] for h in host["hits"]["hits"]]
    assert np.allclose(
        [h["_score"] for h in dist["hits"]["hits"]],
        [h["_score"] for h in host["hits"]["hits"]], rtol=1e-6, atol=0)


def test_msearch_batches_knn_queries(tmp_path):
    """Consecutive bare-knn msearch bodies against one index execute as ONE
    batched device dispatch (B query vectors in one program launch) and
    each response matches its serial equivalent."""
    node = _mk_node(tmp_path, n_docs=60)
    rng = np.random.default_rng(7)
    qs = [rng.standard_normal(8).round(3).tolist() for _ in range(3)]
    searches = [({"index": "vecs"}, _knn_body(q, k=5, size=5)) for q in qs]

    before_d = distributed_serving.stats["distributed_searches"]
    before_b = distributed_serving.stats["batched_queries"]
    batched = node.msearch(searches)
    assert distributed_serving.stats["distributed_searches"] == before_d + 1, \
        "3 knn bodies must share ONE device dispatch"
    assert distributed_serving.stats["batched_queries"] == before_b + 3

    serial = [node.search("vecs", _knn_body(q, k=5, size=5)) for q in qs]
    for got, want in zip(batched["responses"], serial):
        assert [h["_id"] for h in got["hits"]["hits"]] == \
               [h["_id"] for h in want["hits"]["hits"]]
        assert got["hits"]["total"] == want["hits"]["total"]
        assert np.allclose(
            [h["_score"] for h in got["hits"]["hits"]],
            [h["_score"] for h in want["hits"]["hits"]], rtol=1e-6, atol=0)


def test_msearch_mixed_bodies_still_correct(tmp_path):
    """A batchable run followed by non-batchable bodies: every response
    slot must land in order with correct content."""
    node = _mk_node(tmp_path, n_docs=40)
    q1, q2 = [0.1] * 8, [0.9] * 8
    searches = [
        ({"index": "vecs"}, _knn_body(q1, k=3, size=3)),
        ({"index": "vecs"}, _knn_body(q2, k=3, size=3)),
        ({"index": "vecs"}, {"query": {"match_all": {}}, "size": 1}),
        ({"index": "missing_idx"}, {"query": {"match_all": {}}}),
    ]
    resp = node.msearch(searches)
    assert len(resp["responses"]) == 4
    assert resp["responses"][0]["hits"]["hits"]
    assert resp["responses"][1]["hits"]["hits"]
    assert resp["responses"][2]["hits"]["total"]["value"] == 40
    assert "error" in resp["responses"][3]


def test_totals_and_paging(tmp_path):
    """total = sum over shards of matched (<=k) docs; from/size paging over
    the merged order is identical to the host path."""
    node = _mk_node(tmp_path, n_docs=60)
    q = [0.2] * 8
    body = {**_knn_body(q, k=7, size=5), "from": 3}
    before = distributed_serving.stats["distributed_searches"]
    dist = node.search("vecs", body)
    assert distributed_serving.stats["distributed_searches"] == before + 1
    distributed_serving.enabled = False
    host = node.search("vecs", body)
    distributed_serving.enabled = True
    assert dist["hits"]["total"] == host["hits"]["total"]
    assert [h["_id"] for h in dist["hits"]["hits"]] == \
           [h["_id"] for h in host["hits"]["hits"]]
    # with 4 shards and k=7 the total is capped per shard
    assert dist["hits"]["total"]["value"] <= 4 * 7
