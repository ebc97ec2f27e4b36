"""can_match shard skipping, rescore, collapse, sliced scroll
(CanMatchPreFilterSearchPhase.java, search/rescore/
RescorePhase.java, search/collapse/CollapseContext.java,
search/slice/SliceBuilder.java)."""

from __future__ import annotations

import numpy as np
import pytest

from opensearch_tpu.node import TpuNode


@pytest.fixture()
def node(tmp_path):
    n = TpuNode(tmp_path / "d")
    n.create_index("items", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "title": {"type": "text"},
            "n": {"type": "long"},
            "grp": {"type": "keyword"},
        }},
    })
    n.bulk([
        ("index", {"_index": "items", "_id": f"i{i}"},
         {"title": f"doc {'alpha' if i % 2 == 0 else 'beta'} {i}",
          "n": i, "grp": f"g{i % 4}"})
        for i in range(40)
    ], refresh=True)
    yield n
    n.close()


# -- can_match ---------------------------------------------------------------


def test_can_match_skips_provably_empty_shards(tmp_path):
    n = TpuNode(tmp_path / "d")
    # route docs so shards hold DISJOINT n-ranges via per-doc routing
    n.create_index("logs", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {"n": {"type": "long"}}},
    })
    # shard assignment is hash-based; index values in narrow bands per id
    n.bulk([
        ("index", {"_index": "logs", "_id": f"d{i}"}, {"n": i})
        for i in range(200)
    ], refresh=True)
    # a range beyond every doc: every shard is provably non-matching.
    # The work is skipped internally, but _shards.skipped reports 0 below
    # the 128-shard pre-filter threshold (the reference only pre-filters
    # — and reports skips — at pre_filter_shard_size scale).
    resp = n.search("logs", {"query": {"range": {"n": {"gte": 10_000}}}})
    assert resp["hits"]["total"]["value"] == 0
    assert resp["_shards"]["skipped"] == 0
    # a matching range skips nothing it should not: results stay correct
    resp = n.search("logs", {"query": {"range": {"n": {"gte": 150}}},
                             "size": 100, "track_total_hits": True})
    assert resp["hits"]["total"]["value"] == 50
    n.close()


def test_can_match_conservative_on_unknowns(node):
    # term query (no range constraint): no skipping, results correct
    resp = node.search("items", {"query": {"match": {"title": "alpha"}}})
    assert resp["_shards"]["skipped"] == 0
    assert resp["hits"]["total"]["value"] == 20


# -- rescore -----------------------------------------------------------------


def test_rescore_reorders_window(node):
    resp = node.search("items", {
        "query": {"match": {"title": "doc"}},
        "rescore": {
            "window_size": 40,
            "query": {
                "rescore_query": {"range": {"n": {"gte": 30}}},
                "query_weight": 0.0,
                "rescore_query_weight": 2.0,
                "score_mode": "total",
            },
        },
        "size": 10,
    })
    # with query_weight 0, only docs matching the rescore query score 2.0;
    # the top hits must all be n >= 30
    for h in resp["hits"]["hits"]:
        assert h["_source"]["n"] >= 30, h
        assert h["_score"] == pytest.approx(2.0)


def test_rescore_score_modes_and_sort_conflict(node):
    resp = node.search("items", {
        "query": {"match_all": {}},
        "rescore": {"window_size": 5, "query": {
            "rescore_query": {"match_all": {}},
            "score_mode": "multiply",
        }},
    })
    assert resp["hits"]["hits"][0]["_score"] == pytest.approx(1.0)
    from opensearch_tpu.common.errors import OpenSearchTpuException

    with pytest.raises(OpenSearchTpuException):
        node.search("items", {
            "query": {"match_all": {}},
            "sort": [{"n": "asc"}],
            "rescore": {"query": {"rescore_query": {"match_all": {}}}},
        })


# -- collapse ----------------------------------------------------------------


def test_collapse_first_per_group(node):
    resp = node.search("items", {
        "query": {"match_all": {}},
        "sort": [{"n": "asc"}],
        "collapse": {"field": "grp"},
        "size": 10,
    })
    hits = resp["hits"]["hits"]
    assert len(hits) == 4                      # 4 distinct groups
    assert [h["_source"]["n"] for h in hits] == [0, 1, 2, 3]
    assert [h["fields"]["grp"][0] for h in hits] == ["g0", "g1", "g2", "g3"]
    # total is NOT collapsed (reference contract)
    assert resp["hits"]["total"]["value"] == 40


# -- sliced scroll -----------------------------------------------------------


def test_sliced_scroll_partitions_exactly(node):
    seen: list[str] = []
    for slice_id in range(3):
        resp = node.search("items", {
            "query": {"match_all": {}},
            "slice": {"id": slice_id, "max": 3},
            "size": 40,
        }, scroll="1m")
        ids = [h["_id"] for h in resp["hits"]["hits"]]
        # drain the scroll
        sid = resp["_scroll_id"]
        while True:
            page = node.scroll(sid, "1m")
            more = [h["_id"] for h in page["hits"]["hits"]]
            if not more:
                break
            ids.extend(more)
            sid = page["_scroll_id"]
        assert len(set(ids)) == len(ids)
        seen.extend(ids)
    # the three slices partition the corpus: disjoint and complete
    assert sorted(seen) == sorted(f"i{i}" for i in range(40))


def test_slice_validation(node):
    from opensearch_tpu.common.errors import OpenSearchTpuException

    with pytest.raises(OpenSearchTpuException):
        node.search("items", {"query": {"match_all": {}},
                              "slice": {"id": 5, "max": 3}})
