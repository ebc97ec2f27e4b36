"""Segment merging + _forcemerge.

Reference surface: InternalEngine.java:152 (OpenSearchConcurrentMergeScheduler,
TieredMergePolicy, CombinedDeletionPolicy), TransportForceMergeAction.
Done-criteria: many refreshes end in a bounded segment count,
deleted docs are reclaimed, search results unchanged.
"""

import pytest

from opensearch_tpu.index.engine import Engine
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.node import TpuNode

MAPPINGS = {"properties": {"tag": {"type": "keyword"}, "n": {"type": "long"}}}


class TestEngineMerge:
    def test_refresh_count_bounded(self, tmp_path):
        """100 refreshes of small batches must not end in 100 segments."""
        e = Engine(tmp_path / "s", MapperService(MAPPINGS))
        for batch in range(100):
            for i in range(10):
                e.index(f"{batch}-{i}", {"tag": f"t{batch % 7}", "n": batch})
            e.refresh()
        assert len(e._segments) <= Engine.MAX_SEGMENTS_BEFORE_MERGE
        assert e.num_docs == 1000  # nothing lost in the fusions
        e.close()

    def test_merge_preserves_doc_metadata(self, tmp_path):
        e = Engine(tmp_path / "s", MapperService(MAPPINGS))
        e.index("a", {"tag": "x", "n": 1}, routing="rk")
        e.refresh()
        e.index("a", {"tag": "x", "n": 2}, routing="rk")  # v2
        e.refresh()
        e.force_merge(max_num_segments=1)
        assert len(e._segments) == 1
        host = e._segments[0][0]
        d = host.local_doc("a")
        assert host.doc_routings[d] == "rk"
        assert int(host.doc_versions[d]) == 2
        assert int(host.doc_seq_nos[d]) == 1
        e.close()

    def test_force_merge_reclaims_tombstones(self, tmp_path):
        e = Engine(tmp_path / "s", MapperService(MAPPINGS))
        for i in range(20):
            e.index(str(i), {"tag": "t", "n": i})
        e.refresh()
        for i in range(10):
            e.delete(str(i))
        e.refresh()
        host_before = e._segments[0][0]
        assert host_before.n_docs == 20  # tombstones still physically there
        e.force_merge(max_num_segments=1)
        host = e._segments[0][0]
        assert host.n_docs == 10 and int(host.live.sum()) == 10
        assert e.num_docs == 10
        e.close()

    def test_only_expunge_deletes(self, tmp_path):
        e = Engine(tmp_path / "s", MapperService(MAPPINGS))
        for i in range(5):
            e.index(f"a{i}", {"tag": "t", "n": i})
        e.refresh()
        for i in range(5):
            e.index(f"b{i}", {"tag": "t", "n": i})
        e.refresh()
        e.delete("a0")
        e.refresh()
        e.force_merge(only_expunge_deletes=True)
        # only the tombstone-carrying segment was rewritten
        assert len(e._segments) == 2
        assert all(int(h.live.sum()) == h.n_docs for h, _ in e._segments)
        assert e.num_docs == 9
        e.close()

    def test_pit_snapshot_survives_merge(self, tmp_path):
        """A pinned snapshot still sees the pre-merge view (ReaderContext
        refcount semantics via immutability)."""
        e = Engine(tmp_path / "s", MapperService(MAPPINGS))
        for i in range(10):
            e.index(str(i), {"tag": "t", "n": i})
        e.refresh()
        pinned = e.acquire_searcher()
        e.delete("0")
        e.refresh()
        e.force_merge(max_num_segments=1)
        assert pinned.max_doc == 10  # old view intact
        assert e.acquire_searcher().num_docs == 9
        e.close()

    def test_merge_persists_and_recovers(self, tmp_path):
        e = Engine(tmp_path / "s", MapperService(MAPPINGS))
        for batch in range(30):
            for i in range(5):
                e.index(f"{batch}-{i}", {"tag": "t", "n": batch})
            e.refresh()
        e.force_merge(max_num_segments=1)
        e.flush()
        seg_files = list((tmp_path / "s" / "segments").glob("_*.json"))
        assert len(seg_files) == 1  # merged-away files cleaned up
        e.close()
        e2 = Engine(tmp_path / "s", MapperService(MAPPINGS))
        assert e2.num_docs == 150
        e2.close()


class TestForceMergeApi:
    def test_rest_shape_and_search_unchanged(self, tmp_path):
        node = TpuNode(tmp_path / "n")
        node.create_index("idx", {"settings": {"number_of_shards": 1},
                                  "mappings": MAPPINGS})
        for batch in range(40):
            node.bulk([("index", {"_index": "idx", "_id": f"{batch}-{i}"},
                        {"tag": f"t{i % 3}", "n": batch}) for i in range(25)])
            node.refresh("idx")
        before = node.search("idx", {"query": {"term": {"tag": "t1"}},
                                     "size": 5, "sort": [{"n": "asc"}, "_id"]})
        resp = node.force_merge("idx", max_num_segments=1)
        assert resp["_shards"]["successful"] == 1
        assert node.indices["idx"].shards[0].engine.segment_stats()["count"] == 1
        after = node.search("idx", {"query": {"term": {"tag": "t1"}},
                                    "size": 5, "sort": [{"n": "asc"}, "_id"]})
        assert [h["_id"] for h in after["hits"]["hits"]] == \
               [h["_id"] for h in before["hits"]["hits"]]
        assert after["hits"]["total"] == before["hits"]["total"]
