"""Tail-latency control plane (ISSUE 11): priority lanes, per-key
batch-wait auto-tuning, wlm search admission, residency-aware replica
routing, and their stats surfaces.

Process-wide knobs (lanes/routing configs, the default batcher) are
restored in finally blocks — these tests must not leak policy into the
rest of the suite.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from opensearch_tpu.cluster import residency
from opensearch_tpu.common.errors import RejectedExecutionException
from opensearch_tpu.search import lanes
from opensearch_tpu.search.batcher import (
    KnnDispatchBatcher,
    _KeyTuner,
)
from opensearch_tpu.telemetry.tracing import MetricsRegistry

DIMS = 8


def _knn_body(vec, k=5, size=10):
    return {"size": size, "query": {"knn": {"v": {"vector": list(vec),
                                                  "k": k}}}}


# --------------------------------------------------------------------- #
# satellite 1: measured per-entry queue waits, not one per-batch point
# --------------------------------------------------------------------- #


class TestQueueWaitRecording:
    def test_recorded_waits_are_per_entry_and_vary(self):
        """Regression (ISSUE 11 satellite): `knn.batch.queue_wait_ms` used
        to record ONE observation per launch; the auto-tuner needs the
        real distribution — one MEASURED wait per entry, varying with
        each entry's actual time in the queue."""
        metrics = MetricsRegistry()
        batcher = KnnDispatchBatcher(
            max_batch_size=8, max_wait_ms=150, auto_tune=False,
            metrics=metrics)
        results = []
        barrier = threading.Barrier(4)

        def launch(rows):
            return [r for r in rows], False

        def client(i):
            barrier.wait()
            time.sleep(0.03 * i)  # staggered arrivals -> distinct waits
            out = batcher.dispatch("k", i, launch)
            results.append(out)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert any(o.merged > 1 for o in results), \
            "arrivals inside the window must coalesce"
        h = metrics.histogram("knn.batch.queue_wait_ms").stats()
        # one observation per ENTRY (4 queries), not one per launch
        assert h["count"] == 4
        waits = sorted(o.wait_ms for o in results)
        # staggered enqueues -> the measured waits differ entry to entry
        assert waits[0] < waits[-1], f"waits did not vary: {waits}"
        # and nothing recorded the configured ceiling verbatim for all
        assert h["max"] <= 150 + 100  # measured, bounded by wall slack

    def test_solo_launch_records_zero_wait(self):
        metrics = MetricsRegistry()
        batcher = KnnDispatchBatcher(
            max_batch_size=8, max_wait_ms=0, metrics=metrics)
        batcher.dispatch("k", 1, lambda rows: ([0] * len(rows), False))
        h = metrics.histogram("knn.batch.queue_wait_ms").stats()
        # measured enqueue -> take in ns resolution: a solo launch takes
        # its own entry at once, microseconds later
        assert h["count"] == 1 and 0 <= h["max"] < 1


# --------------------------------------------------------------------- #
# per-key batch-wait auto-tuning
# --------------------------------------------------------------------- #


class TestKeyTuner:
    def test_solo_stream_converges_to_zero_wait(self):
        t = _KeyTuner()
        assert t.effective_wait(10) > 0, "optimistic start engages the wait"
        for _ in range(8):
            t.note_flush(merged=1, max_wait_ms=0)
        assert t.solo
        assert t.effective_wait(10) == 0

    def test_bursty_key_earns_the_ceiling(self):
        t = _KeyTuner()
        # measured waits AT the ceiling: the window earns the full 10
        for _ in range(8):
            t.note_flush(merged=6, max_wait_ms=10)
        assert not t.solo
        assert t.effective_wait(10) == 10

    def test_measured_waits_cap_the_window(self):
        # merges arrive fast (size-flushes after ~3ms of waiting): the
        # window shrinks toward the MEASURED wait, not the 20ms ceiling
        t = _KeyTuner()
        for _ in range(8):
            t.note_flush(merged=6, max_wait_ms=3)
        assert not t.solo
        assert 1 <= t.effective_wait(20) <= 5

    def test_arrival_gap_floors_the_window(self):
        t = _KeyTuner()
        # merges just above solo -> small fraction of the ceiling...
        for _ in range(10):
            t.note_flush(merged=2, max_wait_ms=1)
        base = t.effective_wait(20)
        assert 0 < base <= 20
        # ...but arrivals 6ms apart floor the window at one gap
        now = 0
        for _ in range(10):
            t.note_arrival(now)
            now += 6
        assert t.effective_wait(20) >= 6

    def test_batcher_tuner_state_surfaces_and_converges(self):
        batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=5,
                                     auto_tune=True)
        for _ in range(10):
            batcher.dispatch("key1", 1,
                             lambda rows: ([0] * len(rows), False),
                             tune_key="fam1")
        stats = batcher.snapshot_stats()
        tune = stats["auto_tune"]
        assert tune["enabled"] and tune["tuned_keys"] == 1
        (row,) = tune["keys"].values()
        assert row["effective_wait_ms"] == 0, \
            "a solo key family must converge to zero added wait"
        assert row["flushes"] >= 10
        # solo traffic takes the fast path once the controller converges
        assert stats["solo_fast_path"] > 0

    def test_tuner_table_is_bounded(self):
        from opensearch_tpu.search import batcher as batcher_mod

        b = KnnDispatchBatcher(max_batch_size=4, max_wait_ms=0,
                               auto_tune=True)
        for i in range(batcher_mod._MAX_TUNERS + 50):
            b.dispatch(("k", i), 1,
                       lambda rows: ([0] * len(rows), False),
                       tune_key=("fam", i))
        assert len(b._tuners) <= batcher_mod._MAX_TUNERS

    def test_auto_tune_setting_round_trip(self, tmp_path):
        from opensearch_tpu.node import TpuNode
        from opensearch_tpu.search import batcher as batcher_mod

        node = TpuNode(tmp_path / "n")
        try:
            assert node.knn_batcher.auto_tune is True
            node.put_cluster_settings({"persistent": {
                "search": {"knn": {"batch": {"auto_tune": False}}}}})
            assert node.knn_batcher.auto_tune is False
        finally:
            node.put_cluster_settings({"persistent": {
                "search": {"knn": {"batch": {"auto_tune": None}}}}})
            assert batcher_mod.default_batcher.auto_tune is True
            node.close()


# --------------------------------------------------------------------- #
# priority lanes
# --------------------------------------------------------------------- #


class TestLanes:
    def test_rest_classification(self):
        assert lanes.classify_rest("/idx/_search", {}) == lanes.INTERACTIVE
        assert lanes.classify_rest("/idx/_count", {}) == lanes.INTERACTIVE
        assert lanes.classify_rest("/idx/_msearch", {}) == lanes.BACKGROUND
        assert lanes.classify_rest("/_bulk", {}) == lanes.BACKGROUND
        assert lanes.classify_rest("/idx/_forcemerge", {}) == \
            lanes.BACKGROUND
        assert lanes.classify_rest("/_search/scroll", {}) == lanes.BACKGROUND
        assert lanes.classify_rest("/idx/_search", {"scroll": "1m"}) == \
            lanes.BACKGROUND
        # explicit override wins
        assert lanes.classify_rest("/idx/_search",
                                   {"lane": "background"}) == \
            lanes.BACKGROUND

    def test_lane_scope_reaches_the_batcher(self):
        """A background-lane dispatch accepts a LONGER deadline than the
        configured ceiling (it earns merges); the lane rides the
        contextvar, no signature threading."""
        batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=30,
                                     auto_tune=False)
        t0 = time.perf_counter()
        with lanes.lane_scope(lanes.BACKGROUND):
            out = batcher.dispatch(
                "k", 1, lambda rows: ([0] * len(rows), False))
        elapsed_ms = 1000 * (time.perf_counter() - t0)
        assert out.merged == 1
        # background deadline = ceiling * factor (120ms), so the lone
        # entry waited past the interactive ceiling before flushing
        assert elapsed_ms >= 30

    def test_tracker_bounds_background_and_counts(self):
        tracker = lanes.LaneTracker()
        assert tracker.try_submit(lanes.BACKGROUND, max_queue=2)
        assert tracker.try_submit(lanes.BACKGROUND, max_queue=2)
        assert not tracker.try_submit(lanes.BACKGROUND, max_queue=2), \
            "past the bound the lane sheds"
        snap = tracker.snapshot()
        assert snap["background"]["shed"] == 1
        assert snap["background"]["depth"] == 2
        tracker.complete(lanes.BACKGROUND)
        assert tracker.depth(lanes.BACKGROUND) == 1

    def test_lane_settings_round_trip(self, tmp_path):
        from opensearch_tpu.node import TpuNode

        node = TpuNode(tmp_path / "n")
        try:
            assert lanes.default_config.enabled is True
            node.put_cluster_settings({"persistent": {
                "search": {"lanes": {"enabled": False,
                                     "background_max_queue": 7}}}})
            assert lanes.default_config.enabled is False
            assert lanes.default_config.background_max_queue == 7
        finally:
            node.put_cluster_settings({"persistent": {
                "search": {"lanes": {"enabled": None,
                                     "background_max_queue": None}}}})
            assert lanes.default_config.enabled is True
            node.close()

    def test_msearch_node_rpc_runs_background_lane(self, tmp_path):
        """msearch[node] is background-lane work: the executing node's
        lane tracker counts it there (the sim path is synchronous but the
        lane scope + accounting still apply)."""
        sim = _mk_vec_sim(tmp_path, n_shards=1, replicas=0, n_docs=8)
        try:
            state = sim.leader().applied_state
            r = next(iter(state.shards_for_index("vecs")))
            target = sim.nodes[r.node_id]
            before = target.lane_tracker.snapshot()["background"]["submitted"]
            out = []
            sim.transport.send(
                "n0", r.node_id, "indices:data/read/msearch[node]",
                {"index": "vecs", "shards": [0],
                 "bodies": [_knn_body([0.1] * DIMS)]},
                on_response=out.append, on_failure=out.append)
            for _ in range(300):
                if out:
                    break
                sim.queue.run_one()
            assert isinstance(out[0], dict) and "responses" in out[0]
            after = target.lane_tracker.snapshot()["background"]["submitted"]
            assert after == before + 1
        finally:
            _close(sim)


# --------------------------------------------------------------------- #
# wlm search admission (QueuePressure twin)
# --------------------------------------------------------------------- #


class TestWlmSearchAdmission:
    def test_enforced_group_sheds_past_share(self, tmp_path):
        from opensearch_tpu.wlm import QueryGroupService

        svc = QueryGroupService(tmp_path / "qg.json")
        svc.put({"name": "grp", "resiliency_mode": "enforced",
                 "resource_limits": {"cpu": 0.05}})  # 3 of 64 slots
        releases = [svc.admit_search("grp") for _ in range(3)]
        with pytest.raises(RejectedExecutionException):
            svc.admit_search("grp")
        stats = svc.search_slot_stats()
        (entry,) = stats.values()
        assert entry["rejections"] == 1
        # release is idempotent and frees the slot
        releases[0]()
        releases[0]()
        svc.admit_search("grp")()
        # untagged / soft groups run unconstrained
        svc.admit_search(None)()
        svc.put({"name": "soft", "resiliency_mode": "soft",
                 "resource_limits": {"cpu": 0.01}})
        for _ in range(10):
            svc.admit_search("soft")()

    def test_delete_drops_search_budget(self, tmp_path):
        from opensearch_tpu.wlm import QueryGroupService

        svc = QueryGroupService(tmp_path / "qg.json")
        svc.put({"name": "grp", "resiliency_mode": "enforced",
                 "resource_limits": {"cpu": 0.1}})
        svc.admit_search("grp")()
        assert svc.search_slot_stats()
        svc.delete("grp")
        assert svc.search_slot_stats() == {}

    def test_cluster_search_sheds_429_before_fanout(self, tmp_path):
        sim = _mk_vec_sim(tmp_path, n_shards=1, replicas=0, n_docs=8)
        try:
            coord = sim.nodes["n1"]
            coord.query_groups.put({
                "name": "grp", "resiliency_mode": "enforced",
                "resource_limits": {"cpu": 0.02}})  # 1 slot
            # hold the single slot, then search on the group's behalf
            hold = coord.query_groups.admit_search("grp")
            resp = sim.call(coord.search, "vecs",
                            _knn_body([0.1] * DIMS), query_group="grp")
            assert resp.get("status") == 429
            assert "RejectedExecutionException" in str(resp.get("error"))
            hold()
            resp = sim.call(coord.search, "vecs",
                            _knn_body([0.1] * DIMS), query_group="grp")
            assert resp["_shards"]["failed"] == 0
        finally:
            _close(sim)


# --------------------------------------------------------------------- #
# residency-aware replica routing
# --------------------------------------------------------------------- #


def _mk_vec_sim(tmp_path, n_shards=2, replicas=1, n_docs=24):
    from tests.test_cluster_data import DataSim

    sim = DataSim(3, seed=42, tmp_path=tmp_path)
    sim.run(5_000)
    sim.call(sim.nodes["n0"].create_index, "vecs",
             {"settings": {"index": {"number_of_shards": n_shards,
                                     "number_of_replicas": replicas}},
              "mappings": {"properties": {
                  "v": {"type": "knn_vector", "dimension": DIMS}}}})
    sim.run(5_000)
    rng = np.random.default_rng(3)
    for i in range(n_docs):
        sim.call(sim.nodes["n0"].index_doc, "vecs", f"d{i}",
                 {"v": rng.standard_normal(DIMS).round(3).tolist()})
    sim.run(2_000)
    sim.call(sim.nodes["n0"].refresh, "vecs")
    sim.run(2_000)
    return sim


def _close(sim):
    for n in sim.nodes.values():
        n.close()


class TestResidencyBoard:
    def test_observe_warm_prune(self):
        b = residency.ResidencyBoard()
        b.observe("n1", "idx", "v", True)
        b.observe("n2", "idx", "v", False)
        assert b.warm_nodes("idx", "v") == {"n1"}
        b.prune(live_nodes={"n2"})
        assert b.warm_nodes("idx", "v") == set()
        b.observe("n2", "idx", "v", True)
        b.prune(live_indices={"other"})
        assert b.warm_nodes("idx", "v") == set()

    def test_board_is_bounded(self):
        b = residency.ResidencyBoard(max_entries=8)
        for i in range(50):
            b.observe(f"n{i}", "idx", "v", True)
        assert b.snapshot_stats()["entries"] <= 8

    def test_choose_copies_prefers_warm_else_round_robin(self):
        class R:
            def __init__(self, node_id, primary):
                self.node_id, self.primary = node_id, primary

        a, b_ = R("na", True), R("nb", False)
        board = residency.ResidencyBoard()
        cands = {0: [a, b_], 1: [a, b_]}
        # cold: round-robin rank applies uniformly across shards
        t0, warm = residency.choose_copies(board, "idx", "v", cands, 0)
        assert not warm and {r.node_id for r in t0.values()} == {"na"}
        t1, _ = residency.choose_copies(board, "idx", "v", cands, 1)
        assert {r.node_id for r in t1.values()} == {"nb"}
        # warm copy wins regardless of rotation
        board.observe("nb", "idx", "v", True)
        t2, warm = residency.choose_copies(board, "idx", "v", cands, 2)
        assert warm and {r.node_id for r in t2.values()} == {"nb"}
        stats = board.snapshot_stats()
        assert stats["warm_hits"] == 1 and stats["cold_routes"] == 2

    def test_knn_query_field(self):
        assert residency.knn_query_field(_knn_body([0.0])) == "v"
        assert residency.knn_query_field(
            {"query": {"match": {"f": "x"}}}) is None
        assert residency.knn_query_field(None) is None


class TestClusterResidencyRouting:
    def test_warm_copy_preferred_builds_stay_flat(self, tmp_path):
        """Steady-state kNN on a replicated index: after the first
        (cold, round-robin) fan-out teaches the board, every later search
        lands on the warm copies — mesh `builds` stays FLAT while
        `warm_hits` grows (the cold-rebuild-tax acceptance)."""
        from opensearch_tpu.search import distributed_serving

        distributed_serving.clear_caches()
        sim = _mk_vec_sim(tmp_path, n_shards=2, replicas=1)
        try:
            coord = sim.nodes["n1"]
            body = _knn_body([0.2] * DIMS, k=5)
            resp = sim.call(coord.search, "vecs", body)
            assert resp["_shards"]["failed"] == 0
            builds_after_first = \
                distributed_serving.registry.snapshot_stats()["builds"]
            warm_before = coord.residency_board.snapshot_stats()["warm_hits"]
            for _ in range(6):
                resp = sim.call(coord.search, "vecs", body)
                assert resp["_shards"]["failed"] == 0
            stats = distributed_serving.registry.snapshot_stats()
            assert stats["builds"] == builds_after_first, \
                "steady-state traffic must not rebuild mesh bundles"
            board = coord.residency_board.snapshot_stats()
            assert board["warm_hits"] > warm_before, \
                "the board never learned the warm copies"
            assert board["observations"] > 0
        finally:
            _close(sim)

    def test_cold_only_fallback_still_serves(self, tmp_path):
        """Routing disabled (control plane off): cold prefer-primary
        selection serves exactly as before."""
        sim = _mk_vec_sim(tmp_path, n_shards=2, replicas=1)
        try:
            residency.default_config.configure(enabled=False)
            coord = sim.nodes["n1"]
            resp = sim.call(coord.search, "vecs", _knn_body([0.2] * DIMS))
            assert resp["_shards"]["failed"] == 0
            assert len(resp["hits"]["hits"]) > 0
            board = coord.residency_board.snapshot_stats()
            assert board["warm_hits"] == 0 and board["cold_routes"] == 0
        finally:
            residency.default_config.configure(enabled=True)
            _close(sim)

    def test_warm_copy_loss_degrades_to_any_serving_copy(self, tmp_path):
        """The warm copy vanishes mid-stream: the fan-out degrades to the
        other serving copy with _shards.failed == 0."""
        from opensearch_tpu.search import distributed_serving

        distributed_serving.clear_caches()
        sim = _mk_vec_sim(tmp_path, n_shards=2, replicas=1)
        try:
            coord = sim.nodes["n1"]
            body = _knn_body([0.2] * DIMS, k=24, size=24)
            for _ in range(3):  # warm up + teach the board
                sim.call(coord.search, "vecs", body)
            warm = {
                nid for (nid, idx, f), w in
                coord.residency_board._warm.items() if w
            }
            assert warm, "board must know warm copies by now"
            victim_id = sorted(warm)[0]
            victim = sim.nodes[victim_id]
            dropped = dict(victim.local_shards)
            for key in list(victim.local_shards):
                if key[0] == "vecs":
                    victim.local_shards.pop(key)
            try:
                resp = sim.call(coord.search, "vecs", body)
                assert resp["_shards"]["failed"] == 0, \
                    "lost warm copy must degrade to the other copy"
                assert len(resp["hits"]["hits"]) == 24
            finally:
                victim.local_shards.update(dropped)
        finally:
            _close(sim)


# --------------------------------------------------------------------- #
# stats surfaces
# --------------------------------------------------------------------- #


class TestTailStatsSurfaces:
    def test_single_node_tail_section(self, tmp_path):
        from opensearch_tpu.node import TpuNode
        from opensearch_tpu.rest.handlers import nodes_stats

        node = TpuNode(tmp_path / "n")
        try:
            node.create_index("t", {"mappings": {"properties": {
                "msg": {"type": "text"}}}})
            node.index_doc("t", "1", {"msg": "hello"})
            node.refresh("t")
            node.search("t", {"query": {"match_all": {}}})
            status, resp = nodes_stats(node, {}, {}, None)
            assert status == 200
            (entry,) = resp["nodes"].values()
            tail = entry["tail"]
            assert tail["lanes"]["enabled"] is True
            assert "interactive" in tail["lanes"]
            assert "wlm_search" in tail and "routing" in tail
            # metric filter accepts the new section
            status, resp = nodes_stats(node, {"metric": "tail"}, {}, None)
            (entry,) = resp["nodes"].values()
            assert "tail" in entry and "device" not in entry
            # lane-labeled took series rides the labeled-histogram machinery
            took = node.telemetry.metrics.stats()["histograms"][
                "search.took_ms"]
            lanes_seen = {
                s["labels"].get("lane") for s in took.get("series", [])
                if "lane" in s["labels"]
            }
            assert "interactive" in lanes_seen
        finally:
            node.close()

    def test_cluster_node_tail_section_rides_stats_rpc(self, tmp_path):
        sim = _mk_vec_sim(tmp_path, n_shards=1, replicas=0, n_docs=8)
        try:
            coord = sim.nodes["n1"]
            sim.call(coord.search, "vecs", _knn_body([0.1] * DIMS))
            out = []
            sim.transport.send(
                "n0", "n1", "indices:monitor/stats[node]",
                {"full": True, "sections": ["tail"]},
                on_response=out.append, on_failure=out.append)
            for _ in range(200):
                if out:
                    break
                sim.queue.run_one()
            assert isinstance(out[0], dict)
            tail = out[0]["tail"]
            assert "lanes" in tail and "routing" in tail
            assert tail["routing"]["enabled"] is True
        finally:
            _close(sim)

    def test_batcher_stats_carry_tuner_section(self):
        b = KnnDispatchBatcher(max_batch_size=4, max_wait_ms=2)
        b.dispatch("k", 1, lambda rows: ([0] * len(rows), False),
                   tune_key="fam")
        stats = b.snapshot_stats()
        assert "auto_tune" in stats
        assert stats["auto_tune"]["tuned_keys"] == 1
