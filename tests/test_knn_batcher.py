"""kNN dispatch batcher (search/batcher.py): cross-request coalescing.

Acceptance properties of the serving-path micro-batcher:
 - K concurrent searches over the same field produce <= ceil(K/max_batch)
   device dispatches, with results BIT-identical to the unbatched path;
 - steady-state bucketed batches never retrace (profiler oracle);
 - the pending queue sheds with a 429-style rejection instead of growing;
 - a mid-flight reader refresh (generation bump) never merges a query into
   a batch against the wrong snapshot;
 - settings ride /_cluster/settings; stats ride /_nodes/stats and the
   Prometheus exposition; virtual-clock (sim) runs cannot hang on the
   wall-clock wait window.
"""

from __future__ import annotations

import math
import threading

import numpy as np
import pytest

from opensearch_tpu.common.errors import (
    IllegalArgumentException,
    RejectedExecutionException,
)
from opensearch_tpu.node import TpuNode
from opensearch_tpu.search import distributed_serving, executor
from opensearch_tpu.search.batcher import KnnDispatchBatcher

DIM = 4


@pytest.fixture()
def node(tmp_path, monkeypatch):
    # force the shard-level scan paths onto the tiny corpus and keep the
    # distributed bundle out of the way unless a test re-enables it
    monkeypatch.setattr(distributed_serving, "enabled", False)
    n = TpuNode(tmp_path / "node")
    n.create_index("v", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "x": {"type": "knn_vector", "dimension": DIM,
                  "space_type": "l2"},
            "n": {"type": "long"},
        }},
    })
    rng = np.random.default_rng(7)
    n.bulk([
        ("index", {"_index": "v", "_id": str(i)},
         {"x": rng.standard_normal(DIM).round(3).tolist(), "n": i})
        for i in range(96)
    ], refresh=True)
    yield n
    n.knn_batcher.configure(enabled=True, max_batch_size=32, max_wait_ms=2,
                            max_queue=1024)
    n.close()


def _queries(k: int) -> list:
    rng = np.random.default_rng(21)
    return [rng.standard_normal(DIM).round(3).tolist() for _ in range(k)]


def _knn_body(vec, k=5, **extra):
    return {"query": {"knn": {"x": {"vector": vec, "k": k}}},
            "size": k, **extra}


def _concurrent_search(node, bodies):
    out = [None] * len(bodies)
    errs = []
    barrier = threading.Barrier(len(bodies))

    def run(i):
        barrier.wait()
        try:
            out[i] = node.search("v", bodies[i])
        except Exception as e:  # noqa: BLE001 - surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    return out


def _hits(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


# ---------------------------------------------------------------------------
# coalescing: dispatch-count bound + bit-identical results
# ---------------------------------------------------------------------------


def test_concurrent_searches_coalesce_bit_identical(node):
    K, B = 8, 8
    qs = _queries(K)
    node.knn_batcher.configure(enabled=False)
    ref = [node.search("v", _knn_body(q)) for q in qs]

    node.knn_batcher.configure(enabled=True, max_batch_size=B,
                               max_wait_ms=2000)
    node.knn_batcher.reset()
    s0 = executor.knn_path_stats["fused"]
    out = _concurrent_search(node, [_knn_body(q) for q in qs])

    st = node.knn_batcher.snapshot_stats()
    assert st["dispatches"] <= math.ceil(K / B)
    assert st["merged_queries"] == K
    assert executor.knn_path_stats["fused"] > s0
    for got, want in zip(out, ref):
        # BIT-identical: same ids AND float-equal scores vs unbatched
        assert _hits(got) == _hits(want)


def test_dispatch_count_respects_max_batch_size(node):
    K, B = 8, 4
    qs = _queries(K)
    node.knn_batcher.configure(enabled=True, max_batch_size=B,
                               max_wait_ms=2000)
    node.knn_batcher.reset()
    _concurrent_search(node, [_knn_body(q) for q in qs])
    st = node.knn_batcher.snapshot_stats()
    assert st["dispatches"] == math.ceil(K / B)  # size-threshold flushes
    assert st["merged_queries"] == K
    assert st["max_batch"] <= B


def test_distributed_serving_path_coalesces(node, monkeypatch):
    monkeypatch.setattr(distributed_serving, "enabled", True)
    K = 6
    qs = _queries(K)
    node.knn_batcher.configure(enabled=False)
    ref = [node.search("v", _knn_body(q)) for q in qs]

    node.knn_batcher.configure(enabled=True, max_batch_size=K,
                               max_wait_ms=2000)
    node.knn_batcher.reset()
    d0 = distributed_serving.stats["distributed_searches"]
    out = _concurrent_search(node, [_knn_body(q) for q in qs])
    assert distributed_serving.stats["distributed_searches"] - d0 \
        <= math.ceil(K / K)
    for got, want in zip(out, ref):
        assert _hits(got) == _hits(want)


# ---------------------------------------------------------------------------
# profiler oracle: steady-state bucketed batches never retrace
# ---------------------------------------------------------------------------


def test_steady_state_batches_report_not_retraced(node):
    from opensearch_tpu.search import profile

    K, B = 8, 8
    node.knn_batcher.configure(enabled=True, max_batch_size=B,
                               max_wait_ms=2000)
    # warm every power-of-two batch width this run could produce, so the
    # asserted round is steady-state no matter how arrivals split
    snap = node.indices["v"].shards[0].acquire_searcher()
    vf = snap.segments[0][1].vector_fields["x"]
    k_bucket = 8  # k=5 -> next power of two
    from opensearch_tpu.ops import knn as knn_ops, pallas_knn

    sim = knn_ops.canonical_similarity(vf.similarity)
    impl, interpret = pallas_knn.fused_impl("auto", k_bucket)
    valid = vf.present & snap.segments[0][1].live
    for b in (1, 2, 4, 8):
        q = np.zeros((b, DIM), np.float32)
        np.asarray(pallas_knn.knn_fused(
            vf.vectors, vf.norms_sq, valid, q, k=k_bucket, similarity=sim,
            score_precision="fp32", impl=impl, interpret=interpret)[0])
        profile.signature_retraced(
            "knn_fused_pallas", (vf.vectors, q),
            (k_bucket, sim, "fp32", impl, interpret))

    out = _concurrent_search(
        node, [_knn_body(q, profile=True) for q in _queries(K)])
    for resp in out:
        shard = resp["profile"]["shards"][0]
        assert shard["tpu"]["jit_retrace"] is False
        assert shard["tpu"]["device_time_in_nanos"] > 0


# ---------------------------------------------------------------------------
# backpressure: bounded queue sheds with 429 instead of growing
# ---------------------------------------------------------------------------


def test_queue_bound_sheds_with_429():
    batcher = KnnDispatchBatcher(max_batch_size=2, max_wait_ms=10_000,
                                 max_queue=1)

    def launch(payloads):
        return [f"r-{p}" for p in payloads], False

    results = {}
    t = threading.Thread(
        target=lambda: results.update(
            a=batcher.dispatch("key", "a", launch).value))
    t.start()
    # wait until the first dispatch is actually queued
    for _ in range(2_000):
        if batcher.pressure.current == 1:
            break
        import time as _t

        _t.sleep(0.001)
    assert batcher.pressure.current == 1

    with pytest.raises(RejectedExecutionException) as exc:
        batcher.dispatch("key", "shed-me", launch)
    assert exc.value.status == 429  # the REST layer maps this to HTTP 429
    assert batcher.snapshot_stats()["rejections"] == 1

    # capacity restored: the next arrival fills the bucket and flushes it
    batcher.configure(max_queue=2)
    out = batcher.dispatch("key", "b", launch)
    t.join(timeout=10)
    assert not t.is_alive()
    assert results["a"] == "r-a"
    assert out.value == "r-b" and out.merged == 2


# ---------------------------------------------------------------------------
# snapshot safety: a generation bump is a different batch key
# ---------------------------------------------------------------------------


def test_distinct_keys_never_merge():
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=300)
    seen: dict[str, list] = {}
    lock = threading.Lock()

    def launch_for(gen):
        def launch(payloads):
            with lock:
                seen.setdefault(gen, []).append(sorted(payloads))
            return [f"{gen}:{p}" for p in payloads], False
        return launch

    barrier = threading.Barrier(4)
    out = {}

    def run(gen, payload):
        barrier.wait()
        out[(gen, payload)] = batcher.dispatch(
            ("knn", gen), payload, launch_for(gen)).value

    threads = [threading.Thread(target=run, args=args) for args in [
        ("gen1", "a"), ("gen1", "b"), ("gen2", "c"), ("gen2", "d")]]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every query answered by a launch of ITS OWN generation, and no launch
    # ever mixed generations
    assert out == {("gen1", "a"): "gen1:a", ("gen1", "b"): "gen1:b",
                   ("gen2", "c"): "gen2:c", ("gen2", "d"): "gen2:d"}
    for gen, batches in seen.items():
        for batch in batches:
            assert all(p in ("a", "b") if gen == "gen1" else p in ("c", "d")
                       for p in batch)


def test_refresh_mid_stream_serves_fresh_snapshot(node):
    """A refresh between two batched searches bumps the key generation: the
    second search must see the new document (it can never be answered from
    a stale batch formed against the old reader)."""
    node.knn_batcher.configure(enabled=True, max_batch_size=8,
                               max_wait_ms=50)
    node.knn_batcher.reset()
    target = [9.0, 9.0, 9.0, 9.0]
    r1 = node.search("v", _knn_body(target, k=3))
    ids1 = [h["_id"] for h in r1["hits"]["hits"]]
    assert "bullseye" not in ids1

    node.index_doc("v", "bullseye", {"x": target, "n": 999}, refresh=True)
    r2 = node.search("v", _knn_body(target, k=3))
    assert [h["_id"] for h in r2["hits"]["hits"]][0] == "bullseye"
    assert node.knn_batcher.snapshot_stats()["dispatches"] >= 2


# ---------------------------------------------------------------------------
# adaptivity + determinism + surfacing
# ---------------------------------------------------------------------------


def test_adaptive_solo_fast_path_engages_for_sequential_traffic():
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=30)

    def launch(payloads):
        return list(payloads), False

    for i in range(8):
        assert batcher.dispatch("k", i, launch).value == i
    st = batcher.snapshot_stats()
    assert st["dispatches"] == 8          # no concurrency: nothing merges
    assert st["solo_fast_path"] >= 1      # EWMA learned to stop waiting
    assert st["coalesced_batches"] == 0


def test_virtual_clock_dispatch_does_not_hang():
    from opensearch_tpu.common import timeutil
    from opensearch_tpu.testing.sim import DeterministicTaskQueue

    queue = DeterministicTaskQueue(seed=3)
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=50)

    def launch(payloads):
        return [p * 2 for p in payloads], False

    with timeutil.clock_scope(queue.clock()):
        # virtual time never advances by itself; the frozen-clock guard
        # must flush instead of waiting for a deadline that cannot come
        out = batcher.dispatch("k", 21, launch)
    assert out.value == 42
    assert batcher.snapshot_stats()["dispatches"] == 1


def test_settings_ride_cluster_settings_api(node):
    node.put_cluster_settings({"persistent": {"search": {"knn": {"batch": {
        "max_wait_ms": "7ms", "max_batch_size": 16, "max_queue": 64,
    }}}}})
    assert node.knn_batcher.max_wait_ms == 7
    assert node.knn_batcher.max_batch_size == 16
    assert node.knn_batcher.pressure.limit == 64

    with pytest.raises(IllegalArgumentException):
        node.put_cluster_settings({"persistent": {"search": {"knn": {
            "batch": {"max_batch_size": 0}}}}})
    with pytest.raises(IllegalArgumentException):
        node.put_cluster_settings({"persistent": {"search": {"knn": {
            "batch": {"max_wait_ms": "soon"}}}}})


def test_second_node_boot_does_not_clobber_live_batcher_config(node,
                                                               tmp_path):
    """The batcher is process-wide: constructing another node with no
    persisted batch settings must leave live configuration alone (only an
    explicit settings update may change it)."""
    node.put_cluster_settings({"persistent": {"search": {"knn": {"batch": {
        "enabled": False, "max_batch_size": 16}}}}})
    assert node.knn_batcher.enabled is False
    other = TpuNode(tmp_path / "other")
    try:
        # neither booting a sibling node nor its UNRELATED settings update
        # may reset the shared batcher
        assert node.knn_batcher.enabled is False
        assert node.knn_batcher.max_batch_size == 16
        other.put_cluster_settings({"persistent": {
            "search": {"max_buckets": 1000}}})
        assert node.knn_batcher.enabled is False
        assert node.knn_batcher.max_batch_size == 16
    finally:
        other.close()
        node.put_cluster_settings({"persistent": {"search": {"knn": {
            "batch": {"enabled": None, "max_batch_size": None}}}}})
    # the null deletion above is an explicit batch-key update: defaults back
    assert node.knn_batcher.enabled is True


def test_stats_surface_nodes_stats_and_prometheus(node):
    from opensearch_tpu.rest.handlers import nodes_stats, prometheus_metrics

    node.knn_batcher.configure(enabled=True, max_batch_size=4,
                               max_wait_ms=2000)
    node.knn_batcher.reset()
    _concurrent_search(node, [_knn_body(q) for q in _queries(4)])

    _status, resp = nodes_stats(node, {}, {}, None)
    kb = resp["nodes"]["node-0"]["knn_batch"]
    assert kb["dispatches"] >= 1
    assert kb["merged_queries"] == 4
    assert kb["mean_merged_batch"] > 1
    assert kb["queue"]["limit"] > 0

    _status, text = prometheus_metrics(node, {}, {}, None)
    assert "# TYPE opensearch_tpu_knn_batch_size histogram" in text
    assert 'opensearch_tpu_knn_batch_size_bucket{le="+Inf"}' in text
    assert "opensearch_tpu_knn_batch_queue_wait_ms_count" in text


def test_kill_switch_disables_coalescing(node):
    node.put_cluster_settings({"persistent": {"search": {"knn": {"batch": {
        "enabled": False}}}}})
    node.knn_batcher.reset()
    _concurrent_search(node, [_knn_body(q) for q in _queries(4)])
    st = node.knn_batcher.snapshot_stats()
    # every query launched alone: nothing queued, nothing merged
    assert st["dispatches"] == 4
    assert st["coalesced_batches"] == 0
    assert st["queue"]["total"] == 0
    node.put_cluster_settings({"persistent": {"search": {"knn": {"batch": {
        "enabled": None}}}}})
