"""Batched ANN serving (ISSUE 9): IVF-PQ through the kNN dispatch batcher.

Acceptance properties of the ANN serving path:
 - concurrent ANN queries against one built index coalesce into ONE
   `search_index` launch with ids IDENTICAL to the unbatched path at the
   default (fp32) ADC precision;
 - reduced-precision ADC (bf16/int8) holds a recall@10 parity bound vs
   fp32 — the widened exact-rescore pool is doing its ANNS-AMP job;
 - batch keys carry the INDEX-BUILD GENERATION: a rebuild mid-stream can
   never merge into a batch formed against the previous build;
 - the `search.knn.ann.*` setting pair rides /_cluster/settings with
   validation, and applies live;
 - the ANN queue sheds with HTTP 429 semantics when bounded;
 - cross-k coalescing serves a small-k request from a bigger-k batch of
   the same family (`cross_k_served`), never the other way around;
 - observability: nprobe histogram + ANN/exact dispatch counters in
   Prometheus and `_nodes/stats`, ADC labels in `"profile": true`.
"""

from __future__ import annotations

import json
import math
import threading

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from opensearch_tpu.common.errors import (
    IllegalArgumentException,
    RejectedExecutionException,
)
from opensearch_tpu.node import TpuNode
from opensearch_tpu.ops import ivfpq, pallas_knn
from opensearch_tpu.search import ann as ann_mod
from opensearch_tpu.search import executor
from opensearch_tpu.search.batcher import KnnDispatchBatcher

DIM = 16
N_DOCS = 600


def _clustered(rng, n, d, n_centers=8, spread=5.0):
    centers = rng.standard_normal((n_centers, d)) * spread
    return (
        centers[rng.integers(0, n_centers, n)] + rng.standard_normal((n, d))
    ).astype(np.float32)


@pytest.fixture()
def ann_node(tmp_path):
    n = TpuNode(tmp_path / "node")
    n.create_index("av", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"x": {
            "type": "knn_vector", "dimension": DIM,
            "method": {"name": "ivf_pq", "parameters": {
                "nlist": 8, "m": 4, "nprobe": 8, "min_train": 100,
            }},
        }}},
    })
    rng = np.random.default_rng(7)
    data = _clustered(rng, N_DOCS, DIM)
    n.bulk([
        ("index", {"_index": "av", "_id": str(i)},
         {"x": data[i].round(3).tolist()})
        for i in range(N_DOCS)
    ], refresh=True)
    n._test_data = data
    yield n
    n.knn_batcher.configure(enabled=True, max_batch_size=32, max_wait_ms=2,
                            max_queue=1024)
    ann_mod.default_config.configure(adc_precision="fp32",
                                     rescore_multiplier=4)
    n.close()


def _body(vec, k=5, **extra):
    return {"query": {"knn": {"x": {"vector": vec, "k": k}}},
            "size": k, **extra}


def _hits(resp):
    return [h["_id"] for h in resp["hits"]["hits"]]


def _concurrent(node, bodies):
    out = [None] * len(bodies)
    errs = []
    barrier = threading.Barrier(len(bodies))

    def run(i):
        barrier.wait()
        try:
            out[i] = node.search("av", bodies[i])
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


def _ann_published(node, index="av"):
    snap = node.indices[index].shards[0].acquire_searcher()
    return [
        dev.vector_fields["x"].ann
        for _host, dev in snap.segments
        if "x" in dev.vector_fields and dev.vector_fields["x"].ann is not None
    ]


# ---------------------------------------------------------------------------
# coalescing: one launch, ids identical to the unbatched path
# ---------------------------------------------------------------------------


def test_batched_ann_identical_ids_and_single_dispatch(ann_node):
    assert _ann_published(ann_node), "fixture must publish an ANN structure"
    data = ann_node._test_data
    K, B = 8, 8
    ann_node.knn_batcher.configure(enabled=False)
    ref = [ann_node.search("av", _body(data[i].tolist())) for i in range(K)]

    ann_node.knn_batcher.configure(enabled=True, max_batch_size=B,
                                   max_wait_ms=2000)
    ann_node.knn_batcher.reset()
    out = _concurrent(ann_node, [_body(data[i].tolist()) for i in range(K)])

    st = ann_node.knn_batcher.snapshot_stats()
    assert st["dispatches"] <= math.ceil(K / B)
    assert st["merged_queries"] == K
    assert st["ann_dispatches"] >= 1
    assert st["exact_dispatches"] == 0
    for got, want in zip(out, ref):
        assert _hits(got) == _hits(want)
        # self-query: ANN with a healthy nprobe must find the doc itself
        assert _hits(got)[0] == _hits(want)[0]


def test_ann_dispatch_counted_in_path_stats(ann_node):
    before = executor.knn_path_stats["ann"]
    ann_node.search("av", _body(ann_node._test_data[3].tolist()))
    assert executor.knn_path_stats["ann"] > before


# ---------------------------------------------------------------------------
# ANNS-AMP: reduced-precision ADC holds a recall parity bound
# ---------------------------------------------------------------------------


def _recall_at_k(ids, exact_ids, k):
    ids, exact_ids = np.asarray(ids), np.asarray(exact_ids)
    return float(np.mean([
        len(set(ids[i].tolist()) & set(exact_ids[i].tolist())) / k
        for i in range(ids.shape[0])
    ]))


def test_reduced_precision_recall_parity():
    rng = np.random.default_rng(11)
    n, d, k = 8_000, 32, 10
    data = _clustered(rng, n, d, n_centers=32)
    queries = _clustered(rng, 32, d, n_centers=32)
    idx = ivfpq.build(data, nlist=64, m=8, iters=6)
    vecs = jnp.asarray(data)
    norms = jnp.sum(vecs * vecs, -1)
    valid = jnp.ones(n, bool)
    q = jnp.asarray(queries)
    _evals, eids = pallas_knn.knn_fused(vecs, norms, valid, q, k=k, impl="xla")

    recalls = {}
    for precision in ivfpq.ADC_PRECISIONS:
        _vals, ids = ivfpq.search_index(
            idx, vecs, norms, valid, q, k=k, nprobe=16, rerank=128,
            adc_precision=precision,
        )
        recalls[precision] = _recall_at_k(ids, eids, k)
    assert recalls["fp32"] >= 0.85
    # parity bound: reduced-precision candidate ranking + exact rescore
    # stays within a few points of the fp32 reference
    assert recalls["bf16"] >= recalls["fp32"] - 0.05
    assert recalls["int8"] >= recalls["fp32"] - 0.05


def test_wider_rescore_pool_recovers_int8_recall():
    """The ANNS-AMP knob pair: at int8 a WIDER rescore pool must never
    lose recall (monotone in R) — that is what makes the precision knob
    safe to flip live."""
    rng = np.random.default_rng(13)
    n, d, k = 4_000, 32, 10
    data = _clustered(rng, n, d, n_centers=16)
    idx = ivfpq.build(data, nlist=32, m=8, iters=5)
    vecs = jnp.asarray(data)
    norms = jnp.sum(vecs * vecs, -1)
    valid = jnp.ones(n, bool)
    q = jnp.asarray(_clustered(rng, 16, d, n_centers=16))
    _evals, eids = pallas_knn.knn_fused(vecs, norms, valid, q, k=k, impl="xla")
    narrow = _recall_at_k(np.asarray(ivfpq.search_index(
        idx, vecs, norms, valid, q, k=k, nprobe=8, rerank=2 * k,
        adc_precision="int8")[1]), eids, k)
    wide = _recall_at_k(np.asarray(ivfpq.search_index(
        idx, vecs, norms, valid, q, k=k, nprobe=8, rerank=16 * k,
        adc_precision="int8")[1]), eids, k)
    assert wide >= narrow


# ---------------------------------------------------------------------------
# build-generation isolation
# ---------------------------------------------------------------------------


def test_build_generations_are_unique_and_monotone():
    rng = np.random.default_rng(3)
    data = _clustered(rng, 600, DIM, n_centers=4)
    a = ivfpq.build(data, nlist=4, m=4, iters=2)
    b = ivfpq.build(data, nlist=4, m=4, iters=2)
    assert a.build_generation != b.build_generation
    assert b.build_generation > a.build_generation


def test_generation_keys_never_merge_across_builds():
    """Batcher contract: keys differing ONLY in build generation never
    share a launch — a rebuild can never answer from an old batch."""
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=300)
    seen: dict[int, list] = {}
    lock = threading.Lock()

    def launch_for(gen):
        def launch(payloads):
            with lock:
                seen.setdefault(gen, []).append(sorted(payloads))
            return [f"g{gen}:{p}" for p in payloads], False
        return launch

    barrier = threading.Barrier(4)
    out = {}

    def run(gen, payload):
        key = ("ivfpq", 1234, gen, 0, 8, 8, "l2_norm", "fp32", 4)
        barrier.wait()
        out[(gen, payload)] = batcher.dispatch(
            key, payload, launch_for(gen), kind="ann").value

    threads = [threading.Thread(target=run, args=args) for args in [
        (1, "a"), (1, "b"), (2, "c"), (2, "d")]]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert out == {(1, "a"): "g1:a", (1, "b"): "g1:b",
                   (2, "c"): "g2:c", (2, "d"): "g2:d"}
    for gen, batches in seen.items():
        for batch in batches:
            assert all(p in ("a", "b") if gen == 1 else p in ("c", "d")
                       for p in batch)


def test_rebuild_mid_stream_bumps_generation_and_serves_fresh(ann_node):
    gens_before = {a.build_generation for a in _ann_published(ann_node)}
    assert gens_before
    ann_node.knn_batcher.configure(enabled=True, max_batch_size=8,
                                   max_wait_ms=50)
    target = (np.full(DIM, 9.0)).tolist()
    r1 = ann_node.search("av", _body(target, k=3))
    assert "bullseye" not in _hits(r1)

    # rebuild: fresh doc + refresh + force-merge re-trains the structure
    ann_node.index_doc("av", "bullseye", {"x": target}, refresh=True)
    ann_node.force_merge("av", max_num_segments=1)
    gens_after = {a.build_generation for a in _ann_published(ann_node)}
    assert gens_after and gens_after.isdisjoint(gens_before)

    r2 = ann_node.search("av", _body(target, k=3))
    assert _hits(r2)[0] == "bullseye"


# ---------------------------------------------------------------------------
# settings: round-trip, validation, live application
# ---------------------------------------------------------------------------


def test_ann_settings_roundtrip_and_validation(ann_node):
    ann_node.put_cluster_settings({"persistent": {"search": {"knn": {
        "ann": {"adc_precision": "bf16", "rescore_multiplier": 8}}}}})
    assert ann_mod.default_config.adc_precision == "bf16"
    assert ann_mod.default_config.rescore_multiplier == 8

    # applied live: the next search runs under the new precision and the
    # ids still come back sane (self-query wins through the rescore)
    data = ann_node._test_data
    r = ann_node.search("av", _body(data[5].tolist()))
    assert _hits(r)[0] == "5"
    st = ann_node.knn_batcher.snapshot_stats()
    assert st["ann"]["adc_precision"] == "bf16"
    assert st["ann"]["rescore_multiplier"] == 8

    with pytest.raises(IllegalArgumentException):
        ann_node.put_cluster_settings({"persistent": {"search": {"knn": {
            "ann": {"adc_precision": "fp8"}}}}})
    with pytest.raises(IllegalArgumentException):
        ann_node.put_cluster_settings({"persistent": {"search": {"knn": {
            "ann": {"rescore_multiplier": 0}}}}})

    # null deletion restores defaults
    ann_node.put_cluster_settings({"persistent": {"search": {"knn": {
        "ann": {"adc_precision": None, "rescore_multiplier": None}}}}})
    assert ann_mod.default_config.adc_precision == "fp32"
    assert ann_mod.default_config.rescore_multiplier == 4


def test_bucket_nprobe_policy():
    assert ann_mod.bucket_nprobe(1, 64) == 1
    assert ann_mod.bucket_nprobe(5, 64) == 8
    assert ann_mod.bucket_nprobe(8, 64) == 8
    assert ann_mod.bucket_nprobe(9, 64) == 16
    # clamped to nlist: more probes than lists is meaningless
    assert ann_mod.bucket_nprobe(100, 64) == 64
    assert ann_mod.bucket_nprobe(0, 64) == 1


# ---------------------------------------------------------------------------
# backpressure: the ANN queue sheds with 429 semantics
# ---------------------------------------------------------------------------


def test_ann_queue_sheds_with_429():
    batcher = KnnDispatchBatcher(max_batch_size=2, max_wait_ms=10_000,
                                 max_queue=1)

    def launch(payloads):
        return [f"r-{p}" for p in payloads], False

    key = ("ivfpq", 1, 1, 0, 8, 8, "l2_norm", "fp32", 4)
    results = {}
    t = threading.Thread(
        target=lambda: results.update(
            a=batcher.dispatch(key, "a", launch, kind="ann").value))
    t.start()
    for _ in range(2_000):
        if batcher.pressure.current == 1:
            break
        import time as _t

        _t.sleep(0.001)
    assert batcher.pressure.current == 1

    with pytest.raises(RejectedExecutionException) as exc:
        batcher.dispatch(key, "shed-me", launch, kind="ann")
    assert exc.value.status == 429
    assert batcher.snapshot_stats()["rejections"] == 1

    batcher.configure(max_queue=2)
    out = batcher.dispatch(key, "b", launch, kind="ann")
    t.join(timeout=10)
    assert not t.is_alive()
    assert results["a"] == "r-a"
    assert out.value == "r-b" and out.merged == 2
    assert batcher.snapshot_stats()["ann_dispatches"] == 1


# ---------------------------------------------------------------------------
# cross-k coalescing: small k rides a bigger-k batch, never vice versa
# ---------------------------------------------------------------------------


def test_cross_k_joins_forming_bigger_k_batch():
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=5_000)
    launches: list[tuple[int, list]] = []
    lock = threading.Lock()

    def launch_for(k):
        def launch(payloads):
            with lock:
                launches.append((k, sorted(payloads)))
            return [f"k{k}:{p}" for p in payloads], False
        return launch

    k8_key, k4_key = ("ivfpq", 1, 1, 8), ("ivfpq", 1, 1, 4)
    out = {}
    t = threading.Thread(target=lambda: out.update(
        big=batcher.dispatch(k8_key, "big", launch_for(8), kind="ann",
                             rank=8).value))
    t.start()
    # wait until the k=8 batch is actually forming
    for _ in range(5_000):
        if batcher.pressure.current == 1:
            break
        import time as _t

        _t.sleep(0.001)
    assert batcher.pressure.current == 1

    # the k=4 arrival names the k=8 family as an alt key: it must ride
    # that batch (one launch, led by the k=8 closure) instead of opening
    # its own bucket
    small = batcher.dispatch(k4_key, "small", launch_for(4), kind="ann",
                             rank=4, alt_keys=(k8_key,))
    t.join(timeout=10)
    assert not t.is_alive()
    assert small.merged == 2
    assert out["big"] == "k8:big"
    # the LARGEST-rank member's closure launched the batch: the small-k
    # joiner got k=8-shaped rows to truncate
    assert small.value == "k8:small"
    assert launches == [(8, ["big", "small"])]
    assert batcher.snapshot_stats()["cross_k_served"] == 1


def test_cross_k_never_creates_a_bigger_bucket():
    """An alt key with NO batch forming must not open one — the request
    falls back to its own k-bucket."""
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=0)

    def launch(payloads):
        return [f"r-{p}" for p in payloads], False

    out = batcher.dispatch(("k", 4), "solo", launch, rank=4,
                           alt_keys=(("k", 8), ("k", 16)))
    assert out.value == "r-solo"
    st = batcher.snapshot_stats()
    assert st["cross_k_served"] == 0


def test_mixed_k_concurrent_traffic_each_k_correct(ann_node):
    """End-to-end: concurrent k=3 and k=8 ANN searches (same index) all
    come back with their OWN k and the same ids the unbatched path gives,
    whether or not the small-k ones rode a bigger launch."""
    data = ann_node._test_data
    ks = [3, 8, 3, 8, 3, 8]
    ann_node.knn_batcher.configure(enabled=False)
    ref = [ann_node.search("av", _body(data[i].tolist(), k=k))
           for i, k in enumerate(ks)]
    ann_node.knn_batcher.configure(enabled=True, max_batch_size=8,
                                   max_wait_ms=2000)
    ann_node.knn_batcher.reset()
    out = _concurrent(
        ann_node, [_body(data[i].tolist(), k=k) for i, k in enumerate(ks)])
    for got, want, k in zip(out, ref, ks):
        assert len(_hits(got)) == k
        assert _hits(got) == _hits(want)


# ---------------------------------------------------------------------------
# observability: Prometheus, _nodes/stats, profile labels
# ---------------------------------------------------------------------------


def test_ann_observability_surfaces(ann_node):
    from opensearch_tpu.rest.handlers import nodes_stats, prometheus_metrics

    ann_node.knn_batcher.configure(enabled=True, max_batch_size=4,
                                   max_wait_ms=2000)
    ann_node.knn_batcher.reset()
    data = ann_node._test_data
    _concurrent(ann_node, [_body(data[i].tolist()) for i in range(4)])
    # one EXACT launch on the same node so the dispatch split is visible
    ann_node.create_index("ev", {"mappings": {"properties": {"x": {
        "type": "knn_vector", "dimension": DIM}}}})
    ann_node.bulk([
        ("index", {"_index": "ev", "_id": str(i)},
         {"x": data[i].round(3).tolist()}) for i in range(32)
    ], refresh=True)
    ann_node.search("ev", _body(data[0].tolist()))

    _status, resp = nodes_stats(ann_node, {}, {}, None)
    kb = resp["nodes"]["node-0"]["knn_batch"]
    assert kb["ann_dispatches"] >= 1
    assert kb["ann"]["adc_precision"] == "fp32"
    assert kb["ann"]["rescore_multiplier"] == 4
    assert kb["ann"]["index_builds"]["builds"] >= 1
    assert kb["ann"]["index_builds"]["last_generation"] >= 1

    _status, text = prometheus_metrics(ann_node, {}, {}, None)
    assert "# TYPE opensearch_tpu_knn_batch_nprobe histogram" in text
    assert 'opensearch_tpu_knn_batch_nprobe_bucket{le="+Inf"}' in text
    assert "opensearch_tpu_knn_dispatch_ann" in text
    assert "opensearch_tpu_knn_dispatch_exact" in text


def test_profile_labels_ann_operator(ann_node):
    r = ann_node.search(
        "av", _body(ann_node._test_data[0].tolist(), profile=True))
    blob = json.dumps(r["profile"])
    assert "ivfpq_search" in blob
    assert "adc_precision" in blob
    assert "rescore_candidates" in blob
    # steady state after the fixture warmup searches in other tests is not
    # guaranteed here; a SECOND identical search must be cache-warm
    r2 = ann_node.search(
        "av", _body(ann_node._test_data[0].tolist(), profile=True))
    assert r2["profile"]["shards"][0]["tpu"]["jit_retrace"] is False


# ---------------------------------------------------------------------------
# mapping-time validation of ANN method config
# ---------------------------------------------------------------------------


class TestMappingValidation:
    def test_unknown_parameter_rejected(self, tmp_path):
        from opensearch_tpu.common.errors import MapperParsingException

        n = TpuNode(tmp_path / "node")
        try:
            with pytest.raises(MapperParsingException):
                n.create_index("bad", {"mappings": {"properties": {"x": {
                    "type": "knn_vector", "dimension": 8,
                    "method": {"name": "ivf_pq",
                               "parameters": {"nlists": 4}},
                }}}})
        finally:
            n.close()

    def test_m_must_divide_dims(self, tmp_path):
        from opensearch_tpu.common.errors import MapperParsingException

        n = TpuNode(tmp_path / "node")
        try:
            with pytest.raises(MapperParsingException):
                n.create_index("bad", {"mappings": {"properties": {"x": {
                    "type": "knn_vector", "dimension": 10,
                    "method": {"name": "ivf_pq", "parameters": {"m": 4}},
                }}}})
        finally:
            n.close()

    def test_non_integer_parameter_rejected(self, tmp_path):
        from opensearch_tpu.common.errors import MapperParsingException

        n = TpuNode(tmp_path / "node")
        try:
            with pytest.raises(MapperParsingException):
                n.create_index("bad", {"mappings": {"properties": {"x": {
                    "type": "knn_vector", "dimension": 8,
                    "method": {"name": "ivf_pq",
                               "parameters": {"nprobe": "many"}},
                }}}})
        finally:
            n.close()

    def test_other_engines_pass_through(self, tmp_path):
        n = TpuNode(tmp_path / "node")
        try:
            resp = n.create_index("ok", {"mappings": {"properties": {"x": {
                "type": "knn_vector", "dimension": 8,
                "method": {"name": "hnsw",
                           "parameters": {"ef_construction": 128}},
            }}}})
            assert resp["acknowledged"]
        finally:
            n.close()
