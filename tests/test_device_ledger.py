"""Device-memory observability (ISSUE 10): the residency ledger, the mesh
HBM byte budget, span events, the `_nodes/stats` `device` section, the
Prometheus device gauges + labeled histogram series, and `/_otel/flush`.

The acceptance bar: every device-resident structure (exact column, IVF-PQ
slab, mesh bundle) appears in the ledger with bytes equal to the summed
``.nbytes`` of its live arrays, and ``resident == allocated − freed``
holds through publish/merge/evict/close cycles.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from opensearch_tpu.telemetry.device_ledger import (
    DeviceResidencyLedger,
    default_ledger,
    upload_scope,
)


# ---------------------------------------------------------------------------
# ledger unit semantics
# ---------------------------------------------------------------------------


class TestLedgerCore:
    def test_identity_through_register_free_cycles(self):
        led = DeviceResidencyLedger()
        a = led.register("column", 1024, index="i", field="f", generation=1)
        b = led.register("ivfpq_slab", 2048, index="i", field="f")
        assert led.resident_bytes() == 3072
        led.verify_identity()
        a.free()
        a.free()  # idempotent: double-free must not double-subtract
        assert led.resident_bytes() == 2048
        led.verify_identity()
        b.free(reason="evicted")
        assert led.resident_bytes() == 0
        st = led.snapshot_stats()
        assert st["identity_ok"]
        assert st["allocations"] == 2 and st["frees"] == 2
        assert st["allocated_bytes"] == 3072 == st["freed_bytes"]

    def test_transient_counts_both_sides(self):
        led = DeviceResidencyLedger()
        led.record_transient("query_batch", 512)
        st = led.snapshot_stats()
        assert st["resident_bytes"] == 0 and st["identity_ok"]
        assert st["transient_uploads"] == 1
        assert st["allocated_bytes"] == 512 == st["freed_bytes"]

    def test_upload_scope_attribution_nests(self):
        led = DeviceResidencyLedger()
        with upload_scope(index="events", shard=2, generation=7):
            with upload_scope(field="vec"):
                alloc = led.register("column", 64)
        row = alloc.row()
        assert row["index"] == "events" and row["shard"] == 2
        assert row["field"] == "vec" and row["generation"] == 7

    def test_structures_group_by_identity(self):
        led = DeviceResidencyLedger()
        led.register("column", 10, index="i", field="f", generation=1,
                     device="d0")
        led.register("column", 20, index="i", field="f", generation=1,
                     device="d0")
        led.register("column", 5, index="i", field="g", generation=1,
                     device="d0")
        rows = led.structures()
        assert len(rows) == 2
        f_row = next(r for r in rows if r["field"] == "f")
        assert f_row["bytes"] == 30 and f_row["allocations"] == 2
        assert led.device_totals() == {"d0": 35}

    def test_a_sharded_structure_counts_chip_by_chip(self):
        led = DeviceResidencyLedger()
        led.register("column", 10, index="i", field="f", device="d0")
        alloc = led.register("mesh_bundle", 40, index="i", field="f",
                             generation=(1,), device="mesh[2]",
                             by_device={"d0": 20, "d1": 20})
        assert led.device_totals() == {"d0": 30, "d1": 20}
        row = next(r for r in led.structures() if r["kind"] == "mesh_bundle")
        assert row["device"] == "mesh[2]" and row["bytes"] == 40
        assert row["by_device"] == {"d0": 20, "d1": 20}
        assert led.resident_bytes() == 50
        alloc.free()
        assert led.device_totals() == {"d0": 10}
        led.verify_identity()
        with pytest.raises(ValueError, match="by_device sums to 30"):
            led.register("mesh_bundle", 40, by_device={"d0": 30})

    def test_device_bytes_reads_addressable_shards(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from opensearch_tpu.telemetry.device_ledger import device_bytes

        devs = jax.devices()[:2]
        mesh = Mesh(np.asarray(devs), ("data",))
        a = jax.device_put(np.zeros((2, 8), np.float32),
                           NamedSharding(mesh, P("data")))
        b = jax.device_put(np.zeros(4, np.int32), devs[1])
        assert device_bytes(a, b) == {str(devs[0]): 32, str(devs[1]): 48}

    def test_compile_accounting_per_family(self):
        led = DeviceResidencyLedger()
        led.record_compile("knn_fused_pallas", 1000)
        led.record_compile("knn_fused_pallas", 3000)
        led.record_compile("mesh_knn_fused", 500)
        comp = led.compile_stats()
        assert comp["knn_fused_pallas"] == {
            "entries": 2, "compile_wall_ns": 4000}
        assert comp["mesh_knn_fused"]["entries"] == 1


# ---------------------------------------------------------------------------
# engine lifecycle: columns + IVF-PQ slabs register and retire
# ---------------------------------------------------------------------------


def _engine(tmp_path, mapping, label=("idx", 0)):
    from opensearch_tpu.index.engine import Engine
    from opensearch_tpu.index.mapper import MapperService

    ms = MapperService()
    ms.merge({"properties": mapping})
    return Engine(tmp_path, ms, shard_label=label)


class TestEngineResidency:
    def test_columns_bytes_match_live_arrays(self, tmp_path):
        before = default_ledger.resident_bytes()
        e = _engine(tmp_path / "a", {
            "title": {"type": "text"}, "n": {"type": "integer"}})
        for i in range(16):
            e.index(f"d{i}", {"title": f"w{i} common", "n": i})
        e.refresh()
        # ledger rows for this index == the published device arrays' nbytes
        rows = {r["field"]: r for r in default_ledger.structures("idx")}
        (host, dev), = e.acquire_searcher().segments
        tf = dev.text_fields["title"]
        assert rows["title"]["bytes"] == sum(
            int(a.nbytes) for a in
            (tf.postings_docs, tf.postings_tfs, tf.postings_dl, tf.doc_len))
        nf = dev.numeric_fields["n"]
        assert rows["n"]["bytes"] == sum(
            int(a.nbytes) for a in (nf.hi, nf.lo, nf.present))
        assert rows["_live"]["bytes"] == int(dev.live.nbytes)
        default_ledger.verify_identity()
        e.close()
        # everything this engine published is freed on close
        assert default_ledger.structures("idx") == []
        assert default_ledger.resident_bytes() == before
        default_ledger.verify_identity()

    def test_merge_retires_source_segments(self, tmp_path):
        e = _engine(tmp_path / "b", {"n": {"type": "integer"}},
                    label=("midx", 0))
        for i in range(8):
            e.index(f"a{i}", {"n": i})
        e.refresh()
        for i in range(8):
            e.index(f"b{i}", {"n": i})
        e.refresh()
        assert len(e._segments) == 2
        e.force_merge(1)
        assert len(e._segments) == 1
        # exactly one generation of rows remains; identity holds
        rows = default_ledger.structures("midx")
        assert {r["field"] for r in rows} == {"n", "_live"}
        default_ledger.verify_identity()
        e.close()
        assert default_ledger.structures("midx") == []

    def test_delete_republish_swaps_live_allocation(self, tmp_path):
        e = _engine(tmp_path / "c", {"n": {"type": "integer"}},
                    label=("didx", 0))
        for i in range(8):
            e.index(f"d{i}", {"n": i})
        e.refresh()
        live_before = [r for r in default_ledger.structures("didx")
                       if r["field"] == "_live"]
        e.delete("d3")
        e.refresh()  # republished deletes bitmap swaps the _live alloc
        live_after = [r for r in default_ledger.structures("didx")
                      if r["field"] == "_live"]
        assert len(live_before) == 1 == len(live_after)
        default_ledger.verify_identity()
        e.close()

    def test_ivfpq_slab_registers_and_frees(self, tmp_path):
        rng = np.random.default_rng(7)
        docs = rng.normal(size=(600, 16)).astype(np.float32)
        e = _engine(tmp_path / "d", {
            "v": {"type": "knn_vector", "dimension": 16,
                  "method": {"name": "ivf_pq",
                             "parameters": {"nlist": 8, "m": 4,
                                            "min_train": 512}}},
        }, label=("annidx", 0))
        for i, row in enumerate(docs):
            e.index(f"d{i}", {"v": [float(x) for x in row]})
        e.refresh()
        rows = default_ledger.structures("annidx")
        slab = [r for r in rows if r["kind"] == "ivfpq_slab"]
        assert len(slab) == 1
        (host, dev), = e.acquire_searcher().segments
        ann = dev.vector_fields["v"].ann
        assert ann is not None
        assert slab[0]["bytes"] == ann.nbytes
        default_ledger.verify_identity()
        e.close()
        assert default_ledger.structures("annidx") == []


# ---------------------------------------------------------------------------
# mesh registry: byte budget, LRU-by-bytes, ledger frees, span events
# ---------------------------------------------------------------------------


class _FakeBundle:
    def __init__(self, nbytes):
        self.nbytes = nbytes
        self.allocation = default_ledger.register(
            "mesh_bundle", nbytes, index="fake", field="v",
            generation=(1,), device="mesh[1]")


class TestMeshByteBudget:
    def _registry(self, budget):
        from opensearch_tpu.cluster.shard_mesh import ShardMeshRegistry

        return ShardMeshRegistry(hbm_budget_bytes=budget)

    def test_lru_by_bytes_eviction(self):
        reg = self._registry(budget=1000)
        b1, b2, b3 = _FakeBundle(400), _FakeBundle(400), _FakeBundle(400)
        reg.put(("i1", "v", 1, (1,), (0,), (1,)), b1)
        reg.put(("i2", "v", 1, (2,), (0,), (1,)), b2)
        assert reg.resident_bytes() == 800
        reg.get(("i1", "v", 1, (1,), (0,), (1,)))           # LRU touch: i2 becomes coldest
        reg.put(("i3", "v", 1, (3,), (0,), (1,)), b3)       # 1200 > 1000: evict i2
        st = reg.snapshot_stats()
        assert st["resident_bytes"] == 800
        assert st["evictions"] == 1 and st["evicted_bytes"] == 400
        assert {r["index"] for r in reg.resident()} == {"i1", "i3"}
        # the evicted bundle's ledger allocation is freed
        assert b2.allocation.freed and b2.allocation.freed_reason == \
            "hbm-budget"
        assert not b1.allocation.freed

    def test_oversized_bundle_still_admitted(self):
        reg = self._registry(budget=100)
        big = _FakeBundle(500)
        reg.put(("huge", "v", 1, (9,), (0,), (1,)), big)
        assert reg.snapshot_stats()["resident_bundles"] == 1
        reg.clear()
        assert big.allocation.freed

    def test_budget_shrink_evicts_live(self):
        reg = self._registry(budget=1000)
        b1, b2 = _FakeBundle(400), _FakeBundle(400)
        reg.put(("i1", "v", 1, (1,), (0,), (1,)), b1)
        reg.put(("i2", "v", 1, (2,), (0,), (1,)), b2)
        reg.apply_settings({"search.mesh.hbm_budget_bytes": "500b"})
        assert reg.hbm_budget_bytes == 500
        assert reg.resident_bytes() == 400
        assert b1.allocation.freed  # coldest went first
        reg.clear()

    def test_eviction_emits_span_event(self):
        from opensearch_tpu.telemetry.tracing import Telemetry, activate

        reg = self._registry(budget=500)
        tel = Telemetry(name="evt")
        with activate(tel.tracer), tel.tracer.start_span("req") as span:
            reg.put(("i1", "v", 1, (1,), (0,), (1,)), _FakeBundle(400))
            reg.put(("i2", "v", 1, (2,), (0,), (1,)), _FakeBundle(400))
            events = [e for e in span.events if e["name"] == "mesh.evict"]
            assert events and events[0]["attributes"]["reason"] == \
                "hbm-budget"
            assert events[0]["attributes"]["bytes"] == 400
        reg.clear()

    def test_duplicate_build_race_frees_loser(self):
        reg = self._registry(budget=10_000)
        winner, loser = _FakeBundle(100), _FakeBundle(100)
        assert reg.put(("i", "v", 1, (5,), (0,), (1,)), winner) is winner
        assert reg.put(("i", "v", 1, (5,), (0,), (1,)), loser) is winner
        assert loser.allocation.freed
        assert not winner.allocation.freed
        reg.clear()

    def test_invalidate_frees_and_counts(self):
        reg = self._registry(budget=10_000)
        b = _FakeBundle(100)
        reg.put(("i", "v", 1, (5,), (0,), (1,)), b)
        assert reg.invalidate_index("i") == 1
        st = reg.snapshot_stats()
        assert st["invalidations"] == 1 and st["evictions"] == 0
        # bytes reconcile with the counters they document: the invalidated
        # bundle's bytes move with it, not into evicted_bytes
        assert st["evicted_bytes"] == 0 and st["invalidated_bytes"] == 100
        assert b.allocation.freed and b.allocation.freed_reason == \
            "invalidated"


# ---------------------------------------------------------------------------
# span events: bound + OTLP round-trip
# ---------------------------------------------------------------------------


class TestSpanEvents:
    def test_bounded_per_span(self):
        from opensearch_tpu.telemetry.tracing import MAX_SPAN_EVENTS, Span

        s = Span("t", "s", None, "op")
        for i in range(MAX_SPAN_EVENTS + 10):
            s.add_event("e", {"i": i})
        assert len(s.events) == MAX_SPAN_EVENTS
        assert s.dropped_events == 10
        assert s.to_dict()["dropped_events"] == 10

    def test_otlp_round_trip_preserves_events(self):
        from opensearch_tpu.telemetry.export import parse_otlp, spans_to_otlp
        from opensearch_tpu.telemetry.tracing import Span

        s = Span("t1", "s1", None, "op", start_ns=5, end_ns=9)
        s.add_event("knn.batch.flush", {"reason": "deadline", "merged": 3})
        s.add_event("mesh.evict", {"bytes": 4096, "cold": True})
        s.dropped_events = 2
        doc = spans_to_otlp([s], "node-x")
        json.dumps(doc)  # must be wire-serializable
        back, = parse_otlp(doc)
        assert back.events == s.events
        assert back.dropped_events == 2
        assert back.to_dict() == s.to_dict()

    def test_batcher_flush_reason_event(self):
        import threading

        from opensearch_tpu.search.batcher import KnnDispatchBatcher
        from opensearch_tpu.telemetry.tracing import Telemetry, activate

        # a coalesced size-flush emits the event on the LEADER's span; the
        # steady solo fast path stays event-free (export-payload budget)
        b = KnnDispatchBatcher(max_wait_ms=5_000, max_batch_size=2)
        tel = Telemetry(name="bat")
        spans: dict[int, object] = {}
        barrier = threading.Barrier(2)

        def client(i):
            with activate(tel.tracer), tel.tracer.start_span("req") as span:
                spans[i] = span
                barrier.wait(timeout=5)
                out = b.dispatch(("k",), i,
                                 lambda rows: (list(rows), False))
                assert out.value == i

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        flushes = [e for s in spans.values() for e in s.events
                   if e["name"] == "knn.batch.flush"]
        assert len(flushes) == 1
        assert flushes[0]["attributes"]["merged"] == 2
        assert flushes[0]["attributes"]["reason"] in ("size", "deadline",
                                                      "backlog")

    def test_solo_fast_path_emits_no_event(self):
        from opensearch_tpu.search.batcher import KnnDispatchBatcher
        from opensearch_tpu.telemetry.tracing import Telemetry, activate

        b = KnnDispatchBatcher(max_wait_ms=0)
        tel = Telemetry(name="bat2")
        with activate(tel.tracer), tel.tracer.start_span("req") as span:
            out = b.dispatch(("k",), 1, lambda rows: ([0] * len(rows), False))
            assert out.value == 0
            assert not [e for e in span.events
                        if e["name"] == "knn.batch.flush"]

    def test_batcher_retrace_records_compile_family(self):
        from opensearch_tpu.search.batcher import KnnDispatchBatcher

        led_before = default_ledger.compile_stats().get(
            "fam_x", {"entries": 0})["entries"]
        b = KnnDispatchBatcher(max_wait_ms=0)
        b.dispatch(("k",), 1, lambda rows: ([0] * len(rows), True),
                   family="fam_x")
        after = default_ledger.compile_stats()["fam_x"]["entries"]
        assert after == led_before + 1


# ---------------------------------------------------------------------------
# REST surfaces: _nodes/stats device, prometheus gauges + labels, otel flush
# ---------------------------------------------------------------------------


@pytest.fixture()
def node(tmp_path):
    from opensearch_tpu.node import TpuNode

    n = TpuNode(data_path=str(tmp_path / "data"))
    n.create_index("t", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"msg": {"type": "text"},
                                    "n": {"type": "integer"}}},
    })
    n.index_doc("t", "1", {"msg": "hello world", "n": 1})
    n.refresh("t")
    yield n
    n.close()


def _handle(node, method, path, query=None, body=None):
    from opensearch_tpu.rest.handlers import build_router

    router = build_router()
    handler, params = router.resolve(method, path)
    return handler(node, params, query or {}, body)


class TestRestSurfaces:
    def test_nodes_stats_device_section(self, node):
        status, resp = _handle(node, "GET", "/_nodes/stats")
        assert status == 200
        device = resp["nodes"]["node-0"]["device"]
        assert device["identity_ok"]
        assert device["resident_bytes"] == (
            device["allocated_bytes"] - device["freed_bytes"])
        rows = [r for r in device["structures"] if r["index"] == "t"]
        assert {r["field"] for r in rows} >= {"msg", "n", "_live"}
        assert all(r["bytes"] > 0 for r in rows)
        assert "shard_mesh" in device
        assert device["shard_mesh"]["hbm_budget_bytes"] > 0

    def test_nodes_stats_metric_filter_accepts_device(self, node):
        status, resp = _handle(node, "GET", "/_nodes/stats/device")
        assert status == 200
        entry = resp["nodes"]["node-0"]
        assert "device" in entry and "indices" not in entry

    def test_prometheus_device_gauges_and_labels(self, node):
        node.search("t", {"query": {"match": {"msg": "hello"}}})
        status, text = _handle(node, "GET", "/_prometheus/metrics")
        assert status == 200
        assert "# TYPE opensearch_tpu_device_resident_bytes gauge" in text
        gauge_lines = [
            ln for ln in text.splitlines()
            if ln.startswith("opensearch_tpu_device_resident_bytes{device=")
        ]
        assert gauge_lines
        total = sum(float(ln.rsplit(" ", 1)[1]) for ln in gauge_lines)
        assert total == default_ledger.resident_bytes()
        # per-index labeled took series under the constant family name
        assert 'opensearch_tpu_search_took_ms_bucket{index="t",le=' in text

    def test_otel_flush_endpoint(self, node):
        node.put_cluster_settings({"persistent": {
            "telemetry.tracing.exporter": "file",
            "telemetry.tracing.sample_ratio": 1.0,
        }})
        node.search("t", {"query": {"match_all": {}}})
        status, resp = _handle(node, "POST", "/_otel/flush")
        assert status == 200
        entry = resp["nodes"]["node-0"]
        assert entry["flushed"] is True
        exp = entry["exporter"]
        assert exp["pending_spans"] == 0 and exp["queued_spans"] == 0
        assert exp["spans_seen"] == exp["spans_exported"] + \
            exp["spans_dropped"]
        assert entry["device"]["identity_ok"]

    def test_otel_flush_without_exporter(self, node):
        status, resp = _handle(node, "POST", "/_otel/flush")
        assert status == 200
        entry = resp["nodes"]["node-0"]
        assert entry["flushed"] is False and entry["exporter"] is None

    def test_profile_response_carries_device_rows(self, node):
        resp = node.search("t", {"query": {"match": {"msg": "hello"}},
                                 "profile": True})
        rows = resp["profile"]["device"]
        assert rows and all(r["index"] == "t" for r in rows)
        assert {r["field"] for r in rows} >= {"msg", "_live"}

    def test_delete_index_invalidates_mesh_bundle(self, node):
        import numpy as np

        rng = np.random.default_rng(11)
        node.create_index("mv", {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {
                "v": {"type": "knn_vector", "dimension": 8}}},
        })
        for i in range(32):
            node.index_doc("mv", str(i),
                           {"v": rng.normal(size=8).tolist()})
        node.refresh("mv")
        node.search("mv", {"size": 3, "query": {
            "knn": {"v": {"vector": [0.1] * 8, "k": 3}}}})
        bundles = [r for r in default_ledger.structures("mv")
                   if r["kind"] == "mesh_bundle"]
        assert bundles, "mesh path did not build a bundle"
        node.delete_index("mv")
        # the slab leaves HBM with the index, not at later LRU pressure
        assert default_ledger.structures("mv") == []
        default_ledger.verify_identity()

    def test_mesh_budget_setting_round_trip(self, node):
        from opensearch_tpu.cluster.shard_mesh import default_registry

        node.put_cluster_settings({"persistent": {
            "search.mesh.hbm_budget_bytes": "64mb"}})
        assert default_registry.hbm_budget_bytes == 64 * 1024 * 1024
        # invalid value -> 400 at validation time
        from opensearch_tpu.common.errors import IllegalArgumentException

        with pytest.raises(IllegalArgumentException):
            node.put_cluster_settings({"persistent": {
                "search.mesh.hbm_budget_bytes": "-5"}})
        # null deletion restores the default
        node.put_cluster_settings({"persistent": {
            "search.mesh.hbm_budget_bytes": None}})
        assert default_registry.hbm_budget_bytes == 1 << 30


# ---------------------------------------------------------------------------
# cluster paths: per-node device section + otel-flush RPC
# ---------------------------------------------------------------------------


class TestClusterSurfaces:
    def test_node_stats_device_section_and_narrowing(self, tmp_path):
        from tests.test_cluster_data import DataSim
        from tests.test_fault_injection import _obs_index

        sim = DataSim(2, seed=41, tmp_path=tmp_path)
        sim.run(5_000)
        try:
            _obs_index(sim, "obs")
            n0 = sim.nodes["n0"]
            full = n0._on_node_stats("x", {"full": True})
            device = full["device"]
            assert device["identity_ok"]
            assert any(r["index"] == "obs" for r in device["structures"])
            assert device["shard_mesh"]["hbm_budget_bytes"] > 0
            # section narrowing: a metrics-only scrape ships no structure
            # rows, only the lightweight per-device totals
            narrowed = n0._on_node_stats(
                "x", {"full": True, "sections": ["metrics",
                                                 "device_totals"]})
            assert "device" not in narrowed
            assert isinstance(narrowed["device_totals"], dict)
            assert sum(narrowed["device_totals"].values()) == \
                default_ledger.resident_bytes()
        finally:
            for n in sim.nodes.values():
                n.close()

    def test_otel_flush_rpc_shape(self, tmp_path):
        from tests.test_cluster_data import DataSim

        sim = DataSim(2, seed=43, tmp_path=tmp_path)
        sim.run(5_000)
        try:
            n0 = sim.nodes["n0"]
            resp = n0._on_otel_flush("x", {})
            assert resp["name"] == "n0"
            assert resp["flushed"] is False and resp["exporter"] is None
            assert resp["device"]["identity_ok"]
        finally:
            for n in sim.nodes.values():
                n.close()


# ---------------------------------------------------------------------------
# labeled histograms: registry semantics + cardinality bound
# ---------------------------------------------------------------------------


class TestHistogramLabels:
    def test_labeled_series_separate_from_base(self):
        from opensearch_tpu.telemetry.tracing import MetricsRegistry

        m = MetricsRegistry()
        m.histogram("took").record(5)
        m.histogram("took", labels={"index": "a"}).record(10)
        m.histogram("took", labels={"index": "b"}).record(20)
        st = m.stats()["histograms"]["took"]
        assert st["count"] == 1
        series = {tuple(s["labels"].items()): s for s in st["series"]}
        assert series[(("index", "a"),)]["count"] == 1
        assert series[(("index", "b"),)]["sum"] == 20

    def test_cardinality_bound_overflows_to_reserved_series(self):
        from opensearch_tpu.telemetry.tracing import (
            MAX_LABEL_SETS,
            MetricsRegistry,
        )

        m = MetricsRegistry()
        for i in range(MAX_LABEL_SETS + 5):
            m.histogram("took", labels={"index": f"i{i}"}).record(1)
        st = m.stats()["histograms"]["took"]
        # cap + ONE reserved overflow bucket; base stays untouched (record
        # sites feed base separately — overflow must not double-count it)
        assert len(st["series"]) == MAX_LABEL_SETS + 1
        assert st["label_sets_dropped"] == 5
        assert st["count"] == 0
        overflow = [s for s in st["series"]
                    if s["labels"] == {"_overflow": "true"}]
        assert overflow and overflow[0]["count"] == 5
