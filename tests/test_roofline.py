"""Kernel roofline observability (telemetry/roofline.py): cost-model
arithmetic against hand-computed FLOP/byte counts, fraction/intensity
math against injected peaks, calibration round-trip + determinism under
the injected clock, the recorder's bounds and accounting identity, and
every surface the section rides — `GET /_roofline`, `_nodes/stats`,
Prometheus gauges, `"profile": true` kernel rows, and the cluster
per-node RPC with section narrowing."""

from __future__ import annotations

import numpy as np
import pytest

from opensearch_tpu.telemetry import roofline
from opensearch_tpu.telemetry.roofline import (
    COST_MODELS,
    KNOWN_FAMILIES,
    MAX_FAMILIES,
    OVERFLOW_FAMILY,
    PlatformPeaks,
    RooflineRecorder,
    base_family,
    stub_peaks,
)


@pytest.fixture()
def stubbed_peaks():
    """Deterministic peak table for math assertions; restores whatever
    was active so other tests keep their calibration."""
    prev = roofline.current_peaks()
    peaks = PlatformPeaks("test", 1000.0, 100.0, source="stub",
                          calibrated_at_ms=0)
    roofline.set_peaks(peaks)
    yield peaks
    if prev is not None:
        roofline.set_peaks(prev)


# ---------------------------------------------------------------------------
# cost models: hand-computed FLOP/byte counts
# ---------------------------------------------------------------------------


class TestCostModels:
    def test_exact_knn_is_2bnd(self):
        # the canonical roofline formula: exact kNN = 2·B·n·d matmul
        # FLOPs plus the 4-op score-space map per entry
        flops, nbytes = COST_MODELS["knn_exact_scores"](
            {"b": 1, "n": 1000, "d": 128})
        assert flops == 2 * 1 * 1000 * 128 + 4 * 1 * 1000
        assert nbytes == 4 * (1000 * 128 + 1000 + 128 + 1000)

    def test_exact_knn_small(self):
        flops, nbytes = COST_MODELS["knn_exact_scores"](
            {"b": 2, "n": 8, "d": 4})
        assert flops == 192          # 2·2·8·4 + 4·2·8
        assert nbytes == 256         # 4·(32 + 8 + 8 + 16)

    def test_raw_similarity(self):
        flops, nbytes = COST_MODELS["knn_raw_similarity"](
            {"b": 2, "n": 8, "d": 4})
        assert flops == 160          # 2·2·8·4 + 2·2·8
        assert nbytes == 256

    def test_fused_scan_returns_only_winners(self):
        flops, nbytes = COST_MODELS["knn_fused_pallas"](
            {"b": 2, "n": 8, "d": 4, "k": 3, "r": 3})
        assert flops == 224          # 2·2·8·4 + 6·2·8
        # corpus + norms + valid + queries stream; only [B,r] (f32,i32)
        # rows back
        assert nbytes == 4 * (32 + 2 * 8 + 8) + 8 * 2 * 3

    def test_ivfpq_per_precision(self):
        params = {"b": 2, "nlist": 4, "d": 8, "m": 2, "ks": 16,
                  "nprobe": 2, "l_pad": 8, "rescore": 5}
        f32, by32 = COST_MODELS["ivfpq_search"](
            {**params, "adc_precision": "fp32"})
        # coarse 2·2·4·8 + LUT 2·2·2·16·8 + ADC 2·2·2·8·2 + rescore 2·2·5·8
        assert f32 == 128 + 1024 + 128 + 160
        # coarse+codebooks 640 + codes 64 + fp32 LUT gather 256 + rescore 384
        assert by32 == 640 + 64 + 256 + 384
        bf, bybf = COST_MODELS["ivfpq_search"](
            {**params, "adc_precision": "bf16"})
        assert bf == f32                      # same math, narrower gather
        assert bybf == 640 + 64 + 128 + 384   # LUT entries halve
        i8, byi8 = COST_MODELS["ivfpq_search"](
            {**params, "adc_precision": "int8"})
        assert i8 == f32 + 4 * 2 * 2 * 2 * 16  # affine quantization pass
        assert byi8 == 640 + 64 + 64 + 384     # LUT entries quarter
        # the ANNS-AMP premise the report tests against reality: reduced
        # precision MODELS fewer bytes moved
        assert byi8 < bybf < by32

    def test_mesh_launch(self):
        flops, nbytes = COST_MODELS["mesh_knn_fused"](
            {"b": 2, "s": 2, "n_flat": 8, "d": 4, "k_shard": 3,
             "devices": 2})
        # S fused scans (each: matmul + transform/merge; column + norms +
        # valid + queries in, [B,r] winners out) + the all_gather traffic
        assert flops == 2 * (2 * 2 * 8 * 4 + 6 * 2 * 8)
        assert nbytes == (2 * (4 * (8 * 4 + 2 * 8 + 2 * 4) + 8 * 2 * 3)
                          + 8 * 2 * 2 * 3)

    def test_bm25_postings_scan(self):
        flops, nbytes = COST_MODELS["bm25_term_scores"](
            {"q": 3, "window": 4, "n_pad": 16})
        assert flops == 6 * 3 * 4
        assert nbytes == 16 * 3 * 4 + 8 * 16

    def test_constant_terms(self):
        flops, nbytes = COST_MODELS["constant_term_scores"](
            {"q": 3, "window": 4, "n_pad": 16})
        assert flops == 2 * 3 * 4
        assert nbytes == 8 * 3 * 4 + 8 * 16

    def test_base_family_strips_variant(self):
        assert base_family("ivfpq_search[int8]") == "ivfpq_search"
        assert base_family("mesh_knn_fused[fp32]") == "mesh_knn_fused"
        assert base_family("knn_fused_pallas") == "knn_fused_pallas"

    def test_every_repo_launch_site_family_is_registered(self):
        # the TPU015 contract, asserted dynamically too: every family the
        # serving tier records has a model
        for family in ("knn_exact_scores", "knn_raw_similarity",
                       "knn_fused_pallas", "ivfpq_search",
                       "ivfpq_adc_pallas", "mesh_knn_fused",
                       "bm25_term_scores", "constant_term_scores"):
            assert family in KNOWN_FAMILIES


# ---------------------------------------------------------------------------
# recorder: fraction math, EWMA, bounds, identity
# ---------------------------------------------------------------------------


class TestRecorder:
    def test_fraction_and_intensity_math(self, stubbed_peaks):
        rec = RooflineRecorder()
        # 1 s wall, model flops=400, bytes=8 -> intensity 50 (compute
        # side of the ridge 1000/100=10), ceiling = min(1000, 50·100)
        # = 1000, fraction = 400/1000
        rec.record("knn_exact_scores", 1_000_000_000,
                   flops=400, nbytes=8)
        row = rec.snapshot_stats()["families"]["knn_exact_scores"]
        assert row["intensity"] == 50.0
        assert row["bound"] == "compute"
        assert row["roofline_fraction"] == pytest.approx(0.4)
        assert row["achieved_gflops"] == pytest.approx(400 / 1e9, rel=1e-3)
        assert row["lost_ms"] == pytest.approx(1000 * 0.6, rel=1e-3)

    def test_memory_bound_verdict(self, stubbed_peaks):
        rec = RooflineRecorder()
        # intensity 2 < ridge 10 -> memory-bound; ceiling = 2·100 = 200
        rec.record("knn_exact_scores", 1_000_000_000,
                   flops=100, nbytes=50)
        row = rec.snapshot_stats()["families"]["knn_exact_scores"]
        assert row["bound"] == "memory"
        assert row["roofline_fraction"] == pytest.approx(0.5)

    def test_fraction_clamped_to_unit_interval(self, stubbed_peaks):
        rec = RooflineRecorder()
        # impossible achieved (model overshoot): clamps to 1.0, never >
        rec.record("knn_exact_scores", 1_000, flops=10**9, nbytes=1)
        row = rec.snapshot_stats()["families"]["knn_exact_scores"]
        assert row["roofline_fraction"] == 1.0
        # and a truthfully tiny one stays strictly positive
        rec.record("mesh_knn_fused", 10**12, flops=1, nbytes=1)
        row = rec.snapshot_stats()["families"]["mesh_knn_fused"]
        assert 0.0 < row["roofline_fraction"] <= 1.0

    def test_model_driven_record_uses_params(self, stubbed_peaks):
        rec = RooflineRecorder()
        rec.record("knn_exact_scores", 1_000_000,
                   params={"b": 2, "n": 8, "d": 4})
        fam = rec.snapshot_stats()["families"]["knn_exact_scores"]
        assert fam["flops"] == 192 and fam["bytes"] == 256

    def test_ewma_tracks_recent_launches(self, stubbed_peaks):
        rec = RooflineRecorder()
        rec.record("mesh_knn_fused", 1_000_000_000, flops=100, nbytes=10)
        rec.record("mesh_knn_fused", 1_000_000_000, flops=300, nbytes=10)
        fam = rec.snapshot_stats()["families"]["mesh_knn_fused"]
        # 0.7·100 + 0.3·300 = 160 flops/s
        assert fam["ewma_gflops"] == pytest.approx(160 / 1e9, rel=1e-3)
        assert fam["achieved_gflops"] == pytest.approx(200 / 1e9, rel=1e-3)

    def test_accounting_identity_and_monotone_counters(self, stubbed_peaks):
        rec = RooflineRecorder()
        for i in range(5):
            rec.record("knn_exact_scores", 1000 + i,
                       params={"b": 1 + i, "n": 16, "d": 4})
        rec.record("mesh_knn_fused", 2000, flops=77, nbytes=11)
        snap = rec.snapshot_stats()
        assert snap["identity_ok"]
        total = sum(r["flops"] for r in snap["families"].values())
        assert total == snap["counters"]["accounted_flops"]
        assert snap["counters"]["launches"] == 6

    def test_unmodeled_launch_counted_not_dropped(self, stubbed_peaks):
        rec = RooflineRecorder()
        rec.record("no_such_family", 1000)
        rec.record("no_such_family", 1000, params={"b": 1})
        snap = rec.snapshot_stats()
        assert snap["counters"]["unmodeled_launches"] == 2
        assert snap["families"] == {}
        assert snap["identity_ok"]

    def test_family_map_bounded_with_overflow_row(self, stubbed_peaks):
        rec = RooflineRecorder()
        for i in range(MAX_FAMILIES + 10):
            rec.record(f"knn_exact_scores[v{i}]", 1000,
                       params={"b": 1, "n": 4, "d": 2})
        snap = rec.snapshot_stats()
        assert len(snap["families"]) <= MAX_FAMILIES + 1
        assert OVERFLOW_FAMILY in snap["families"]
        assert snap["families"][OVERFLOW_FAMILY]["launches"] == 10
        assert snap["identity_ok"]

    def test_kernel_row_fields_match_variant_families(self, stubbed_peaks):
        rec = RooflineRecorder()
        rec.record("ivfpq_search[fp32]", 1_000_000, flops=100, nbytes=10)
        rec.record("ivfpq_search[int8]", 1_000_000, flops=200, nbytes=10)
        fields = rec.kernel_row_fields("ivfpq_search")
        # the most recently fed variant answers for the bare kernel name
        assert set(fields) == {"achieved_gflops", "intensity",
                               "roofline_fraction", "bound"}
        assert fields["intensity"] == 20.0
        assert rec.kernel_row_fields("never_recorded") == {}

    def test_report_ranks_by_lost_time(self, stubbed_peaks):
        rec = RooflineRecorder()
        # same fraction shape, very different cumulative wall: the family
        # with more wall under the roofline loses more
        rec.record("mesh_knn_fused", 10_000_000_000, flops=100, nbytes=100)
        rec.record("bm25_term_scores", 1_000_000_000, flops=10, nbytes=10)
        report = rec.report()
        assert [r["family"] for r in report["families"]] == \
            ["mesh_knn_fused", "bm25_term_scores"]
        assert report["top_offender"] == "mesh_knn_fused"
        assert report["identity_ok"]

    def test_report_explains_int8_inversion(self, stubbed_peaks):
        rec = RooflineRecorder()
        params = {"b": 8, "nlist": 16, "d": 32, "m": 8, "ks": 16,
                  "nprobe": 4, "l_pad": 16, "rescore": 32}
        # fp32 fast, int8 SLOW on the same work (the int8 inversion)
        rec.record("ivfpq_search[fp32]", 1_000_000,
                   params={**params, "adc_precision": "fp32"})
        rec.record("ivfpq_search[int8]", 5_000_000,
                   params={**params, "adc_precision": "int8"})
        report = rec.report()
        rows = {r["family"]: r for r in report["families"]}
        int8 = rows["ivfpq_search[int8]"]
        assert int8["achieved_gflops"] < \
            rows["ivfpq_search[fp32]"]["achieved_gflops"]
        assert "Pallas" in int8["note"]
        assert "XLA" in int8["note"]

    def test_reset(self, stubbed_peaks):
        rec = RooflineRecorder()
        rec.record("mesh_knn_fused", 1000, flops=1, nbytes=1)
        rec.reset()
        snap = rec.snapshot_stats()
        assert snap["families"] == {}
        assert snap["counters"]["launches"] == 0


# ---------------------------------------------------------------------------
# calibration: stub determinism, round-trip, injected clock
# ---------------------------------------------------------------------------


class TestCalibration:
    def test_stub_peaks_deterministic_per_seed(self):
        a, b = stub_peaks(seed=3), stub_peaks(seed=3)
        assert (a.flops_per_s, a.bytes_per_s) == \
            (b.flops_per_s, b.bytes_per_s)
        assert a.source == "stub" and a.calibrated_at_ms == 0
        assert stub_peaks(seed=4).flops_per_s != a.flops_per_s

    def test_set_and_current_round_trip(self):
        prev = roofline.current_peaks()
        try:
            peaks = roofline.set_peaks(stub_peaks(seed=9))
            assert roofline.current_peaks() is peaks
        finally:
            if prev is not None:
                roofline.set_peaks(prev)

    def test_calibrate_measures_and_caches(self):
        prev = roofline.current_peaks()
        try:
            peaks = roofline.calibrate(force=True)
            assert peaks.source == "measured"
            assert peaks.flops_per_s > 0 and peaks.bytes_per_s > 0
            assert peaks.ridge_intensity > 0
            # cached per platform: a non-forced call reuses the table
            assert roofline.calibrate(force=False) is peaks
        finally:
            if prev is not None:
                roofline.set_peaks(prev)

    def test_calibrated_at_uses_injected_clock(self):
        from opensearch_tpu.common import timeutil

        class _Fixed(timeutil.Clock):
            def epoch_millis(self):
                return 777_000

            def monotonic_millis(self):
                return 0

        with timeutil.clock_scope(_Fixed()):
            peaks = PlatformPeaks("t", 1.0, 1.0)
        assert peaks.calibrated_at_ms == 777_000


# ---------------------------------------------------------------------------
# profiler annotation merge (the last-write-wins fix)
# ---------------------------------------------------------------------------


class TestAnnotationMerge:
    def test_disagreeing_values_collect_per_key(self):
        from opensearch_tpu.search.profile import OpProfile

        op = OpProfile("knn", "v")
        op.record_kernel("ivfpq_search", 10, 0, False,
                         annotations={"adc_precision": "int8", "nprobe": 4})
        op.record_kernel("ivfpq_search", 10, 0, False,
                         annotations={"adc_precision": "fp32", "nprobe": 4})
        op.record_kernel("ivfpq_search", 10, 0, False,
                         annotations={"adc_precision": "fp32"})
        merged = op.kernel_annotations["ivfpq_search"]
        # a mixed batch reports EVERY precision it ran, once each
        assert merged["adc_precision"] == ["int8", "fp32"]
        assert merged["nprobe"] == 4
        row = op.to_dict()["kernels"][0]
        assert row["adc_precision"] == ["int8", "fp32"]


# ---------------------------------------------------------------------------
# REST surfaces on a warm node
# ---------------------------------------------------------------------------


def _handle(node, method, path, query=None, body=None):
    from opensearch_tpu.rest.handlers import build_router

    router = build_router()
    handler, params = router.resolve(method, path)
    return handler(node, params, query or {}, body)


@pytest.fixture()
def warm_node(tmp_path):
    """A node that has launched every kernel family: filtered-path exact
    scan (mesh disabled), 2-shard mesh launch, IVF-PQ at all three ADC
    precisions, and a profiled BM25 match."""
    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import ann as ann_mod
    from opensearch_tpu.search import distributed_serving

    prev_peaks = roofline.current_peaks()
    roofline.set_peaks(stub_peaks(seed=1))
    roofline.default_recorder.reset()
    rng = np.random.default_rng(7)
    d = 16
    node = TpuNode(data_path=str(tmp_path / "data"))

    def vec_index(name, n_docs, shards=1, method=None):
        mapping = {"type": "knn_vector", "dimension": d}
        if method is not None:
            mapping["method"] = method
        node.create_index(name, {
            "settings": {"number_of_shards": shards},
            "mappings": {"properties": {"v": mapping}},
        })
        node.bulk([
            ("index", {"_index": name, "_id": str(i)},
             {"v": rng.normal(size=d).round(4).tolist()})
            for i in range(n_docs)
        ], refresh=True)

    vec_index("ex", 48)
    vec_index("m2", 48, shards=2)
    vec_index("annv", 600, method={
        "name": "ivf_pq", "parameters": {"nlist": 8, "m": 4, "nprobe": 4}})
    node.create_index("lex", {"mappings": {"properties": {
        "msg": {"type": "text"}}}})
    for i in range(8):
        node.index_doc("lex", str(i), {"msg": f"hello world {i}"})
    node.refresh("lex")

    def knn(index):
        q = rng.normal(size=d).round(4).tolist()
        node.search(index, {"size": 3, "query": {
            "knn": {"v": {"vector": q, "k": 3}}}})

    distributed_serving.enabled = False
    try:
        for _ in range(3):
            knn("ex")                      # knn_fused_pallas[fp32]
    finally:
        distributed_serving.enabled = True
    for _ in range(3):
        knn("m2")                          # mesh_knn_fused[fp32]
    for precision in ("fp32", "bf16", "int8"):
        ann_mod.default_config.configure(adc_precision=precision)
        for _ in range(3):
            knn("annv")                    # ivfpq_search[precision]
    ann_mod.default_config.configure(adc_precision="fp32")
    node.search("lex", {"query": {"match": {"msg": "hello"}},
                        "profile": True})  # bm25_term_scores
    yield node
    node.close()
    if prev_peaks is not None:
        roofline.set_peaks(prev_peaks)


class TestRestSurfaces:
    def test_roofline_report_ranks_families(self, warm_node):
        status, report = _handle(warm_node, "GET", "/_roofline")
        assert status == 200
        rows = report["families"]
        # a warm node ranks >= 4 kernel families by lost time
        assert len(rows) >= 4
        losses = [r["lost_ms"] for r in rows]
        assert losses == sorted(losses, reverse=True)
        assert report["top_offender"] == rows[0]["family"]
        names = {r["family"] for r in rows}
        assert {"knn_fused_pallas[fp32]", "mesh_knn_fused[fp32]",
                "bm25_term_scores",
                "ivfpq_search[fp32]", "ivfpq_search[int8]"} <= names
        for r in rows:
            assert 0.0 < r["roofline_fraction"] <= 1.0, r
            assert r["bound"] in ("memory", "compute")
        int8 = next(r for r in rows
                    if r["family"] == "ivfpq_search[int8]")
        assert int8["achieved_gflops"] > 0
        assert report["identity_ok"]

    def test_nodes_stats_roofline_section(self, warm_node):
        status, resp = _handle(warm_node, "GET", "/_nodes/stats")
        assert status == 200
        section = resp["nodes"]["node-0"]["roofline"]
        assert section["identity_ok"]
        assert section["peaks"]["source"] == "stub"
        assert "mesh_knn_fused[fp32]" in section["families"]

    def test_nodes_stats_metric_filter_accepts_roofline(self, warm_node):
        status, resp = _handle(warm_node, "GET", "/_nodes/stats/roofline")
        assert status == 200
        entry = resp["nodes"]["node-0"]
        assert "roofline" in entry and "indices" not in entry

    def test_prometheus_roofline_gauges(self, warm_node):
        status, text = _handle(warm_node, "GET", "/_prometheus/metrics")
        assert status == 200
        assert "# TYPE opensearch_tpu_roofline_fraction gauge" in text
        frac_lines = [
            ln for ln in text.splitlines()
            if ln.startswith("opensearch_tpu_roofline_fraction{family=")
        ]
        assert len(frac_lines) >= 4
        for ln in frac_lines:
            value = float(ln.rsplit(" ", 1)[1])
            assert 0.0 < value <= 1.0
        assert any('family="mesh_knn_fused[fp32]"' in ln
                   for ln in frac_lines)
        assert "opensearch_tpu_roofline_achieved_flops{family=" in text

    def test_profile_rows_carry_roofline_fields(self, warm_node):
        from opensearch_tpu.search import ann as ann_mod

        ann_mod.default_config.configure(adc_precision="int8")
        try:
            resp = warm_node.search("annv", {
                "size": 3, "profile": True,
                "query": {"knn": {"v": {"vector": [0.1] * 16, "k": 3}}}})
        finally:
            ann_mod.default_config.configure(adc_precision="fp32")

        def kernels(ops):
            out = []
            for op in ops:
                out += op.get("kernels", [])
                out += kernels(op.get("children", []))
            return out

        rows = kernels(
            resp["profile"]["shards"][0]["searches"][0]["query"])
        ivf = next(r for r in rows if r["name"] == "ivfpq_search")
        assert 0.0 < ivf["roofline_fraction"] <= 1.0
        assert ivf["bound"] in ("memory", "compute")
        assert ivf["achieved_gflops"] > 0
        assert ivf["intensity"] > 0
        # the annotations still ride alongside the roofline fields
        assert ivf["adc_precision"] == "int8"

    def test_calibrate_endpoint_round_trip(self, warm_node):
        prev = roofline.current_peaks()
        try:
            status, resp = _handle(warm_node, "POST", "/_roofline/calibrate")
            assert status == 200 and resp["acknowledged"]
            peaks = resp["peaks"]
            assert peaks["source"] == "measured"
            assert peaks["peak_flops_per_s"] > 0
            assert peaks["peak_bytes_per_s"] > 0
        finally:
            if prev is not None:
                roofline.set_peaks(prev)


# ---------------------------------------------------------------------------
# cluster fan-out: per-node section + narrowing
# ---------------------------------------------------------------------------


class TestClusterSurfaces:
    def test_node_stats_roofline_section_and_narrowing(self, tmp_path):
        from tests.test_cluster_data import DataSim

        prev = roofline.current_peaks()
        roofline.set_peaks(stub_peaks(seed=2))
        sim = DataSim(2, seed=47, tmp_path=tmp_path)
        sim.run(5_000)
        try:
            n0 = sim.nodes["n0"]
            full = n0._on_node_stats("x", {"full": True})
            section = full["roofline"]
            assert section["identity_ok"]
            assert section["peaks"]["source"] == "stub"
            # narrowing: a spans-only poll ships no roofline payload, a
            # roofline-only poll ships no span ring
            narrowed = n0._on_node_stats(
                "x", {"full": True, "sections": ["roofline"]})
            assert "roofline" in narrowed
            assert "spans" not in narrowed.get("telemetry", {})
            spans_only = n0._on_node_stats(
                "x", {"full": True, "sections": ["spans"]})
            assert "roofline" not in spans_only
        finally:
            for n in sim.nodes.values():
                n.close()
            if prev is not None:
                roofline.set_peaks(prev)
