"""IVF-PQ ANN: kernel-level recall + end-to-end engine integration.

Mirrors the k-NN plugin's test approach (recall against exact ground truth,
per-segment index structures) — reference: opensearch-project/k-NN (out of
tree; core only reserves the EnginePlugin slot, SURVEY.md §0).
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from opensearch_tpu.ops import ivfpq, pallas_knn


def _clustered(rng, n, d, n_centers=32, spread=5.0):
    centers = rng.standard_normal((n_centers, d)) * spread
    return (
        centers[rng.integers(0, n_centers, n)] + rng.standard_normal((n, d))
    ).astype(np.float32), centers


class TestIVFPQKernel:
    def test_recall_l2(self):
        rng = np.random.default_rng(0)
        n, d, k = 8_000, 32, 10
        data, centers = _clustered(rng, n, d)
        queries = (
            centers[rng.integers(0, 32, 16)] + rng.standard_normal((16, d))
        ).astype(np.float32)

        idx = ivfpq.build(data, nlist=64, m=8, iters=6)
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, -1)
        valid = jnp.ones(n, bool)
        q = jnp.asarray(queries)
        vals, ids = ivfpq.search_index(
            idx, vecs, norms, valid, q, k=k, nprobe=16, rerank=128
        )
        evals, eids = pallas_knn.knn_fused(vecs, norms, valid, q, k=k, impl="xla")
        ids, eids = np.asarray(ids), np.asarray(eids)
        recall = np.mean(
            [len(set(ids[i]) & set(eids[i])) / k for i in range(len(queries))]
        )
        assert recall >= 0.8
        # rescored scores are exact -> the true top-1 it found scores equal
        assert np.allclose(
            np.asarray(vals)[:, 0],
            np.asarray(evals)[:, 0],
            atol=1e-3,
        ) or recall >= 0.95

    def test_full_nprobe_is_near_exhaustive(self):
        rng = np.random.default_rng(1)
        n, d, k = 2_000, 16, 5
        data, _ = _clustered(rng, n, d, n_centers=8)
        idx = ivfpq.build(data, nlist=16, m=4, iters=6)
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, -1)
        valid = jnp.ones(n, bool)
        q = jnp.asarray(data[:8])  # self-queries: top-1 must be self
        vals, ids = ivfpq.search_index(
            idx, vecs, norms, valid, q, k=k, nprobe=16, rerank=256
        )
        assert np.array_equal(np.asarray(ids)[:, 0], np.arange(8))
        assert np.allclose(np.asarray(vals)[:, 0], 1.0, atol=1e-3)

    def test_deleted_docs_excluded(self):
        rng = np.random.default_rng(2)
        n, d = 1_000, 16
        data, _ = _clustered(rng, n, d, n_centers=4)
        idx = ivfpq.build(data, nlist=8, m=4, iters=4)
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, -1)
        valid = np.ones(n, bool)
        valid[0] = False  # delete the exact-match doc
        vals, ids = ivfpq.search_index(
            idx, vecs, norms, jnp.asarray(valid), jnp.asarray(data[:1]),
            k=3, nprobe=8, rerank=64,
        )
        assert 0 not in np.asarray(ids)[0].tolist()

    def test_cosine_normalized(self):
        rng = np.random.default_rng(3)
        n, d, k = 4_000, 32, 10
        data, _ = _clustered(rng, n, d)
        q_host = data[:8] * 3.7  # cosine is scale-invariant
        idx = ivfpq.build(data, nlist=32, m=8, iters=6, normalized=True)
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, -1)
        valid = jnp.ones(n, bool)
        vals, ids = ivfpq.search_index(
            idx, vecs, norms, valid, jnp.asarray(q_host),
            k=k, nprobe=16, rerank=128, similarity="cosine",
        )
        ids = np.asarray(ids)
        assert np.array_equal(ids[:, 0], np.arange(8))
        assert np.allclose(np.asarray(vals)[:, 0], 1.0, atol=1e-3)


class TestIVFPQEngine:
    """End-to-end: mapping with method ivf_pq -> knn query uses the ANN."""

    @pytest.fixture()
    def node(self, tmp_path):
        from opensearch_tpu.node import TpuNode

        return TpuNode(tmp_path / "node")

    def test_knn_query_via_ann(self, node):
        rng = np.random.default_rng(7)
        n, d = 600, 16
        data, centers = _clustered(rng, n, d, n_centers=4)
        node.create_index("vecs", {
            "settings": {"index": {"number_of_shards": 1}},
            "mappings": {"properties": {"v": {
                "type": "knn_vector", "dimension": d,
                "method": {"name": "ivf_pq", "parameters": {
                    "nlist": 8, "m": 4, "nprobe": 8, "min_train": 100,
                }},
            }}},
        })
        for i in range(n):
            node.index_doc("vecs", str(i), {"v": data[i].tolist()})
        node.refresh("vecs")

        # the published segment really carries an ANN structure
        snap = node.indices["vecs"].shards[0].acquire_searcher()
        anns = [
            dev.vector_fields["v"].ann
            for _, dev in snap.segments
            if "v" in dev.vector_fields
        ]
        assert any(a is not None for a in anns)

        res = node.search("vecs", {
            "size": 5,
            "query": {"knn": {"v": {"vector": data[17].tolist(), "k": 5}}},
        })
        hits = res["hits"]["hits"]
        assert hits[0]["_id"] == "17"
        assert hits[0]["_score"] == pytest.approx(1.0, abs=1e-3)

    def test_cosinesimil_alias_scores_match_exact(self):
        # regression: alias must canonicalize before the rescore branch
        rng = np.random.default_rng(5)
        n, d = 2_000, 16
        data, _ = _clustered(rng, n, d, n_centers=4)
        idx = ivfpq.build(data, nlist=16, m=4, iters=4, normalized=True)
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, -1)
        valid = jnp.ones(n, bool)
        vals, ids = ivfpq.search_index(
            idx, vecs, norms, valid, jnp.asarray(data[:4]),
            k=5, nprobe=16, similarity="cosinesimil",
        )
        evals, eids = pallas_knn.knn_fused(
            vecs, norms, valid, jnp.asarray(data[:4]), k=5,
            similarity="cosine", impl="xla")
        assert np.array_equal(np.asarray(ids)[:, 0], np.asarray(eids)[:, 0])
        assert np.allclose(np.asarray(vals)[:, 0], np.asarray(evals)[:, 0], atol=1e-3)

    def test_k_larger_than_candidate_pool(self):
        # regression: k > nprobe * l_pad must pad, not crash top_k
        rng = np.random.default_rng(6)
        n, d = 1_000, 16
        data, _ = _clustered(rng, n, d, n_centers=4)
        idx = ivfpq.build(data, nlist=64, m=4, iters=4)
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, -1)
        valid = jnp.ones(n, bool)
        pool = 2 * idx.l_pad
        k = pool + 13
        vals, ids = ivfpq.search_index(
            idx, vecs, norms, valid, jnp.asarray(data[:2]), k=k, nprobe=2
        )
        assert vals.shape == (2, k) and ids.shape == (2, k)
        assert np.all(np.asarray(ids)[:, pool:] == -1)

    def test_method_survives_segment_roundtrip(self, tmp_path):
        from opensearch_tpu.index.segment import (
            HostVectorField, load_segment, save_segment,
        )
        import opensearch_tpu.index.segment as segmod

        # build a minimal HostSegment via the public builder path
        from opensearch_tpu.index.analysis import AnalysisRegistry
        from opensearch_tpu.index.mapper import MapperService

        ms = MapperService({"properties": {"v": {
            "type": "dense_vector", "dims": 4,
            "method": {"name": "ivf_pq", "parameters": {"nlist": 4}},
        }}}, AnalysisRegistry.from_index_settings(None))
        builder = segmod.SegmentBuilder(ms, "s0")
        for i in range(3):
            builder.add(ms.parse_document(str(i), {"v": [float(i), 0, 0, 0]}), seq_no=i)
        seg = builder.build()
        save_segment(seg, tmp_path)
        loaded = load_segment(tmp_path, "s0")
        assert loaded.vector_fields["v"].method == {
            "name": "ivf_pq", "parameters": {"nlist": 4},
        }

    def test_malformed_method_parameters_rejected(self, node):
        from opensearch_tpu.search.query_dsl import parse_query

        q = parse_query({"knn": {"v": {
            "vector": [1.0], "k": 2, "method_parameters": [8],
        }}})
        assert q.method_parameters is None

    def test_small_segment_stays_exact(self, node):
        node.create_index("tiny", {
            "mappings": {"properties": {"v": {
                "type": "knn_vector", "dimension": 4,
                "method": {"name": "ivf_pq"},
            }}},
        })
        for i in range(10):
            node.index_doc("tiny", str(i), {"v": [float(i), 0.0, 0.0, 0.0]})
        node.refresh("tiny")
        res = node.search("tiny", {
            "query": {"knn": {"v": {"vector": [3.0, 0, 0, 0], "k": 3}}},
        })
        assert res["hits"]["hits"][0]["_id"] == "3"
