"""knn_topk_streaming must agree exactly with the materializing knn_topk:
same scores, same doc ids, doc-id-ascending tie-break across chunk
boundaries (ops/fused.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from opensearch_tpu.ops.fused import knn_topk, knn_topk_streaming


def _setup(n, d, n_dup=0, seed=0):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n, d)).astype(np.float32)
    if n_dup:
        # duplicate rows spread across the corpus force exact score ties
        # that must resolve by ascending doc id, incl. across chunks
        src = rng.integers(0, n, n_dup)
        dst = rng.integers(0, n, n_dup)
        v[dst] = v[src]
    n_pad = 1 << (n - 1).bit_length()
    vp = np.zeros((n_pad, d), np.float32)
    vp[:n] = v
    vectors = jnp.asarray(vp)
    norms = jnp.sum(vectors * vectors, axis=-1)
    valid = jnp.arange(n_pad) < n
    return vectors, norms, valid


@pytest.mark.parametrize("similarity", ["l2_norm", "cosine", "dot_product"])
def test_streaming_matches_materializing(similarity):
    vectors, norms, valid = _setup(3000, 16)
    q = jnp.asarray(
        np.random.default_rng(1).standard_normal((7, 16)).astype(np.float32))
    ref_v, ref_i = knn_topk(vectors, norms, valid, q, k=5,
                            similarity=similarity)
    got_v, got_i = knn_topk_streaming(vectors, norms, valid, q, k=5,
                                      similarity=similarity, chunk=512)
    np.testing.assert_allclose(np.asarray(ref_v), np.asarray(got_v),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(got_i))


def test_streaming_tiebreak_across_chunks():
    # heavy duplication: ties everywhere, ids must come back ascending
    vectors, norms, valid = _setup(2048, 8, n_dup=1500, seed=3)
    q = jnp.asarray(
        np.random.default_rng(4).standard_normal((5, 8)).astype(np.float32))
    ref_v, ref_i = knn_topk(vectors, norms, valid, q, k=10)
    got_v, got_i = knn_topk_streaming(vectors, norms, valid, q, k=10,
                                      chunk=256)
    np.testing.assert_allclose(np.asarray(ref_v), np.asarray(got_v),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(got_i))


def test_streaming_fewer_docs_than_k():
    vectors, norms, valid = _setup(3, 4)
    q = jnp.asarray(np.ones((2, 4), np.float32))
    got_v, got_i = knn_topk_streaming(vectors, norms, valid, q, k=8,
                                      chunk=2)
    ref_v, ref_i = knn_topk(vectors, norms, valid, q, k=8)
    finite = np.isfinite(np.asarray(ref_v))
    np.testing.assert_array_equal(finite, np.isfinite(np.asarray(got_v)))
    np.testing.assert_array_equal(np.asarray(ref_i)[finite],
                                  np.asarray(got_i)[finite])


# ---------------------------------------------------------------------------
# serving-path integration: _search must score large exact segments through
# the streaming program (a kernel no request reaches proves nothing)
# and return results identical to the materializing scan
# ---------------------------------------------------------------------------

def test_executor_serving_path_uses_streaming(tmp_path, monkeypatch):
    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import distributed_serving, executor

    # force the shard-level knn scan (not the distributed bundle) and make
    # the tiny test corpus eligible for the streaming strategy
    monkeypatch.setattr(distributed_serving, "enabled", False)
    monkeypatch.setattr(executor, "STREAMING_MIN_DOCS", 8)
    monkeypatch.setattr(executor, "STREAMING_CHUNK", 32)

    node = TpuNode(tmp_path / "data")
    node.create_index("vecs", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": 4, "space_type": "l2"},
            "n": {"type": "long"},
        }},
    })
    rng = np.random.default_rng(3)
    node.bulk([
        ("index", {"_index": "vecs", "_id": f"d{i}"},
         {"v": rng.standard_normal(4).round(3).tolist(), "n": i})
        for i in range(96)
    ], refresh=True)

    body = {"query": {"knn": {"v": {"vector": [0.1, -0.2, 0.3, 0.0],
                                    "k": 7}}}, "size": 7}
    executor.knn_path_stats["streaming"] = 0
    streamed = node.search("vecs", body)
    assert executor.knn_path_stats["streaming"] > 0, \
        "streaming scan did not serve the query"

    monkeypatch.setattr(executor, "STREAMING_MIN_DOCS", 10**9)
    executor.knn_path_stats["materializing"] = 0
    materialized = node.search("vecs", body)
    assert executor.knn_path_stats["materializing"] > 0

    assert [h["_id"] for h in streamed["hits"]["hits"]] == \
           [h["_id"] for h in materialized["hits"]["hits"]]
    assert np.allclose(
        [h["_score"] for h in streamed["hits"]["hits"]],
        [h["_score"] for h in materialized["hits"]["hits"]],
        rtol=1e-6, atol=0)


def test_executor_streaming_with_filter(tmp_path, monkeypatch):
    """The streaming scan must honor the knn filter (mask folded into valid
    BEFORE top-k) identically to the materializing scan."""
    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import distributed_serving, executor

    monkeypatch.setattr(distributed_serving, "enabled", False)
    monkeypatch.setattr(executor, "STREAMING_MIN_DOCS", 8)
    monkeypatch.setattr(executor, "STREAMING_CHUNK", 32)

    node = TpuNode(tmp_path / "data")
    node.create_index("vecs", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": 4, "space_type": "l2"},
            "n": {"type": "long"},
        }},
    })
    rng = np.random.default_rng(5)
    node.bulk([
        ("index", {"_index": "vecs", "_id": f"d{i}"},
         {"v": rng.standard_normal(4).round(3).tolist(), "n": i})
        for i in range(64)
    ], refresh=True)

    body = {"query": {"knn": {"v": {
        "vector": [0.0, 0.1, 0.0, -0.1], "k": 5,
        "filter": {"range": {"n": {"lt": 20}}},
    }}}, "size": 5}
    streamed = node.search("vecs", body)
    for h in streamed["hits"]["hits"]:
        assert h["_source"]["n"] < 20

    monkeypatch.setattr(executor, "STREAMING_MIN_DOCS", 10**9)
    materialized = node.search("vecs", body)
    assert [h["_id"] for h in streamed["hits"]["hits"]] == \
           [h["_id"] for h in materialized["hits"]["hits"]]
