"""The dense per-document vector scorer: one score for EVERY row.

`script_score`'s `knn_score` / `cosineSimilarity` / `l2Squared` score every
matching doc (search/executor.py), which is a different operation from a
kNN query's top-k scan: that one is ops/pallas_knn.knn_fused and nothing
here serves it. Segment vectors live in HBM as [n_pad, d] matrices; a
(batch of) queries becomes one [B, d] x [d, n_pad] matmul followed by the
OpenSearch k-NN score-space transforms. `canonical_similarity` names the
three spaces for both.

Score spaces match the k-NN plugin's conventions so `_score` values are
drop-in comparable:
  l2        -> 1 / (1 + d^2)
  cosine    -> (1 + cos) / 2     ("cosinesimil")
  dot/inner -> d >= 0 ? d + 1 : 1 / (1 - d)  ("innerproduct")
"""

from __future__ import annotations

import jax.numpy as jnp

from opensearch_tpu.search.profile import profiled_kernel

L2 = "l2_norm"
COSINE = "cosine"
DOT = "dot_product"

_ALIASES = {
    "l2": L2, "l2_norm": L2,
    "cosine": COSINE, "cosinesimil": COSINE,
    "dot_product": DOT, "innerproduct": DOT, "dot": DOT, "max_inner_product": DOT,
}


def canonical_similarity(name: str) -> str:
    sim = _ALIASES.get(name)
    if sim is None:
        raise ValueError(f"unknown vector similarity [{name}]")
    return sim


def _raw_similarity(
    queries: jnp.ndarray,      # [B, d] float32
    vectors: jnp.ndarray,      # [n_pad, d] float32 (bf16 upcast upstream)
    norms_sq: jnp.ndarray,     # [n_pad] float32 precomputed ||v||^2
    similarity: str,
) -> jnp.ndarray:
    """[B, n_pad] raw similarity, higher = closer, before score-space map.

    HIGHEST matmul precision: exact-path scores must match an fp32 host
    reference bit-for-bit (and the distributed serving program, which also
    runs HIGHEST) — the default TPU bf16 lowering flips near-tie
    neighbors."""
    sim = canonical_similarity(similarity)
    import jax as _jax

    dots = jnp.einsum(
        "bd,nd->bn", queries, vectors, preferred_element_type=jnp.float32,
        precision=_jax.lax.Precision.HIGHEST,
    )
    if sim == L2:
        q_sq = jnp.sum(queries * queries, axis=-1, keepdims=True)      # [B,1]
        # negative squared distance: monotonic for ranking
        return -(q_sq - 2.0 * dots + norms_sq[None, :])
    if sim == COSINE:
        q_norm = jnp.sqrt(jnp.sum(queries * queries, axis=-1, keepdims=True))
        v_norm = jnp.sqrt(norms_sq)[None, :]
        return dots / jnp.maximum(q_norm * v_norm, 1e-12)
    return dots  # DOT


# public entry: profiled when called eagerly; exact_knn_scores uses the
# bare _raw_similarity so its own kernel record doesn't double-count
raw_similarity = profiled_kernel("knn_raw_similarity")(_raw_similarity)


def knn_score(raw: jnp.ndarray, similarity: str) -> jnp.ndarray:
    """Map raw similarity to the OpenSearch k-NN plugin score space."""
    sim = canonical_similarity(similarity)
    if sim == L2:
        d_sq = jnp.maximum(-raw, 0.0)
        return 1.0 / (1.0 + d_sq)
    if sim == COSINE:
        return (1.0 + raw) / 2.0
    return jnp.where(raw >= 0, raw + 1.0, 1.0 / (1.0 - raw))


@profiled_kernel("knn_exact_scores")
def exact_knn_scores(
    queries: jnp.ndarray,
    vectors: jnp.ndarray,
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,        # bool [n_pad]: present & live & not padding
    similarity: str,
) -> jnp.ndarray:
    """[B, n_pad] k-NN scores with invalid docs pushed to -inf."""
    raw = _raw_similarity(queries, vectors, norms_sq, similarity)
    scores = knn_score(raw, similarity)
    return jnp.where(valid[None, :], scores, -jnp.inf)
