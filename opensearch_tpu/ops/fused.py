"""Fused single-chip query programs: score + top_k in one XLA executable.

The flagship forward step (the analog of the reference's hot query loop,
ContextIndexSearcher.search + TopScoreDocCollector, SURVEY.md §3.2 ★★):
hybrid BM25 + exact-kNN scoring over one segment's HBM-resident arrays,
ending in jax.lax.top_k — one compiled program, no host round-trips.

The general executor (search/executor.py) composes eager jnp ops for
arbitrary query trees; these fused paths serve the common shapes (match,
knn, hybrid) and the benchmark/graft entry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from opensearch_tpu.ops import topk as topk_ops


def hybrid_score_topk(
    postings_docs: jnp.ndarray,   # int32 [p_pad]
    postings_tfs: jnp.ndarray,    # f32 [p_pad]
    doc_len: jnp.ndarray,         # f32 [n_pad]
    vectors: jnp.ndarray,         # f32/bf16 [n_pad, d]
    norms_sq: jnp.ndarray,        # f32 [n_pad]
    valid: jnp.ndarray,           # bool [n_pad]
    offsets: jnp.ndarray,         # int32 [Q]
    lengths: jnp.ndarray,         # int32 [Q]
    idfs: jnp.ndarray,            # f32 [Q]
    avgdl: jnp.ndarray,           # f32 scalar
    queries: jnp.ndarray,         # f32 [B, d]
    lexical_weight: jnp.ndarray,  # f32 scalar
    vector_weight: jnp.ndarray,   # f32 scalar
    *,
    k: int,
    window: int,
    similarity: str = "l2_norm",
    k1: float = 1.2,
    b: float = 0.75,
):
    """Returns (scores [B, k], doc_ids [B, k])."""
    n_pad = doc_len.shape[0]

    # lexical: masked postings-window gather + scatter-add (VPU)
    win = jnp.arange(window, dtype=jnp.int32)
    idx = offsets[:, None] + win[None, :]
    tvalid = win[None, :] < lengths[:, None]
    idx = jnp.where(tvalid, idx, 0)
    docs = postings_docs[idx]
    tfs = postings_tfs[idx]
    dl = doc_len[docs]
    denom = tfs + k1 * (1.0 - b + b * dl / jnp.maximum(avgdl, 1e-6))
    contrib = idfs[:, None] * tfs / jnp.maximum(denom, 1e-9)
    contrib = jnp.where(tvalid, contrib, 0.0)
    docs = jnp.where(tvalid, docs, 0)
    lex = jnp.zeros(n_pad, jnp.float32).at[docs.reshape(-1)].add(contrib.reshape(-1))

    # vector: one [B,d]x[d,n] matmul (MXU) + score-space transform; HIGHEST
    # precision keeps the exact path exact (see knn_topk)
    dots = jnp.einsum(
        "bd,nd->bn", queries, vectors.astype(queries.dtype),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if similarity == "l2_norm":
        q_sq = jnp.sum(queries * queries, axis=-1, keepdims=True)
        d_sq = jnp.maximum(q_sq - 2.0 * dots + norms_sq[None, :], 0.0)
        vec = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(jnp.sum(queries * queries, axis=-1, keepdims=True))
        vec = (1.0 + dots / jnp.maximum(q_norm * jnp.sqrt(norms_sq)[None, :], 1e-12)) / 2.0
    else:
        vec = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))

    scores = vector_weight * vec + lexical_weight * lex[None, :]
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return topk_ops.blockwise_topk(scores, k)


def knn_topk(
    vectors: jnp.ndarray,
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    similarity: str = "l2_norm",
):
    """Pure exact-kNN fused path (the BASELINE config #1 program).

    HIGHEST matmul precision: the default TPU lowering runs fp32 einsum as
    bf16 MXU passes, which flips near-tie neighbors vs an fp32 host
    reference (recall 0.993 on the "exact" path was seen that way).
    The exact path must be exact — recall 1.0; bf16 speed belongs
    to an explicitly approximate path, not a silent downgrade."""
    dots = jnp.einsum(
        "bd,nd->bn", queries, vectors.astype(queries.dtype),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if similarity == "l2_norm":
        q_sq = jnp.sum(queries * queries, axis=-1, keepdims=True)
        d_sq = jnp.maximum(q_sq - 2.0 * dots + norms_sq[None, :], 0.0)
        scores = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(jnp.sum(queries * queries, axis=-1, keepdims=True))
        scores = (1.0 + dots / jnp.maximum(q_norm * jnp.sqrt(norms_sq)[None, :], 1e-12)) / 2.0
    else:
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    # blockwise exact top-k: a sort-based lax.top_k over a [B, 1M] row was
    # the hot spot of this function; block-max pruning + k argmax
    # passes is exact (incl. doc-id tie-break) and runs at HBM bandwidth
    return topk_ops.blockwise_topk(scores, k)


def _vector_scores(queries, vectors, norms_sq, similarity):
    """Exact similarity scores [B, m] for one corpus block (fp32-HIGHEST,
    see knn_topk's precision note)."""
    dots = jnp.einsum(
        "bd,nd->bn", queries, vectors.astype(queries.dtype),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if similarity == "l2_norm":
        q_sq = jnp.sum(queries * queries, axis=-1, keepdims=True)
        d_sq = jnp.maximum(q_sq - 2.0 * dots + norms_sq[None, :], 0.0)
        return 1.0 / (1.0 + d_sq)
    if similarity == "cosine":
        q_norm = jnp.sqrt(jnp.sum(queries * queries, axis=-1, keepdims=True))
        return (1.0 + dots / jnp.maximum(
            q_norm * jnp.sqrt(norms_sq)[None, :], 1e-12)) / 2.0
    return jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))


def knn_topk_streaming(
    vectors: jnp.ndarray,
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    similarity: str = "l2_norm",
    chunk: int = 32_768,
):
    """Exact kNN that never materializes the [B, n] score matrix.

    The roofline gap: knn_topk's einsum writes the full [B, n]
    fp32 scores to HBM (2 GB per 500-query chunk at 1M docs) and
    blockwise_topk re-reads them — ~3x the streaming floor. This variant
    scans the corpus in [chunk]-doc blocks (lax.scan), reduces each
    [B, chunk] tile to a per-block top-k immediately, and folds it into a
    running [B, k] state with one [B, 2k] top_k — so score traffic is one
    write + one read of [B, chunk] per step instead of the whole matrix,
    and XLA can overlap the next block's matmul with the current top-k.

    Exactness/tie-break: per-block reductions are exact with doc-id-asc
    ties (blockwise_topk/argmax-first contract); the cross-block merge
    concatenates running state (earlier = lower doc ids) before the new
    block, and lax.top_k takes the first of equal values, preserving
    doc-id-asc ties globally. n_pad must be a multiple of `chunk`.
    """
    n_pad, d = vectors.shape
    B = queries.shape[0]
    assert n_pad % chunk == 0, (n_pad, chunk)
    nc = n_pad // chunk

    vec_blocks = vectors.reshape(nc, chunk, d)
    norm_blocks = norms_sq.reshape(nc, chunk)
    valid_blocks = valid.reshape(nc, chunk)
    bases = (jnp.arange(nc, dtype=jnp.int32) * chunk)

    def body(carry, xs):
        best_v, best_i = carry
        vec, ns, vd, base = xs
        s = _vector_scores(queries, vec, ns, similarity)
        s = jnp.where(vd[None, :], s, -jnp.inf)
        cv, ci = topk_ops.blockwise_topk(s, min(k, chunk))
        ci = ci.astype(jnp.int32) + base
        allv = jnp.concatenate([best_v, cv], axis=1)
        alli = jnp.concatenate([best_i, ci], axis=1)
        nv, sel = jax.lax.top_k(allv, k)
        ni = jnp.take_along_axis(alli, sel, axis=1)
        return (nv, ni), None

    init = (
        jnp.full((B, k), -jnp.inf, jnp.float32),
        jnp.zeros((B, k), jnp.int32),
    )
    (vals, ids), _ = jax.lax.scan(
        body, init, (vec_blocks, norm_blocks, valid_blocks, bases)
    )
    return vals, ids


def jit_knn_streaming(k: int, similarity: str = "l2_norm",
                      chunk: int = 32_768):
    return jax.jit(functools.partial(
        knn_topk_streaming, k=k, similarity=similarity, chunk=chunk))


@functools.lru_cache(maxsize=64)
def cached_knn_streaming(k: int, similarity: str, chunk: int):
    """Shared jitted streaming program (the serving path calls this per
    segment — a fresh jax.jit per call would retrace every query)."""
    return jit_knn_streaming(k, similarity, chunk)


def jit_hybrid(k: int, window: int, similarity: str = "l2_norm"):
    return jax.jit(
        functools.partial(hybrid_score_topk, k=k, window=window, similarity=similarity)
    )


def jit_knn(k: int, similarity: str = "l2_norm"):
    return jax.jit(functools.partial(knn_topk, k=k, similarity=similarity))
