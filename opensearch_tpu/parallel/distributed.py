"""Distributed search step: shard_map fan-out + on-device cross-shard merge.

This is the TPU-native replacement for the reference's scatter-gather
pipeline (SURVEY.md §3.2: AbstractSearchAsyncAction.performPhaseOnShard:281
fan-out over transport, then SearchPhaseController.mergeTopDocs:224 k-way
merge on the coordinator JVM heap):

- the fan-out is a `shard_map` over the mesh "data" axis — every shard's
  query phase runs simultaneously on its own chip against HBM-resident
  segment arrays;
- intra-shard tensor parallelism splits the vector dim over the "model"
  axis; partial dot products are `psum`-reduced over ICI;
- the cross-shard merge is an `all_gather` of per-shard (score, global_doc)
  top-k pairs over ICI followed by one more top_k — or, with ring=True, an
  S-1 step `ppermute` ring pass that carries a running top-k around the data
  axis (the ring-attention topology with (k-best) state instead of KV
  blocks, SURVEY.md §2.5 "SP analog"), keeping peak memory at 2k per chip
  instead of S*k.

Everything here is jittable and shape-static: it is the flagship multi-chip
program that `__graft_entry__.dryrun_multichip` compiles over a virtual mesh.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from opensearch_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from opensearch_tpu.ops import knn as knn_ops


class ShardedSegments(NamedTuple):
    """Per-shard segment arrays stacked along a leading shard axis [S, ...]."""

    vectors: jnp.ndarray        # [S, n_pad, d]
    norms_sq: jnp.ndarray       # [S, n_pad]
    valid: jnp.ndarray          # [S, n_pad] bool
    postings_docs: jnp.ndarray  # [S, p_pad] int32
    postings_tfs: jnp.ndarray   # [S, p_pad] f32
    doc_len: jnp.ndarray        # [S, n_pad] f32


class QueryArgs(NamedTuple):
    """Per-query small arrays (replicated over the mesh). term_idfs/avgdl
    carry REAL per-shard statistics (shard-local IDF + average doc length,
    the default Lucene similarity scoping); k1/b come from the index's
    similarity settings (index/similarity/, BM25Similarity defaults)."""

    query_vectors: jnp.ndarray  # [B, d]
    term_offsets: jnp.ndarray   # [S, Q] int32 (per shard: offsets differ)
    term_lengths: jnp.ndarray   # [S, Q] int32
    term_idfs: jnp.ndarray      # [S, Q] f32 (per-shard IDF)
    avgdl: jnp.ndarray          # [S] f32 (per-shard average doc length)
    lexical_weight: jnp.ndarray # scalar f32 (hybrid mix)
    vector_weight: jnp.ndarray  # scalar f32
    k1: Any = 1.2   # BM25 k1 (index setting; scalar)
    b: Any = 0.75   # BM25 b (index setting; scalar)


def _merge_topk(vals_a, ids_a, vals_b, ids_b, k: int):
    vals = jnp.concatenate([vals_a, vals_b], axis=-1)
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    top_vals, pos = jax.lax.top_k(vals, k)
    return top_vals, jnp.take_along_axis(ids, pos, axis=-1)


def _shard_query_phase(
    segs: ShardedSegments,
    q: QueryArgs,
    *,
    k: int,
    window: int,
    similarity: str,
):
    """Body executed per (data, model) mesh slot. Blocks arrive with the
    leading shard axis reduced to 1 and the vector dim split over MODEL."""
    vectors = segs.vectors[0]          # [n_pad, d_local]
    norms = segs.norms_sq[0]
    valid = segs.valid[0]
    n_pad = vectors.shape[0]

    # ---- vector scoring (TP over MODEL axis: partial dots, psum) ----
    partial = jnp.einsum(
        "bd,nd->bn", q.query_vectors, vectors, preferred_element_type=jnp.float32
    )
    dots = jax.lax.psum(partial, MODEL_AXIS)
    q_sq = jax.lax.psum(
        jnp.sum(q.query_vectors * q.query_vectors, axis=-1, keepdims=True), MODEL_AXIS
    )
    # norms_sq is stored whole (not dim-split); take it from model rank 0 view
    if similarity == "l2_norm":
        raw = -(q_sq - 2.0 * dots + norms[None, :])
        d_sq = jnp.maximum(-raw, 0.0)
        vec_scores = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(q_sq)
        v_norm = jnp.sqrt(norms)[None, :]
        vec_scores = (1.0 + dots / jnp.maximum(q_norm * v_norm, 1e-12)) / 2.0
    else:
        vec_scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))

    # ---- lexical scoring (postings resident on this shard) ----
    offsets = q.term_offsets[0]
    lengths = q.term_lengths[0]
    idfs = q.term_idfs[0]
    avgdl = q.avgdl[0]
    win = jnp.arange(window, dtype=jnp.int32)
    idx = offsets[:, None] + win[None, :]
    tvalid = win[None, :] < lengths[:, None]
    idx = jnp.where(tvalid, idx, 0)
    docs = segs.postings_docs[0][idx]
    tfs = segs.postings_tfs[0][idx]
    dl = segs.doc_len[0][docs]
    denom = tfs + q.k1 * (1.0 - q.b + q.b * dl / jnp.maximum(avgdl, 1e-6))
    contrib = idfs[:, None] * tfs / jnp.maximum(denom, 1e-9)
    contrib = jnp.where(tvalid, contrib, 0.0)
    docs = jnp.where(tvalid, docs, 0)
    lex_scores = jnp.zeros(n_pad, jnp.float32).at[docs.reshape(-1)].add(
        contrib.reshape(-1)
    )

    # ---- hybrid combine + per-shard top-k ----
    scores = (
        q.vector_weight * vec_scores + q.lexical_weight * lex_scores[None, :]
    )
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    from opensearch_tpu.ops.topk import blockwise_topk

    # blockwise_topk self-gates: small shards fall back to lax.top_k
    top_vals, top_ids = blockwise_topk(scores, k)       # [B, k]
    shard_idx = jax.lax.axis_index(DATA_AXIS)
    global_ids = top_ids + shard_idx * n_pad
    return top_vals, global_ids


def _allgather_merge(top_vals, global_ids, k: int):
    all_vals = jax.lax.all_gather(top_vals, DATA_AXIS, axis=1, tiled=True)
    all_ids = jax.lax.all_gather(global_ids, DATA_AXIS, axis=1, tiled=True)
    vals, pos = jax.lax.top_k(all_vals, k)
    return vals, jnp.take_along_axis(all_ids, pos, axis=-1)


def _ring_merge(top_vals, global_ids, k: int, n_shards: int):
    """S-1 ppermute steps pass a running top-k around the ring."""
    def step(i, carry):
        vals, ids, send_vals, send_ids = carry
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        recv_vals = jax.lax.ppermute(send_vals, DATA_AXIS, perm)
        recv_ids = jax.lax.ppermute(send_ids, DATA_AXIS, perm)
        vals, ids = _merge_topk(vals, ids, recv_vals, recv_ids, k)
        return vals, ids, recv_vals, recv_ids

    vals, ids, _, _ = jax.lax.fori_loop(
        0, n_shards - 1, step, (top_vals, global_ids, top_vals, global_ids)
    )
    return vals, ids


def build_distributed_search(
    mesh,
    *,
    k: int,
    window: int,
    similarity: str = "l2_norm",
    ring: bool = False,
):
    """Returns a jitted fn(segments: ShardedSegments, q: QueryArgs) ->
    (scores [B, k], global_doc_ids [B, k]) executing over the mesh."""
    n_shards = mesh.shape[DATA_AXIS]

    seg_specs = ShardedSegments(
        vectors=P(DATA_AXIS, None, MODEL_AXIS),
        norms_sq=P(DATA_AXIS, None),
        valid=P(DATA_AXIS, None),
        postings_docs=P(DATA_AXIS, None),
        postings_tfs=P(DATA_AXIS, None),
        doc_len=P(DATA_AXIS, None),
    )
    q_specs = QueryArgs(
        query_vectors=P(None, MODEL_AXIS),
        term_offsets=P(DATA_AXIS, None),
        term_lengths=P(DATA_AXIS, None),
        term_idfs=P(DATA_AXIS, None),
        avgdl=P(DATA_AXIS),
        lexical_weight=P(),
        vector_weight=P(),
        k1=P(),
        b=P(),
    )

    def step(segs: ShardedSegments, q: QueryArgs):
        top_vals, global_ids = _shard_query_phase(
            segs, q, k=k, window=window, similarity=similarity
        )
        if ring:
            vals, ids = _ring_merge(top_vals, global_ids, k, n_shards)
        else:
            vals, ids = _allgather_merge(top_vals, global_ids, k)
        return vals, ids

    mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(seg_specs, q_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


# --------------------------------------------------------------------- #
# serving-grade exact-kNN step (wired into _search by
# search/distributed_serving.py — SearchPhaseController.mergeTopDocs:224
# replaced by an on-device all_gather + top_k)
# --------------------------------------------------------------------- #


def build_knn_serving_step(
    mesh,
    *,
    k_shard: int,
    k_final: int,
    similarity: str,
    kernel: str = "xla",
    score_precision: str = "fp32",
    interpret: bool = False,
):
    """Exact k-NN over S shards laid out on D devices (S % D == 0; each
    device owns a block of S/D shards — the two-level layout of the
    reference: shards across nodes, concurrent segment slices within one).

    fn(vectors [S, n, d], norms_sq [S, n], valid [S, n], queries [B, d])
      -> packed int32 [B, 2 * k_final + S]: ONE array, so a launch's
         results cross to the host in one transfer; `unpack` reads it as
         (scores [B, k_final], global_ids [B, k_final], counts [S, B])

    global id = shard_idx * n + flat_doc; counts[s, b] = number of finite
    per-shard winners (the shard's matched-doc count, ≤ k_shard). At the
    default (kernel="xla", score_precision="fp32") scoring runs in fp32
    with HIGHEST matmul precision so results are exact and identical to
    the host path. Any other combination routes each
    local shard's scan through ops/pallas_knn.knn_fused_shard — the fused
    blockwise kernel (kernel="pallas"; `interpret` threads the caller's
    platform resolution, ONE read per program build) or its bit-compatible
    XLA reference (kernel="xla" at a reduced precision), so pallas-vs-xla
    mesh programs compare identical math per precision. Reduced-precision
    scans end in the kernel's exact fp32 rescore, keeping scores in the
    serving score space; fused slots past a shard's valid-doc count carry
    explicit (-inf, -1) global ids. The S % D == 0 precondition is the
    caller's (distributed_serving picks D as a divisor of S)."""
    fused = (kernel, score_precision) != ("xla", "fp32")

    # a stable scope name: a trace reduction can match the step's device ops
    # to the host's `launch.device` span by name after a refactor
    @jax.named_scope("mesh_knn_step")
    def step(vectors, norms_sq, valid, queries):
        # block shapes: [S_local, n, d], [S_local, n], [S_local, n], [B, d]
        s_local, n_flat, _d = vectors.shape
        if fused:
            # one fused blockwise scan per LOCAL shard (s_local is a
            # static block shape, so this unrolls at trace time into the
            # single compiled per-device program)
            from opensearch_tpu.ops import pallas_knn

            per_v, per_i = [], []
            for si in range(s_local):
                v, i = pallas_knn.knn_fused_shard(
                    vectors[si], norms_sq[si], valid[si], queries,
                    k=k_shard, similarity=similarity,
                    score_precision=score_precision,
                    impl=kernel, interpret=interpret,
                )
                per_v.append(v)
                per_i.append(i)
            vals = jnp.stack(per_v)                    # [S_local, B, k]
            ids = jnp.stack(per_i)
        else:
            dots = jnp.einsum(
                "bd,snd->sbn", queries, vectors,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            q_sq = jnp.sum(queries * queries, axis=-1)[None, :, None]
            if similarity == "l2_norm":
                d_sq = jnp.maximum(
                    q_sq - 2.0 * dots + norms_sq[:, None, :], 0.0)
                scores = 1.0 / (1.0 + d_sq)
            elif similarity == "cosine":
                denom = jnp.sqrt(q_sq) * jnp.sqrt(norms_sq)[:, None, :]
                scores = (1.0 + dots / jnp.maximum(denom, 1e-12)) / 2.0
            else:  # dot_product
                scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
            scores = jnp.where(valid[:, None, :], scores, -jnp.inf)

            # per-shard top-k (k-NN plugin: k applies per shard)
            vals, ids = jax.vmap(lambda s: jax.lax.top_k(s, k_shard))(scores)
        counts = jnp.sum(jnp.isfinite(vals), axis=-1)          # [S_local, B]

        shard0 = jax.lax.axis_index(DATA_AXIS) * s_local
        offsets = (shard0 + jnp.arange(s_local))[:, None, None] * n_flat
        if fused:
            # fused scans mark empty slots id -1: keep them explicit
            # instead of wrapping them into a neighbouring shard's range
            gids = jnp.where(ids >= 0, ids + offsets, -1)
        else:
            gids = ids + offsets

        # merge: local shards concat in shard order, gather device blocks in
        # data-axis order — candidate position order is (shard asc, rank
        # asc), so lax.top_k's lowest-position tie-break reproduces the host
        # merge's (-score, shard, segment, doc) ordering exactly.
        b = vals.shape[1]
        local_vals = jnp.transpose(vals, (1, 0, 2)).reshape(b, s_local * k_shard)
        local_ids = jnp.transpose(gids, (1, 0, 2)).reshape(b, s_local * k_shard)
        all_vals = jax.lax.all_gather(local_vals, DATA_AXIS, axis=1, tiled=True)
        all_ids = jax.lax.all_gather(local_ids, DATA_AXIS, axis=1, tiled=True)
        top_vals, pos = jax.lax.top_k(all_vals, k_final)
        top_ids = jnp.take_along_axis(all_ids, pos, axis=-1)
        all_counts = jax.lax.all_gather(counts, DATA_AXIS, axis=0, tiled=True)
        # one output (layout: `unpack`): the scores' float32 bits carried
        # as int32, -inf included, beside the int32 ids and counts
        return jnp.concatenate(
            [jax.lax.bitcast_convert_type(top_vals, jnp.int32), top_ids,
             all_counts.T], axis=1)

    mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None),
                  P(DATA_AXIS, None), P(None, None)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)


def unpack(packed, k_final: int, s: int):
    """Host side of `build_knn_serving_step`'s one output: packed int32
    [B, 2 * k_final + S] on the host -> (scores float32 [B, k_final],
    global_ids int32 [B, k_final], counts int32 [S, B]), views of it and
    bit for bit what the step computed."""
    packed = np.asarray(packed)
    assert packed.dtype == np.int32 and packed.shape[1] == 2 * k_final + s, (
        packed.dtype, packed.shape, k_final, s)
    return (packed[:, :k_final].view(np.float32),
            packed[:, k_final:2 * k_final],
            packed[:, 2 * k_final:].T)


def shard_arrays_to_mesh(mesh, segments: ShardedSegments) -> ShardedSegments:
    """device_put every array with its mesh sharding (host -> HBM layout)."""
    seg_shardings = ShardedSegments(
        vectors=NamedSharding(mesh, P(DATA_AXIS, None, MODEL_AXIS)),
        norms_sq=NamedSharding(mesh, P(DATA_AXIS, None)),
        valid=NamedSharding(mesh, P(DATA_AXIS, None)),
        postings_docs=NamedSharding(mesh, P(DATA_AXIS, None)),
        postings_tfs=NamedSharding(mesh, P(DATA_AXIS, None)),
        doc_len=NamedSharding(mesh, P(DATA_AXIS, None)),
    )
    return ShardedSegments(
        *(jax.device_put(a, s) for a, s in zip(segments, seg_shardings))
    )
