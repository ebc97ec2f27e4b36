"""The served multi-shard exact-kNN step: shard_map fan-out + on-device
cross-shard merge.

This is the TPU-native replacement for the reference's scatter-gather
pipeline (SURVEY.md §3.2: AbstractSearchAsyncAction.performPhaseOnShard:281
fan-out over transport, then SearchPhaseController.mergeTopDocs:224 k-way
merge on the coordinator JVM heap):

- the fan-out is a `shard_map` over the mesh "data" axis — every shard's
  scan (ops/pallas_knn.knn_fused) runs simultaneously on its own chip
  against HBM-resident segment arrays;
- the cross-shard merge is an `all_gather` of per-shard (score, global_doc)
  top-k pairs over ICI followed by one more top_k.

Jittable and shape-static; search/distributed_serving.py wires it into
_search, and `__graft_entry__.dryrun_multichip` runs it over a virtual mesh
through that same path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from opensearch_tpu.ops import pallas_knn
from opensearch_tpu.parallel.mesh import DATA_AXIS


def build_knn_serving_step(
    mesh,
    *,
    k_shard: int,
    k_final: int,
    similarity: str,
    kernel: str,
    score_precision: str,
    interpret: bool,
    doc_stride: int | None = None,
):
    """Exact k-NN over S shards laid out on D devices (S % D == 0; each
    device owns a block of S/D shards — the two-level layout of the
    reference: shards across nodes, concurrent segment slices within one).

    fn(vectors [S, n, d], norms_sq [S, n], valid [S, n], queries [B, d])
      -> packed int32 [B, 2 * k_final + S]: ONE array, so a launch's
         results cross to the host in one transfer; `unpack` reads it as
         (scores [B, k_final], global_ids [B, k_final], counts [S, B])

    global id = shard_idx * n + flat_doc; counts[s, b] = number of finite
    per-shard winners (the shard's matched-doc count, ≤ k_shard). Each
    local shard is scanned by ops/pallas_knn.knn_fused, the one exact scan
    the per-shard host path also launches, so the two agree by
    construction: `kernel` ("pallas" | "xla") and `interpret` are what
    `pallas_knn.fused_impl` returned for this program (ONE platform read
    per program build), and pallas-vs-xla mesh programs compare identical
    math per precision. fp32 scans at HIGHEST matmul precision; a
    reduced-precision scan ends in the exact fp32 rescore, keeping scores
    in the serving score space; slots past a shard's valid-doc count carry
    explicit (-inf, -1) global ids. The S % D == 0 precondition is the
    caller's (distributed_serving picks D as a divisor of S).

    With `doc_stride` the program serves a multi-vector field (a `nested`
    `knn`): fn takes a fifth operand, parent [S, n] (each row's document in
    its shard's flat document space, -1 on padding rows), before the
    queries; each local shard is scanned by ops/pallas_knn.knn_nested, so
    the reduction to documents is done before the cut and the winners are
    documents; global id = shard_idx * doc_stride + flat_doc."""
    # a stable scope name: a trace reduction can match the step's device ops
    # to the host's `launch.device` span by name after a refactor
    @jax.named_scope("mesh_knn_step")
    def scan(vectors, norms_sq, valid, parent, queries):
        # block shapes: [S_local, n, d], [S_local, n], [S_local, n], [B, d]
        s_local, n_flat, _d = vectors.shape
        # one scan per LOCAL shard (s_local is a static block shape, so
        # this unrolls at trace time into the single compiled per-device
        # program)
        per_v, per_i = [], []
        for si in range(s_local):
            if parent is None:
                v, i = pallas_knn.knn_fused(
                    vectors[si], norms_sq[si], valid[si], queries,
                    k=k_shard, similarity=similarity,
                    score_precision=score_precision,
                    impl=kernel, interpret=interpret,
                )
            else:
                v, i = pallas_knn.knn_nested(
                    vectors[si], norms_sq[si], valid[si], parent[si],
                    queries, k=k_shard, n_docs=doc_stride,
                    similarity=similarity, impl=kernel,
                    interpret=interpret,
                )
            per_v.append(v)
            per_i.append(i)
        vals = jnp.stack(per_v)                    # [S_local, B, k]
        ids = jnp.stack(per_i)
        counts = jnp.sum(jnp.isfinite(vals), axis=-1)          # [S_local, B]

        shard0 = jax.lax.axis_index(DATA_AXIS) * s_local
        stride = n_flat if parent is None else doc_stride
        offsets = (shard0 + jnp.arange(s_local))[:, None, None] * stride
        # the scan marks empty slots id -1: keep them explicit instead of
        # wrapping them into a neighbouring shard's range
        gids = jnp.where(ids >= 0, ids + offsets, -1)

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

    rows = P(DATA_AXIS, None)
    if doc_stride is None:
        def step(vectors, norms_sq, valid, queries):
            return scan(vectors, norms_sq, valid, None, queries)

        in_specs = (P(DATA_AXIS, None, None), rows, rows, P(None, None))
    else:
        step = scan
        in_specs = (P(DATA_AXIS, None, None), rows, rows, rows,
                    P(None, None))
    mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=in_specs,
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
