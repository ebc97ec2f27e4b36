"""The exact-kNN scan: `knn_fused`, the one implementation of exact kNN
scoring in the program, and `fused_impl`, the one rule that picks its
lowering.

The flagship hot loop (ContextIndexSearcher.search + TopScoreDocCollector,
SURVEY.md §3.2 ★★). `knn_fused` pads, preps the matmul operands at the
scan precision, scans the column into a per-query top-R pool and, for the
reduced precisions, rescores the pool in exact fp32. The pool scan has two
lowerings that share every line of scoring math:

  pallas  `pallas_knn_fused`, a hand-scheduled TPU kernel: the column
          streams through VMEM in [tile, d] blocks (grid iterations are
          sequential on a TensorCore, so VMEM scratch persists across them
          — /opt/skills/guides/pallas_guide.md "Grid and Block
          Specifications"), each block scored on the MXU and folded into a
          running [B, R] pool; no [B, n] score matrix ever exists.
          Selection avoids lax.top_k / sort (not Mosaic-lowerable): R
          rounds of max / argmax with iota-equality one-hot gathers.
  xla     `_fused_xla_pool`: the full [B, n] scores and lax.top_k. What
          serves on the CPU backend, what serves k > FUSED_MAX_K anywhere,
          and the reference the kernel's tests compare against.

Both callers — the mesh program (parallel/distributed.
build_knn_serving_step) and the per-shard path (search/executor.
shard_knn_selection) — ask `fused_impl` which of the two a launch runs;
nothing else reads the platform for that. interpret=True is the CPU tests'
parity path and nothing else: the rule turns it on only for a forced
"pallas" on the CPU backend, so any accelerator compiles the kernel (or
fails in lowering) instead of silently interpreting.

Slots past the valid-doc count carry (-inf, -1): callers drop entries with
id < 0 (or a non-finite score) BEFORE gathering, since -1 wraps to the last
row in jnp / numpy indexing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


# --------------------------------------------------------------------- #
# fused exact-kNN kernel (ROADMAP item 2a: "finish the roofline climb")
#
# One kernel for every serving shape (any segment size, any batch width):
# blockwise [b_tile, d] x [tile, d] distance tiles on the MXU with a running
# per-query top-R pool in VMEM scratch — the PR 13 ADC kernel's pool
# idiom (threshold early-exit + carried-entries-first merge), so only
# [B, R] winners ever reach HBM. Three score precisions:
#
#   fp32  MXU at HIGHEST (six-pass) — bitwise the serving score space,
#         R = k, no rescore.
#   bf16  operands cast to bf16, f32 accumulate — one MXU pass, ~2x
#         matmul throughput; pool widened to R = 4k and exact-rescored.
#   int8  symmetric per-tensor quantization, int8 x int8 -> int32 on the
#         MXU (4x throughput) + scalar dequant; R = 4k + exact rescore.
#
# Reduced precisions only approximate the SCAN; the returned top-k is
# always exact-fp32-rescored, so score values stay in the serving score
# space at every precision (the ANNS-AMP split from PR 9/13 applied to
# the exact path).
#
# What a launch moves: the [n, d] column once, 8 bytes a row of side
# operands and [B, r] winners out. The side operands (||v||^2 and the float
# valid mask) go in as lane-dense [1, n] rows, the layout both serving
# callers hold them in. As [n, 1] columns a TPU tiles them one value per
# 128-lane row: XLA then wrote 2 x 512 MB of padded copies per launch
# (1.07 GB of temporaries, 2 x 0.82 ms), the kernel fetched 1.5 GB for 0.5
# and relaid both blocks out in VMEM on every grid step (PR 27, call 1).
#
# Device ms a launch from profiler traces, n = 2^20 x 128-d (1M live
# rows), k = 10, TPU v5e (PR 27, chip calls 1-3; "->" from PR 26's tree,
# whose [n, 1] side operands and 1,024-row tile cost the difference):
#
#   fp32  B = 1 (the served launch: 1 row padded to 8)    8.98 -> 0.89
#         B = 8 / 32 / 128            9.42 / 30.9 / 131.3 -> 1.05 / 1.97 / 6.58
#   bf16  B = 8, r = 40 (the kernel's part 13.1 -> 1.98)   16.0 -> 3.22
#   int8  B = 8, r = 40 (the kernel's part 13.2 -> 1.68)   16.5 -> 3.35
#   `_fused_xla_pool`, fp32, B = 8 / 32 / 128: 0.99 / 2.16 / 6.45
#
# 0.89 ms is 512 MB at 590 GB/s, 70% of the v5e's 819 GB/s; with the
# matmul stubbed out the same launch takes 0.73 ms (call 1), so at small B
# the column's DMA sets the pace and the six-pass fp32 matmul hides behind
# it. From B = 32 up the pool merges do: r rounds of max / argmax over
# [b_tile, tile + r], each a chain of dependent reductions, so fewer and
# wider merges are cheaper (B = 128: 10.3 ms at 1,024-row tiles, 6.6 at
# 8,192, call 1). The rest of a reduced-precision launch is
# `_prep_operands` casting the whole column and the rescore's gather.
# --------------------------------------------------------------------- #

FK_BLOCK = 1024   # the column pads to a multiple of this many doc rows
FK_QTILE = 128    # query rows per grid step (one MXU tile)
FK_TILE_BYTES = 4 << 20    # column bytes one grid step streams through VMEM
FK_SCORE_BYTES = 4 << 20   # ... and the most its f32 score tile may take
FUSED_MAX_K = 128          # serving cap: pool merge is O(R) VPU rounds
FUSED_RESCORE_MULT = 4     # reduced-precision pool width multiplier
SCORE_PRECISIONS = ("fp32", "bf16", "int8")


def fused_pool_width(k: int, score_precision: str) -> int:
    """Pool width R carried through the scan. fp32 needs no rescore slack;
    reduced precisions keep a 4x pool (floor 32) so quantization rank
    noise around position k stays inside the exact-rescore candidate set."""
    if score_precision == "fp32":
        return k
    return max(k, min(max(FUSED_RESCORE_MULT * k, 32), 512))


def _check_precision(score_precision: str) -> None:
    if score_precision not in SCORE_PRECISIONS:
        raise ValueError(
            f"unknown score precision [{score_precision}]; "
            f"expected one of {SCORE_PRECISIONS}"
        )


def quantize_symmetric_int8(x: jnp.ndarray):
    """Per-tensor symmetric int8: scale = max|x| / 127 (zero-guarded).
    Returns (q int8, scale f32 scalar) with x ~= q * scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _prep_operands(vectors, queries, score_precision: str):
    """Cast/quantize the matmul operands once, OUTSIDE the kernel, so the
    pallas scan and the XLA reference consume bit-identical inputs.
    Returns (v_x, q_x, scale) where dots_f32 = dot(q_x, v_x) * scale
    (scale folds both quantization scales; 1.0 for fp32/bf16)."""
    if score_precision == "int8":
        v_x, sv = quantize_symmetric_int8(vectors)
        q_x, sq = quantize_symmetric_int8(queries)
        return v_x, q_x, sq * sv
    if score_precision == "bf16":
        return (vectors.astype(jnp.bfloat16), queries.astype(jnp.bfloat16),
                jnp.float32(1.0))
    return vectors, queries, jnp.float32(1.0)


def _fused_dots(q_x, v_x, score_precision: str, scale):
    """[B, d] x [n, d] -> [B, n] f32 dots under the chosen scan precision.
    int8 contracts exactly in int32 (sums bounded far below 2^31) then
    dequantizes with one scalar multiply; bf16 accumulates in f32; fp32
    runs HIGHEST so the scan is bitwise the serving score space."""
    dn = (((1,), (1,)), ((), ()))
    if score_precision == "int8":
        dots = jax.lax.dot_general(
            q_x, v_x, dn, preferred_element_type=jnp.int32
        )
        return dots.astype(jnp.float32) * scale
    if score_precision == "bf16":
        return jax.lax.dot_general(
            q_x, v_x, dn, preferred_element_type=jnp.float32
        )
    return jax.lax.dot_general(
        q_x, v_x, dn, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _transform_scores(dots, qsq, nsq, similarity: str):
    """OpenSearch k-NN score-space transforms (identical math to ops/knn's
    dense per-document scorer; shared so pallas/XLA/rescore agree bitwise).
    qsq broadcasts as [B, 1], nsq as [1, n] or [B, n]."""
    if similarity == "l2_norm":
        d_sq = jnp.maximum(qsq - 2.0 * dots + nsq, 0.0)
        return 1.0 / (1.0 + d_sq)
    if similarity == "cosine":
        q_norm = jnp.sqrt(jnp.maximum(qsq, 1e-24))
        v_norm = jnp.sqrt(jnp.maximum(nsq, 1e-24))
        return (1.0 + dots / (q_norm * v_norm)) / 2.0
    return jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))


def fused_tile(n_pad: int, d: int, itemsize: int, b_tile: int) -> int:
    """Doc rows one grid step of the fused scan streams, scores and merges:
    FK_TILE_BYTES of the [n_pad, d] column as VMEM holds it (the minor dim
    in whole 128-lane tiles), cut so the [b_tile, tile] f32 score tile
    stays within FK_SCORE_BYTES, as a power of two that divides `n_pad` (a
    multiple of FK_BLOCK) — so a power-of-two column, which is what both
    serving callers hold, is never padded to fit. Read from the operands
    and from nothing a user sets."""
    row_bytes = -(-d // 128) * 128 * itemsize
    rows = min(FK_TILE_BYTES // row_bytes, FK_SCORE_BYTES // (4 * b_tile))
    rows = 1 << (max(rows, FK_BLOCK).bit_length() - 1)    # a power of two
    return min(rows, n_pad & -n_pad)    # n & -n: the power of two in n


def _knn_fused_kernel(
    q_ref,        # [b_tile, d] f32/bf16/int8 (prepped)
    qsq_ref,      # [b_tile, 1] f32 (always from the ORIGINAL f32 queries)
    v_ref,        # [tile, d] tile, same dtype as q_ref
    nsq_ref,      # [1, tile] f32
    valid_ref,    # [1, tile] f32
    scale_ref,    # [1, 1] f32 dequant scale
    vals_out,     # [b_tile, r] f32
    ids_out,      # [b_tile, r] i32
    vals_scr,     # scratch [b_tile, r] f32 — pool persists across doc blocks
    ids_scr,      # scratch [b_tile, r] i32
    *,
    r: int,
    similarity: str,
    score_precision: str,
    n_blocks: int,
):
    i = pl.program_id(1)   # doc-block index — INNERMOST, iterates fastest,
    #                        so the scratch pool is per-query-tile coherent
    B = q_ref.shape[0]
    bs = v_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        vals_scr[:] = jnp.full((B, r), _NEG_INF)
        ids_scr[:] = jnp.full((B, r), -1, jnp.int32)

    dots = _fused_dots(q_ref[:], v_ref[:], score_precision, scale_ref[0, 0])
    scores = _transform_scores(dots, qsq_ref[:], nsq_ref[:], similarity)
    scores = jnp.where(valid_ref[:] > 0.5, scores, _NEG_INF)

    # threshold early-exit: merge only when some row's tile-best beats its
    # current Rth-best (O(R log n_blocks) merges on a scanned corpus)
    kth_best = vals_scr[:, r - 1]
    improves = jnp.any(jnp.max(scores, axis=1) > kth_best)

    @pl.when(improves)
    def _merge():
        # carried entries FIRST: argmax takes the first maximum, so score
        # ties keep the earlier (lower doc id) entry — lax.top_k tie-break
        block_ids = i * bs + jax.lax.broadcasted_iota(jnp.int32, (B, bs), 1)
        ext_vals = jnp.concatenate([vals_scr[:], scores], axis=1)
        ext_ids = jnp.concatenate([ids_scr[:], block_ids], axis=1)
        width = bs + r
        col = jax.lax.broadcasted_iota(jnp.int32, (B, width), 1)
        colr = jax.lax.broadcasted_iota(jnp.int32, (B, r), 1)

        def select_one(j, carry):
            ext, acc_v, acc_i = carry
            best = jnp.max(ext, axis=1, keepdims=True)
            arg = jnp.argmax(ext, axis=1).astype(jnp.int32)
            onehot = col == arg[:, None]
            best_id = jnp.sum(
                jnp.where(onehot, ext_ids, 0), axis=1, keepdims=True
            )
            best_id = jnp.where(best > _NEG_INF, best_id, -1)
            sel = colr == j
            acc_v = jnp.where(sel, best, acc_v)
            acc_i = jnp.where(sel, best_id, acc_i)
            return jnp.where(onehot, _NEG_INF, ext), acc_v, acc_i

        _, acc_v, acc_i = jax.lax.fori_loop(
            0, r, select_one,
            (ext_vals,
             jnp.full((B, r), _NEG_INF, jnp.float32),
             jnp.full((B, r), -1, jnp.int32)),
        )
        vals_scr[:] = acc_v
        ids_scr[:] = acc_i

    @pl.when(i == n_blocks - 1)
    def _emit():
        vals_out[:] = vals_scr[:]
        ids_out[:] = ids_scr[:]


@functools.partial(
    jax.jit,
    static_argnames=("r", "similarity", "score_precision", "interpret"),
)
def pallas_knn_fused(
    v_x: jnp.ndarray,        # [n_pad, d] prepped operand, n_pad % FK_BLOCK == 0
    norms_sq: jnp.ndarray,   # [n_pad] f32 (from the ORIGINAL f32 vectors)
    valid: jnp.ndarray,      # [n_pad] bool
    q_x: jnp.ndarray,        # [B, d] prepped operand, B % b_tile == 0
    qsq: jnp.ndarray,        # [B, 1] f32 (from the ORIGINAL f32 queries)
    scale: jnp.ndarray,      # f32 scalar dequant scale
    *,
    r: int,
    similarity: str = "l2_norm",
    score_precision: str = "fp32",
    interpret: bool = False,
):
    """Raw pool scan: (pool_scores [B, r], pool_ids [B, r]), slots past the
    valid-doc count carry (-inf, -1). Operands come pre-prepped from
    `_prep_operands` so this and `_fused_xla_pool` see identical bits;
    use `knn_fused` for the end-to-end contract."""
    n, d = v_x.shape
    B = q_x.shape[0]
    assert n % FK_BLOCK == 0, f"n [{n}] must be a multiple of {FK_BLOCK}"
    b_tile = min(FK_QTILE, B)
    assert B % b_tile == 0, f"B [{B}] must be a multiple of {b_tile}"
    tile = fused_tile(n, d, v_x.dtype.itemsize, b_tile)
    n_blocks = n // tile
    kernel = functools.partial(
        _knn_fused_kernel, r=r, similarity=similarity,
        score_precision=score_precision, n_blocks=n_blocks,
    )
    vals, ids = pl.pallas_call(
        kernel,
        # query tiles outer, doc blocks INNER: the per-query-tile pool in
        # VMEM scratch survives exactly one full doc sweep
        grid=(B // b_tile, n_blocks),
        in_specs=[
            pl.BlockSpec((b_tile, d), lambda j, i: (j, 0)),
            pl.BlockSpec((b_tile, 1), lambda j, i: (j, 0)),
            pl.BlockSpec((tile, d), lambda j, i: (i, 0)),
            # side operands as [1, n] rows, never [n, 1] (see above)
            pl.BlockSpec((1, tile), lambda j, i: (0, i)),
            pl.BlockSpec((1, tile), lambda j, i: (0, i)),
            pl.BlockSpec((1, 1), lambda j, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b_tile, r), lambda j, i: (j, 0)),
            pl.BlockSpec((b_tile, r), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, r), jnp.float32),
            jax.ShapeDtypeStruct((B, r), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b_tile, r), jnp.float32),
            pltpu.VMEM((b_tile, r), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="pallas_knn_fused",
    )(
        q_x,
        qsq,
        v_x,
        norms_sq.reshape(1, -1),
        valid.astype(jnp.float32).reshape(1, -1),
        scale.reshape(1, 1),
    )
    return vals, ids


def _fused_xla_pool(v_x, norms_sq, valid, q_x, qsq, scale, *,
                    r, similarity, score_precision):
    """XLA reference for the pool scan: full [B, n] scores + lax.top_k.
    Elementwise identical math to the kernel (same `_fused_dots` /
    `_transform_scores` on the same prepped operands); the d-contraction
    is never tiled in either impl, so dots agree bitwise."""
    dots = _fused_dots(q_x, v_x, score_precision, scale)
    scores = _transform_scores(dots, qsq, norms_sq[None, :], similarity)
    scores = jnp.where(valid[None, :], scores, _NEG_INF)
    vals, ids = jax.lax.top_k(scores, r)
    ids = jnp.where(vals > _NEG_INF, ids, -1)
    return vals, ids


def _fused_rescore(queries, vectors, norms_sq, valid, cand, *,
                   k, similarity):
    """Exact fp32 HIGHEST rescore of pool candidates [B, R] -> top-k.
    Score ties keep pool order (scan-score rank), like the ADC rescore."""
    cand_safe = jnp.maximum(cand, 0)
    cvec = vectors[cand_safe]                          # [B, R, d]
    dots = jnp.einsum("bd,brd->br", queries, cvec,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    scores = _transform_scores(dots, qsq, norms_sq[cand_safe], similarity)
    ok = (cand >= 0) & valid[cand_safe]
    scores = jnp.where(ok, scores, _NEG_INF)
    vals, pos = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids


@functools.partial(
    jax.jit,
    static_argnames=("k", "similarity", "score_precision", "impl",
                     "interpret"),
)
def knn_fused(
    vectors: jnp.ndarray,    # [n, d] f32 (any n)
    norms_sq: jnp.ndarray,   # [n] f32
    valid: jnp.ndarray,      # [n] bool
    queries: jnp.ndarray,    # [B, d] f32 (any B)
    *,
    k: int,
    similarity: str = "l2_norm",
    score_precision: str = "fp32",
    impl: str = "pallas",
    interpret: bool = False,
):
    """End-to-end fused exact kNN: pad -> prep operands -> pool scan
    (pallas kernel or the bit-compatible XLA reference, per `impl`) ->
    exact fp32 rescore for reduced precisions. Returns (scores [B, k],
    ids [B, k]) with (-inf, -1) past the valid-doc count; scores are in
    the serving fp32 score space at EVERY precision."""
    _check_precision(score_precision)
    n, d = vectors.shape
    B = queries.shape[0]
    n_pad = -(-n // FK_BLOCK) * FK_BLOCK
    if B <= FK_QTILE:
        b_pad = max(8, -(-B // 8) * 8)
    else:
        b_pad = -(-B // FK_QTILE) * FK_QTILE
    if n_pad != n:
        vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
        norms_sq = jnp.pad(norms_sq, (0, n_pad - n))
        valid = jnp.pad(valid, (0, n_pad - n))
    if b_pad != B:
        queries = jnp.pad(queries, ((0, b_pad - B), (0, 0)))

    k_eff = min(k, n_pad)
    r = min(fused_pool_width(k_eff, score_precision), n_pad)
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    v_x, q_x, scale = _prep_operands(vectors, queries, score_precision)
    if impl == "pallas":
        pv, pi = pallas_knn_fused(
            v_x, norms_sq, valid, q_x, qsq, scale,
            r=r, similarity=similarity, score_precision=score_precision,
            interpret=interpret,
        )
    else:
        pv, pi = _fused_xla_pool(
            v_x, norms_sq, valid, q_x, qsq, scale,
            r=r, similarity=similarity, score_precision=score_precision,
        )
    if score_precision == "fp32":
        vals, ids = pv[:, :k_eff], pi[:, :k_eff]
    else:
        vals, ids = _fused_rescore(
            queries, vectors, norms_sq, valid, pi,
            k=k_eff, similarity=similarity,
        )
    if k_eff < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - k_eff)),
                       constant_values=_NEG_INF)
        ids = jnp.pad(ids, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return vals[:B], ids[:B]


def fused_impl(policy: str, k: int) -> tuple[str, bool]:
    """The one rule: which lowering of `knn_fused` a launch of pool size `k`
    runs under `search.knn.kernel` = `policy`, as (impl, interpret). The
    kernel when the policy forces it or is "auto" on a TPU, AND k <=
    FUSED_MAX_K (its pool merge is O(R) VPU rounds); else the XLA twin.
    `interpret` only for a forced "pallas" on the CPU backend (the tests'
    parity path). Callers carry the pair on their batch / program key, so a
    live policy flip starts new batches and never re-ranks one in flight."""
    platform = jax.devices()[0].platform
    wanted = policy == "pallas" or (policy != "xla" and platform == "tpu")
    if not wanted or k > FUSED_MAX_K:
        return "xla", False
    return "pallas", platform == "cpu"
