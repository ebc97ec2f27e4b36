"""Pallas TPU kernel: blockwise exact-kNN scan with running top-k.

The flagship hot loop (ContextIndexSearcher.search + TopScoreDocCollector,
SURVEY.md §3.2 ★★) as a hand-scheduled TPU kernel. The XLA path
(ops/fused.knn_topk) materializes the full [B, n] score matrix in HBM
before lax.top_k; this kernel instead streams the corpus through VMEM in
[BLOCK, d] tiles (grid iterations are sequential on a TensorCore, so VMEM
scratch persists across them — the standard accumulation pattern,
/opt/skills/guides/pallas_guide.md "Grid and Block Specifications") and
keeps only a running [B, K] top-k:

  per tile:  scores = q @ tile.T on the MXU -> l2/cosine/dot transform
             ext    = concat(scores, running_vals)          [B, BLOCK+K]
             K x    (row max, one-hot argmax select, mask out)  on the VPU
  HBM traffic: n*d tile reads once; no [B, n] intermediate.

Top-k selection avoids lax.top_k/sort (not Mosaic-lowerable) by K rounds
of max/argmax with iota-equality one-hot gathers — K is small (<= 64).

interpret=True is the CPU tests' parity path and nothing else: the `*_auto`
wrappers turn it on only when the backend IS the CPU, so any accelerator
compiles the kernel (or fails in lowering) instead of silently interpreting.
The shape/dtype contract matches fused.knn_topk, except that slots past
the valid-doc count carry id -1 (explicit, vs fused's arbitrary masked
indices) — see pallas_knn_topk's docstring.

This running-top-k kernel's niche is bounded-memory scans where the XLA
path's [B, n] score matrix does NOT fit (B x n >= HBM budget, e.g. B=1024
over 100M docs = 400GB of scores): it is O(B k) resident instead of O(B n),
the blockwise-tiling pattern SURVEY.md §5 "long-context" calls for. It and
the two variants below it have no serving caller and no timing on today's
code; `pallas_knn_fused` is the served kernel, and its timings stand in its
own section below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from opensearch_tpu.search.profile import profiled_kernel

BLOCK = 1024
_NEG_INF = float("-inf")


def _knn_block_kernel(
    q_ref,        # [B, d] f32 (VMEM, full)
    qsq_ref,      # [B, 1] f32 precomputed ||q||^2
    v_ref,        # [BLOCK, d] f32 (VMEM, one tile)
    nsq_ref,      # [BLOCK, 1] f32 ||v||^2
    valid_ref,    # [BLOCK, 1] f32 (1.0 live / 0.0 dead; bool tiles are awkward)
    vals_out,     # [B, K] f32
    ids_out,      # [B, K] i32
    vals_scr,     # scratch [B, K] f32
    ids_scr,      # scratch [B, K] i32
    *,
    k: int,
    similarity: str,
    n_blocks: int,
):
    pi = pl.program_id(0)
    B = q_ref.shape[0]

    @pl.when(pi == 0)
    def _init():
        vals_scr[:] = jnp.full((B, k), _NEG_INF)
        ids_scr[:] = jnp.full((B, k), -1, jnp.int32)

    q = q_ref[:]
    v = v_ref[:]
    dots = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # [B, BLOCK]
    nsq = nsq_ref[:].reshape(1, -1)                    # [1, BLOCK]
    if similarity == "l2_norm":
        d_sq = jnp.maximum(qsq_ref[:] - 2.0 * dots + nsq, 0.0)
        scores = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(jnp.maximum(qsq_ref[:], 1e-24))
        v_norm = jnp.sqrt(jnp.maximum(nsq, 1e-24))
        scores = (1.0 + dots / (q_norm * v_norm)) / 2.0
    else:  # dot_product
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    live = valid_ref[:].reshape(1, -1) > 0.5
    scores = jnp.where(live, scores, _NEG_INF)

    base = pi * BLOCK
    block_ids = base + jax.lax.broadcasted_iota(jnp.int32, (B, BLOCK), 1)

    # threshold early-exit (the BottomSortValuesCollector trick,
    # SURVEY.md §2.5 "cross-shard early termination"): the expensive K-round
    # merge only runs when this tile holds a score beating some row's
    # current kth-best — for a scanned corpus that is O(B k log n_blocks)
    # tiles, so the steady-state per-tile cost is one matmul + one row-max
    kth_best = vals_scr[:, k - 1]                                # [B]
    improves = jnp.any(jnp.max(scores, axis=1) > kth_best)

    @pl.when(improves)
    def _merge():
        # carried entries FIRST: argmax takes the first maximum, so on
        # score ties the earlier (lower doc id) entry wins — the
        # lax.top_k / Lucene doc-id-ascending tie-break the reduce relies on
        ext_vals = jnp.concatenate([vals_scr[:], scores], axis=1)
        ext_ids = jnp.concatenate([ids_scr[:], block_ids], axis=1)
        width = BLOCK + k
        col = jax.lax.broadcasted_iota(jnp.int32, (B, width), 1)
        colk = jax.lax.broadcasted_iota(jnp.int32, (B, k), 1)

        # K rounds of extract-max via fori_loop (NOT a Python unroll) so
        # Mosaic reuses one set of [B, width] buffers. The [B, K]
        # accumulators ride the loop carry (dynamic lane-offset stores are
        # not Mosaic-lowerable) and land in scratch once at the end.
        def select_one(i, carry):
            ext, acc_v, acc_i = carry
            best = jnp.max(ext, axis=1, keepdims=True)           # [B, 1]
            arg = jnp.argmax(ext, axis=1).astype(jnp.int32)      # [B]
            onehot = col == arg[:, None]
            best_id = jnp.sum(
                jnp.where(onehot, ext_ids, 0), axis=1, keepdims=True
            )
            # a -inf row yields id -1 (padding), matching fused.knn_topk
            best_id = jnp.where(best > _NEG_INF, best_id, -1)
            sel = colk == i
            acc_v = jnp.where(sel, best, acc_v)
            acc_i = jnp.where(sel, best_id, acc_i)
            return jnp.where(onehot, _NEG_INF, ext), acc_v, acc_i

        _, acc_v, acc_i = jax.lax.fori_loop(
            0, k, select_one,
            (ext_vals,
             jnp.full((B, k), _NEG_INF, jnp.float32),
             jnp.full((B, k), -1, jnp.int32)),
        )
        vals_scr[:] = acc_v
        ids_scr[:] = acc_i

    @pl.when(pi == n_blocks - 1)
    def _emit():
        vals_out[:] = vals_scr[:]
        ids_out[:] = ids_scr[:]


@functools.partial(
    jax.jit, static_argnames=("k", "similarity", "interpret")
)
def pallas_knn_topk(
    vectors: jnp.ndarray,    # [n_pad, d] f32, n_pad % BLOCK == 0
    norms_sq: jnp.ndarray,   # [n_pad]
    valid: jnp.ndarray,      # [n_pad] bool
    queries: jnp.ndarray,    # [B, d] f32, B % 8 == 0 preferred
    *,
    k: int,
    similarity: str = "l2_norm",
    interpret: bool = False,
):
    """Returns (scores [B, k], ids [B, k]).

    When fewer than k docs are valid, trailing entries are (-inf, -1) —
    NOTE this differs from fused.knn_topk, which returns arbitrary masked
    indices with -inf scores: callers must drop entries with id < 0 (or
    non-finite score) BEFORE gathering, since -1 wraps to the last row in
    jnp/numpy indexing. Callers pad n to a BLOCK multiple (pad rows
    valid=False) and B to a sublane multiple; `knn_topk_auto` does both.
    """
    n, d = vectors.shape
    B = queries.shape[0]
    assert n % BLOCK == 0, f"n [{n}] must be a multiple of {BLOCK}"
    n_blocks = n // BLOCK
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    kernel = functools.partial(
        _knn_block_kernel, k=k, similarity=similarity, n_blocks=n_blocks
    )
    vals, ids = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((B, d), lambda i: (0, 0)),          # queries
            pl.BlockSpec((B, 1), lambda i: (0, 0)),          # ||q||^2
            pl.BlockSpec((BLOCK, d), lambda i: (i, 0)),      # vector tile
            pl.BlockSpec((BLOCK, 1), lambda i: (i, 0)),      # ||v||^2 tile
            pl.BlockSpec((BLOCK, 1), lambda i: (i, 0)),      # valid tile
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda i: (0, 0)),
            pl.BlockSpec((B, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, k), jnp.float32),
            pltpu.VMEM((B, k), jnp.int32),
        ],
        # the K-round selection keeps several [B, BLOCK+K] temporaries live
        # (Mosaic unrolls short fori_loops); raise the scoped-VMEM cap well
        # past the default 16M — v5e has 128M physical VMEM per core
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        queries,
        qsq,
        vectors,
        norms_sq.reshape(-1, 1),
        valid.astype(jnp.float32).reshape(-1, 1),
    )
    return vals, ids


# --------------------------------------------------------------------- #
# per-block top-k kernel (the fast path)
#
# The running-top-k kernel above merges [B, BLOCK+K] state on EVERY tile —
# measured 86ms on v5e-1 for 1M x 128d. This kernel instead computes an
# INDEPENDENT exact top-k per (query, doc-block) entirely in VMEM — top-k
# of the union of per-block top-ks is the global top-k, so a tiny second
# stage (lax.top_k over [B, nb*k]) finishes the job. HBM traffic: the
# vector tiles once + [B, nb, k] winners out; the [B, n] score matrix
# never exists.
# --------------------------------------------------------------------- #

PB_BLOCK = 2048
PB_QTILE = 128


def _knn_pb_kernel(
    q_ref,        # [B_TILE, d] f32
    qsq_ref,      # [B_TILE, 1] f32
    v_ref,        # [PB_BLOCK, d] f32 tile
    nsq_ref,      # [PB_BLOCK, 1] f32 tile
    valid_ref,    # [PB_BLOCK, 1] f32 tile
    vals_out,     # [1, B_TILE, K] f32 (this block's slot)
    ids_out,      # [1, B_TILE, K] i32
    s_scr,        # scratch [B_TILE, PB_BLOCK] f32
    *,
    k: int,
    similarity: str,
    precision,
):
    B = q_ref.shape[0]
    bs = v_ref.shape[0]
    dots = jax.lax.dot_general(
        q_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )                                                   # [B, bs] in VMEM
    nsq = nsq_ref[:].reshape(1, -1)
    if similarity == "l2_norm":
        d_sq = jnp.maximum(qsq_ref[:] - 2.0 * dots + nsq, 0.0)
        scores = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(jnp.maximum(qsq_ref[:], 1e-24))
        v_norm = jnp.sqrt(jnp.maximum(nsq, 1e-24))
        scores = (1.0 + dots / (q_norm * v_norm)) / 2.0
    else:
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    scores = jnp.where(valid_ref[:].reshape(1, -1) > 0.5, scores, _NEG_INF)
    s_scr[:] = scores

    base = pl.program_id(1) * bs
    colk = jax.lax.broadcasted_iota(jnp.int32, (B, k), 1)
    # k extract-max rounds through VMEM SCRATCH (loads/stores through the
    # ref, one round live at a time — an SSA-carried loop spills hundreds
    # of MB of registers at these widths). Static round index i lets each
    # round target its own output lane.
    acc_v = jnp.full((B, k), _NEG_INF, jnp.float32)
    acc_i = jnp.full((B, k), -1, jnp.int32)
    for i in range(k):
        s = s_scr[:]
        best = jnp.max(s, axis=1, keepdims=True)             # [B, 1]
        arg = jnp.argmax(s, axis=1).astype(jnp.int32)        # [B]
        col = jax.lax.broadcasted_iota(jnp.int32, (B, bs), 1)
        sel = colk == i
        acc_v = jnp.where(sel, best, acc_v)
        acc_i = jnp.where(sel, arg[:, None] + base, acc_i)
        s_scr[:] = jnp.where(col == arg[:, None], _NEG_INF, s)
    vals_out[0, :, :] = acc_v
    ids_out[0, :, :] = acc_i


@functools.partial(
    jax.jit, static_argnames=("k", "similarity", "interpret", "exact")
)
def pallas_knn_blocktopk(
    vectors: jnp.ndarray,    # [n_pad, d] f32, n_pad % PB_BLOCK == 0
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,
    queries: jnp.ndarray,    # [B, d], B % 8 == 0
    *,
    k: int,
    similarity: str = "l2_norm",
    interpret: bool = False,
    exact: bool = True,
):
    """(scores [B, k], ids [B, k]) — exact incl. doc-id tie-break: per-block
    argmax-first picks the lowest doc id among ties, the final merge's
    lax.top_k picks the lowest (block, rank) position, and positions are
    block-major so lower doc ids win. `exact=True` runs the scoring matmul
    at HIGHEST precision (fp32-faithful on the MXU)."""
    n, d = vectors.shape
    B = queries.shape[0]
    assert n % PB_BLOCK == 0, f"n [{n}] must be a multiple of {PB_BLOCK}"
    nb = n // PB_BLOCK
    b_tile = min(PB_QTILE, B)
    assert B % b_tile == 0, f"B [{B}] must be a multiple of {b_tile}"
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    precision = (jax.lax.Precision.HIGHEST if exact
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(
        _knn_pb_kernel, k=k, similarity=similarity, precision=precision
    )
    # 2D grid (query tiles x doc blocks): bounds the VMEM working set
    # ([b_tile, PB_BLOCK] scores + selection temporaries) so Mosaic's
    # register allocator never spills
    vals, ids = pl.pallas_call(
        kernel,
        grid=(B // b_tile, nb),
        in_specs=[
            pl.BlockSpec((b_tile, d), lambda j, i: (j, 0)),
            pl.BlockSpec((b_tile, 1), lambda j, i: (j, 0)),
            pl.BlockSpec((PB_BLOCK, d), lambda j, i: (i, 0)),
            pl.BlockSpec((PB_BLOCK, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((PB_BLOCK, 1), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, b_tile, k), lambda j, i: (i, j, 0)),
            pl.BlockSpec((1, b_tile, k), lambda j, i: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, B, k), jnp.float32),
            jax.ShapeDtypeStruct((nb, B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b_tile, PB_BLOCK), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        queries, qsq, vectors,
        norms_sq.reshape(-1, 1),
        valid.astype(jnp.float32).reshape(-1, 1),
    )
    # stage 2: tiny merge over [B, nb*k] (block-major position order)
    fv = jnp.transpose(vals, (1, 0, 2)).reshape(B, nb * k)
    fi = jnp.transpose(ids, (1, 0, 2)).reshape(B, nb * k)
    top_vals, pos = jax.lax.top_k(fv, k)
    top_ids = jnp.take_along_axis(fi, pos, axis=1)
    # all--inf rows keep id -1 (matching pallas_knn_topk's contract)
    top_ids = jnp.where(jnp.isfinite(top_vals), top_ids, -1)
    return top_vals, top_ids


# --------------------------------------------------------------------- #
# sub-block-max kernel + XLA rescore (the streaming fast path)
#
# The per-block top-k kernel above needs k unrolled argmax rounds in VMEM,
# which Mosaic compiles slowly and spills at large widths. This path keeps
# the kernel TRIVIAL: score a [B_TILE, PB_BLOCK] tile in VMEM and emit only
# the max of every 128-doc sub-block — no loops, no selection. Selection
# moves to XLA over the tiny [B, n/128] maxima array: the k sub-blocks
# with the largest maxima provably contain every global top-k doc (the
# block-max pruning argument), so an exact fp32 rescore of those k*128
# candidate docs finishes the job. HBM traffic: vectors once + [B, n/128]
# maxima + a [B, k*128, d] candidate gather — the [B, n] score matrix
# never exists.
# --------------------------------------------------------------------- #

SUB = 128  # sub-block width (one lane tile)


def _knn_sbmax_kernel(
    q_ref,        # [B_TILE, d]
    qsq_ref,      # [B_TILE, 1]
    v_ref,        # [PB_BLOCK, d]
    nsq_ref,      # [PB_BLOCK, 1]
    valid_ref,    # [PB_BLOCK, 1]
    out_ref,      # [1, B_TILE, PB_BLOCK // SUB]
    *,
    similarity: str,
    precision,
):
    B = q_ref.shape[0]
    bs = v_ref.shape[0]
    dots = jax.lax.dot_general(
        q_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )
    nsq = nsq_ref[:].reshape(1, -1)
    if similarity == "l2_norm":
        d_sq = jnp.maximum(qsq_ref[:] - 2.0 * dots + nsq, 0.0)
        scores = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(jnp.maximum(qsq_ref[:], 1e-24))
        v_norm = jnp.sqrt(jnp.maximum(nsq, 1e-24))
        scores = (1.0 + dots / (q_norm * v_norm)) / 2.0
    else:
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    scores = jnp.where(valid_ref[:].reshape(1, -1) > 0.5, scores, _NEG_INF)
    out_ref[0, :, :] = jnp.max(
        scores.reshape(B, bs // SUB, SUB), axis=-1
    )


@functools.partial(
    jax.jit, static_argnames=("k", "similarity", "interpret", "exact")
)
def pallas_knn_sbmax_topk(
    vectors: jnp.ndarray,    # [n_pad, d], n_pad % PB_BLOCK == 0
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,
    queries: jnp.ndarray,    # [B, d]
    *,
    k: int,
    similarity: str = "l2_norm",
    interpret: bool = False,
    exact: bool = True,
):
    """(scores [B, k], ids [B, k]) — exact incl. doc-id tie-break (chosen
    sub-blocks sorted ascending => candidate positions are doc-id-major)."""
    n, d = vectors.shape
    B = queries.shape[0]
    assert n % PB_BLOCK == 0
    nb = n // PB_BLOCK
    subs_per_block = PB_BLOCK // SUB
    b_tile = min(PB_QTILE, B)
    assert B % b_tile == 0
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    precision = (jax.lax.Precision.HIGHEST if exact
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(
        _knn_sbmax_kernel, similarity=similarity, precision=precision
    )
    submax = pl.pallas_call(
        kernel,
        grid=(B // b_tile, nb),
        in_specs=[
            pl.BlockSpec((b_tile, d), lambda j, i: (j, 0)),
            pl.BlockSpec((b_tile, 1), lambda j, i: (j, 0)),
            pl.BlockSpec((PB_BLOCK, d), lambda j, i: (i, 0)),
            pl.BlockSpec((PB_BLOCK, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((PB_BLOCK, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, b_tile, subs_per_block),
                               lambda j, i: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, B, subs_per_block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        queries, qsq, vectors,
        norms_sq.reshape(-1, 1),
        valid.astype(jnp.float32).reshape(-1, 1),
    )
    # [nb, B, subs] -> [B, n_sub] in doc order
    n_sub = nb * subs_per_block
    flat = jnp.transpose(submax, (1, 0, 2)).reshape(B, n_sub)

    # the k sub-blocks with the largest maxima contain every top-k doc
    _, sb_ids = jax.lax.top_k(flat, k)
    sb_ids = jnp.sort(sb_ids, axis=1)                  # doc-id-major order
    cand = sb_ids[:, :, None] * SUB + jnp.arange(SUB)[None, None, :]
    cand = cand.reshape(B, k * SUB)                    # [B, k*SUB] doc ids

    # exact fp32 rescore of the candidates only
    cvec = vectors[cand]                               # [B, k*SUB, d]
    cnrm = norms_sq[cand]
    cok = valid[cand]
    dots = jnp.einsum("bd,bcd->bc", queries, cvec,
                      preferred_element_type=jnp.float32,
                      precision=precision)
    if similarity == "l2_norm":
        d_sq = jnp.maximum(qsq - 2.0 * dots + cnrm, 0.0)
        scores = 1.0 / (1.0 + d_sq)
    elif similarity == "cosine":
        q_norm = jnp.sqrt(jnp.maximum(qsq, 1e-24))
        v_norm = jnp.sqrt(jnp.maximum(cnrm, 1e-24))
        scores = (1.0 + dots / (q_norm * v_norm)) / 2.0
    else:
        scores = jnp.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    scores = jnp.where(cok, scores, _NEG_INF)
    vals, pos = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids


def knn_sbmax_auto(vectors, norms_sq, valid, queries, *, k: int,
                   similarity: str = "l2_norm", exact: bool = True):
    """Pad-and-dispatch wrapper for the sub-block-max streaming path."""
    n = vectors.shape[0]
    B = queries.shape[0]
    n_pad = -(-n // PB_BLOCK) * PB_BLOCK
    if B <= PB_QTILE:
        b_pad = max(8, -(-B // 8) * 8)
    else:
        b_pad = -(-B // PB_QTILE) * PB_QTILE
    if n_pad != n:
        vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
        norms_sq = jnp.pad(norms_sq, (0, n_pad - n))
        valid = jnp.pad(valid, (0, n_pad - n))
    if b_pad != B:
        queries = jnp.pad(queries, ((0, b_pad - B), (0, 0)))
    interpret = jax.devices()[0].platform == "cpu"
    vals, ids = pallas_knn_sbmax_topk(
        vectors, norms_sq, valid, queries,
        k=k, similarity=similarity, interpret=interpret, exact=exact,
    )
    return vals[:B], ids[:B]


def knn_blocktopk_auto(vectors, norms_sq, valid, queries, *, k: int,
                       similarity: str = "l2_norm", exact: bool = True):
    """Pad-and-dispatch wrapper for the per-block kernel."""
    n = vectors.shape[0]
    B = queries.shape[0]
    n_pad = -(-n // PB_BLOCK) * PB_BLOCK
    if B <= PB_QTILE:
        b_pad = max(8, -(-B // 8) * 8)
    else:
        b_pad = -(-B // PB_QTILE) * PB_QTILE
    if n_pad != n:
        vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
        norms_sq = jnp.pad(norms_sq, (0, n_pad - n))
        valid = jnp.pad(valid, (0, n_pad - n))
    if b_pad != B:
        queries = jnp.pad(queries, ((0, b_pad - B), (0, 0)))
    interpret = jax.devices()[0].platform == "cpu"
    vals, ids = pallas_knn_blocktopk(
        vectors, norms_sq, valid, queries,
        k=k, similarity=similarity, interpret=interpret, exact=exact,
    )
    return vals[:B], ids[:B]


def knn_topk_auto(vectors, norms_sq, valid, queries, *, k: int,
                  similarity: str = "l2_norm"):
    """Pad-and-dispatch wrapper: compiled pallas, interpret-mode on CPU."""
    n = vectors.shape[0]
    B = queries.shape[0]
    n_pad = -(-n // BLOCK) * BLOCK
    b_pad = max(8, -(-B // 8) * 8)
    if n_pad != n:
        vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
        norms_sq = jnp.pad(norms_sq, (0, n_pad - n))
        valid = jnp.pad(valid, (0, n_pad - n))
    if b_pad != B:
        queries = jnp.pad(queries, ((0, b_pad - B), (0, 0)))
    interpret = jax.devices()[0].platform == "cpu"
    vals, ids = pallas_knn_topk(
        vectors, norms_sq, valid, queries,
        k=k, similarity=similarity, interpret=interpret,
    )
    return vals[:B], ids[:B]


# --------------------------------------------------------------------- #
# fused exact-kNN kernel (ROADMAP item 2a: "finish the roofline climb")
#
# One kernel for BOTH serving shapes (the materializing exact_knn_scores
# path and the streaming knn_topk_streaming path): blockwise
# [b_tile, d] x [FK_BLOCK, d] distance tiles on the MXU with a running
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
    """OpenSearch k-NN score-space transforms (identical math to ops/knn
    and the kernels above; shared so pallas/XLA/rescore agree bitwise).
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
    use `knn_fused` / `knn_fused_auto` for the end-to-end contract."""
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


def knn_fused_shard(vectors, norms_sq, valid, queries, *, k: int,
                    similarity: str = "l2_norm",
                    score_precision: str = "fp32",
                    impl: str = "pallas", interpret: bool = False):
    """Per-shard fused scan for the mesh one-launch-per-node program.
    Traced inside shard_map: no platform read here — the caller
    (distributed.build_knn_serving_step) resolves `interpret` once per
    program build. Same output contract as `knn_fused`."""
    return knn_fused(
        vectors, norms_sq, valid, queries,
        k=k, similarity=similarity, score_precision=score_precision,
        impl=impl, interpret=interpret,
    )


@profiled_kernel("knn_fused_pallas")
def knn_fused_auto(vectors, norms_sq, valid, queries, *, k: int,
                   similarity: str = "l2_norm",
                   score_precision: str = "fp32",
                   impl: str | None = None):
    """Policy front door for the fused exact path (the serving entry the
    dispatch batcher launches). impl None/auto -> pallas on TPU, XLA
    reference elsewhere; "pallas" forces the kernel (interpret-mode only
    when the backend is the CPU, for parity runs); "xla" forces the
    reference."""
    platform = jax.devices()[0].platform
    if impl == "pallas":
        use, interpret = "pallas", platform == "cpu"
    elif impl == "xla":
        use, interpret = "xla", False
    else:
        use, interpret = ("pallas", False) if platform == "tpu" \
            else ("xla", False)
    return knn_fused(
        vectors, norms_sq, valid, queries,
        k=k, similarity=similarity, score_precision=score_precision,
        impl=use, interpret=interpret,
    )
