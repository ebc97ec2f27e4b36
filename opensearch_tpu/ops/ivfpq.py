"""IVF-PQ approximate nearest neighbor: TPU-native build + search.

The ANN slot the reference reserves for the k-NN plugin's FAISS engines
(SURVEY.md §0: EnginePlugin / the separate opensearch-project/k-NN repo's
IVF-PQ path; BASELINE configs #2/#3). Everything heavy runs on device:

- k-means (Lloyd's) as a jitted fori_loop — assignment is a [n, k] matmul
  (MXU), centroid update is segment_sum (VPU). Training uses a host-chosen
  subsample; full-corpus encode streams in fixed chunks via lax.map so the
  [chunk, nlist] distance matrix stays HBM-friendly at 1M+ docs.
- PQ codebooks are trained per subspace on coarse residuals with a single
  vmapped k-means (all m subspaces in one program).
- The built index is a padded, static-shape layout: codes [nlist, L_pad, m]
  uint8 + ids/mask — the TPU analog of FAISS's inverted lists.
- Search is one fused program per (k, nprobe, adc precision) shape: coarse
  top-nprobe, per-probe LUT build ([B, nprobe, m, ks] einsum), ADC
  gather-accumulate, candidate top-R, then an exact fp32 rescore pass over
  gathered full vectors (the FusionANNS-style rerank SURVEY.md §7 calls
  for) ending in jax.lax.top_k. Scores land in the k-NN plugin's score
  space so ANN and exact hits merge comparably.
- ADC accumulation precision is a static knob (ANNS-AMP): "fp32" is the
  reference, "bf16" halves LUT bytes through the gather, "int8" quantizes
  each (query, probe) LUT affinely to uint8 and accumulates in int32.
  Reduced precision only ranks CANDIDATES — the widened rescore pool R
  (``rescore_multiplier``) feeds the exact fp32 rescore, which restores
  score fidelity and recovers recall.

Every built index carries a process-unique ``build_generation``: the
serving tier's batch keys include it so no cross-request batch can ever
merge queries against two different builds of the same column.

Only l2 and cosine are served by ANN (cosine = l2 on unit-normalized
vectors); inner-product falls back to the exact scan upstream.
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from opensearch_tpu.ops import knn as knn_ops

DEFAULT_NLIST = 128
DEFAULT_M = 8
DEFAULT_KS = 256
DEFAULT_NPROBE = 8
# exact-rescore pool width = multiplier * k (floored at 64 candidates)
DEFAULT_RESCORE_MULTIPLIER = 4
# ADC accumulation dtypes the fused search compiles for
ADC_PRECISIONS = ("fp32", "bf16", "int8")
# below this many docs a flat scan beats list overhead; stay exact
MIN_TRAIN_DOCS = 512

# monotonically increasing per-process build ids: rebuilds of the same
# column get a fresh generation, so batch keys never alias across builds
_build_generation = itertools.count(1)


# --------------------------------------------------------------------------
# k-means (device)
# --------------------------------------------------------------------------


def _assign(data: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """[n] int32 nearest-centroid ids (l2). One matmul on the MXU."""
    dots = jnp.einsum(
        "nd,kd->nk", data, centroids, preferred_element_type=jnp.float32
    )
    c_sq = jnp.sum(centroids * centroids, axis=-1)
    # ||x-c||^2 = ||x||^2 - 2 x.c + ||c||^2; ||x||^2 constant per row
    return jnp.argmin(c_sq[None, :] - 2.0 * dots, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(data: jnp.ndarray, init: jnp.ndarray, *, k: int, iters: int = 10):
    """Lloyd's iterations; returns centroids [k, d].

    Empty clusters keep their previous centroid (no re-seeding inside jit —
    callers seed with distinct points, which keeps collapse rare).
    """

    def step(_, centroids):
        assign = _assign(data, centroids)
        sums = jax.ops.segment_sum(data, assign, num_segments=k)
        counts = jax.ops.segment_sum(
            jnp.ones(data.shape[0], jnp.float32), assign, num_segments=k
        )
        fresh = sums / jnp.maximum(counts[:, None], 1.0)
        return jnp.where(counts[:, None] > 0, fresh, centroids)

    return jax.lax.fori_loop(0, iters, step, init)


def _seed_points(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    return rng.choice(n, size=k, replace=n < k)


# --------------------------------------------------------------------------
# training + encoding
# --------------------------------------------------------------------------


@dataclass
class IVFPQParams:
    coarse: jnp.ndarray      # [nlist, d] f32
    codebooks: jnp.ndarray   # [m, ks, dsub] f32 (trained on residuals)
    nlist: int
    m: int
    ks: int
    d: int

    @property
    def dsub(self) -> int:
        return self.d // self.m


@functools.partial(jax.jit, static_argnames=("ks", "iters"))
def _train_pq(residuals_sub: jnp.ndarray, init: jnp.ndarray, *, ks: int, iters: int):
    """vmapped k-means over the m subspaces: [m, n, dsub] -> [m, ks, dsub]."""
    return jax.vmap(lambda data, ini: kmeans(data, ini, k=ks, iters=iters))(
        residuals_sub, init
    )


def train(
    vectors: np.ndarray,
    *,
    nlist: int = DEFAULT_NLIST,
    m: int = DEFAULT_M,
    ks: int = DEFAULT_KS,
    iters: int = 10,
    train_sample: int = 65_536,
    seed: int = 0,
) -> IVFPQParams:
    """Train coarse + PQ codebooks on a subsample (device compute)."""
    n, d = vectors.shape
    if d % m != 0:
        raise ValueError(f"dims [{d}] not divisible by pq m [{m}]")
    ks = min(ks, 256)
    rng = np.random.default_rng(seed)
    # bucket the training-sample row count to a power of two: the kmeans /
    # _train_pq programs are shape-specialized under jit, and index builds
    # happen on the refresh path — raw corpus sizes would compile a fresh
    # training program for every distinct segment size (sampling with
    # replacement when the bucket exceeds n is statistically harmless for
    # Lloyd's iterations)
    want = min(n, train_sample)
    bucket = 1 << (want - 1).bit_length()
    sample_idx = rng.choice(n, size=bucket, replace=bucket > n)
    sample = jnp.asarray(vectors[sample_idx], jnp.float32)

    coarse_init = jnp.asarray(
        vectors[_seed_points(rng, n, nlist)], jnp.float32
    )
    coarse = kmeans(sample, coarse_init, k=nlist, iters=iters)

    assign = _assign(sample, coarse)
    residuals = sample - coarse[assign]
    dsub = d // m
    res_sub = jnp.transpose(
        residuals.reshape(sample.shape[0], m, dsub), (1, 0, 2)
    )  # [m, n_s, dsub]
    pq_seed = _seed_points(rng, int(sample.shape[0]), ks)
    pq_init = res_sub[:, pq_seed, :]  # [m, ks, dsub]
    codebooks = _train_pq(res_sub, pq_init, ks=ks, iters=iters)
    return IVFPQParams(
        coarse=coarse, codebooks=codebooks, nlist=nlist, m=m, ks=ks, d=d
    )


@functools.partial(jax.jit, static_argnames=("m",))
def _encode_chunk(chunk: jnp.ndarray, coarse: jnp.ndarray, codebooks: jnp.ndarray, *, m: int):
    """(list_ids [c], codes [c, m] int32 in [0, ks)) for one chunk of
    vectors. The codes leave the device as int32 and `encode` narrows them
    to uint8 on the host: with the int32 -> uint8 convert inside this
    program, XLA:TPU (libtpu 0.0.34, v5e) returned all-zero codes for half
    the subspaces — the convert fused behind the vmapped argmin is
    miscompiled (PR 21; the CPU backend is not affected)."""
    lists = _assign(chunk, coarse)
    residuals = chunk - coarse[lists]
    dsub = chunk.shape[1] // m
    res_sub = jnp.transpose(residuals.reshape(-1, m, dsub), (1, 0, 2))
    codes = jax.vmap(_assign)(res_sub, codebooks)        # [m, c]
    return lists, jnp.transpose(codes)                    # [c, m]


def encode(vectors: np.ndarray, params: IVFPQParams, *, chunk: int = 65_536):
    """Stream-encode the full corpus: (list_ids [n], codes [n, m]) on host.

    Chunks are padded to power-of-two row counts (outputs sliced off) so
    repeated builds over growing corpora reuse compiled encode programs
    instead of retracing on every ragged tail."""
    n = vectors.shape[0]
    lists_out = np.empty(n, np.int32)
    codes_out = np.empty((n, params.m), np.uint8)
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        rows = hi - lo
        pad = 1 << (rows - 1).bit_length()
        block = np.zeros((pad, vectors.shape[1]), np.float32)
        block[:rows] = vectors[lo:hi]
        l, c = _encode_chunk(
            jnp.asarray(block), params.coarse, params.codebooks, m=params.m,
        )
        lists_out[lo:hi] = np.asarray(l)[:rows]
        codes_out[lo:hi] = np.asarray(c)[:rows]
    return lists_out, codes_out


# --------------------------------------------------------------------------
# index layout (padded inverted lists)
# --------------------------------------------------------------------------


@dataclass
class IVFPQIndex:
    params: IVFPQParams
    codes: jnp.ndarray     # uint8 [nlist, L_pad, m]
    ids: jnp.ndarray       # int32 [nlist, L_pad]  (-1 = padding)
    mask: jnp.ndarray      # bool  [nlist, L_pad]
    l_pad: int
    n: int
    normalized: bool       # True when built for cosine (unit vectors)
    # process-unique id of this build: serving batch keys carry it so a
    # rebuild (refresh / force-merge) can never merge into an old batch
    build_generation: int = 0
    # device-residency ledger handle for this build's slab (freed when the
    # owning segment retires — the engine's retirement path walks it)
    allocation: object | None = None
    # host copies of the coarse centroids (+ precomputed squared norms):
    # the FusionANNS-style cooperative split runs coarse quantization and
    # probe selection host-side (host_probe_select), so the fused-kernel
    # path never pays a device round-trip just to pick its lists
    coarse_host: np.ndarray | None = None
    coarse_sq_host: np.ndarray | None = None

    @property
    def nbytes(self) -> int:
        """Summed device bytes of the slab: packed lists + coarse/PQ
        codebooks (what the residency ledger accounts for this build)."""
        return sum(int(a.nbytes) for a in (
            self.codes, self.ids, self.mask,
            self.params.coarse, self.params.codebooks,
        ))


def build(
    vectors: np.ndarray,
    doc_ids: np.ndarray | None = None,
    *,
    nlist: int = DEFAULT_NLIST,
    m: int = DEFAULT_M,
    ks: int = DEFAULT_KS,
    nprobe_default: int = DEFAULT_NPROBE,  # noqa: ARG001 (recorded by caller)
    iters: int = 10,
    normalized: bool = False,
    seed: int = 0,
    device=None,
) -> IVFPQIndex:
    """Train + encode + pack padded lists, publish arrays to `device`."""
    n, d = vectors.shape
    vecs = vectors.astype(np.float32, copy=False)
    if normalized:
        norms = np.linalg.norm(vecs, axis=1, keepdims=True)
        vecs = vecs / np.maximum(norms, 1e-12)
    nlist = max(1, min(nlist, n // 4 if n >= 8 else 1))
    params = train(vecs, nlist=nlist, m=m, ks=ks, iters=iters, seed=seed)
    lists, codes = encode(vecs, params)
    if doc_ids is None:
        doc_ids = np.arange(n, dtype=np.int32)

    counts = np.bincount(lists, minlength=nlist)
    l_pad = max(8, int(counts.max()))
    l_pad = 1 << (l_pad - 1).bit_length()  # next pow2 for shape bucketing

    packed_codes = np.zeros((nlist, l_pad, params.m), np.uint8)
    packed_ids = np.full((nlist, l_pad), -1, np.int32)
    packed_mask = np.zeros((nlist, l_pad), bool)
    order = np.argsort(lists, kind="stable")
    offs = np.zeros(nlist + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    for li in range(nlist):
        rows = order[offs[li]: offs[li + 1]]
        packed_codes[li, : len(rows)] = codes[rows]
        packed_ids[li, : len(rows)] = doc_ids[rows]
        packed_mask[li, : len(rows)] = True

    put = lambda a: jax.device_put(a, device)
    coarse_host = np.asarray(params.coarse, dtype=np.float32)
    out = IVFPQIndex(
        params=IVFPQParams(
            coarse=put(coarse_host),
            codebooks=put(np.asarray(params.codebooks)),
            nlist=nlist, m=params.m, ks=params.ks, d=d,
        ),
        codes=put(packed_codes),
        ids=put(packed_ids),
        mask=put(packed_mask),
        l_pad=l_pad,
        n=n,
        normalized=normalized,
        build_generation=next(_build_generation),
        coarse_host=coarse_host,
        coarse_sq_host=np.sum(coarse_host * coarse_host, axis=1),
    )
    # HBM residency accounting: the slab is device-resident until the
    # owning segment retires (index/field attribution rides the caller's
    # upload_scope; the generation is this build's own id)
    from opensearch_tpu.telemetry.device_ledger import KIND_IVFPQ, default_ledger

    out.allocation = default_ledger.register(
        KIND_IVFPQ, out.nbytes, generation=out.build_generation)
    return out


# --------------------------------------------------------------------------
# search
# --------------------------------------------------------------------------


def lut_for_probes(queries: jnp.ndarray, coarse: jnp.ndarray,
                   codebooks: jnp.ndarray, probes: jnp.ndarray):
    """f32 [B, P, m, ks] residual ADC lookup tables for the given probe
    table. ONE implementation shared by the monolithic XLA lowering
    (:func:`search`) and the fused Pallas pipeline (ops/pallas_adc) — the
    two paths' score-space parity is enforced by construction, not by
    keeping copies in sync."""
    m, ks, dsub = codebooks.shape
    resid = queries[:, None, :] - coarse[probes]          # [B, P, d]
    r_sub = resid.reshape(queries.shape[0], probes.shape[1], m, dsub)
    r_dot = jnp.einsum(
        "bpms,mks->bpmk", r_sub, codebooks,
        preferred_element_type=jnp.float32,
    )
    r_sq = jnp.sum(r_sub * r_sub, axis=-1)                # [B, P, m]
    cb_sq = jnp.sum(codebooks * codebooks, axis=-1)       # [m, ks]
    return r_sq[..., None] - 2.0 * r_dot + cb_sq[None, None]  # [B,P,m,ks]


def exact_rescore(queries: jnp.ndarray, cand: jnp.ndarray,
                  vectors: jnp.ndarray, norms_sq: jnp.ndarray,
                  valid: jnp.ndarray, *, similarity: str, k_eff: int):
    """Exact fp32 rescore of the [B, R] candidate pool into k-NN score
    space: (scores [B, k_eff], doc_ids [B, k_eff], -1 where no finite
    candidate). Shared by both lowerings — see :func:`lut_for_probes`."""
    cand_safe = jnp.maximum(cand, 0)
    cvecs = vectors[cand_safe]                            # [B, R, d]
    cdots = jnp.einsum(
        "bd,brd->br", queries, cvecs, preferred_element_type=jnp.float32
    )
    if similarity == knn_ops.COSINE:
        q_norm = jnp.sqrt(jnp.sum(queries * queries, axis=-1,
                                  keepdims=True))
        v_norm = jnp.sqrt(jnp.maximum(norms_sq[cand_safe], 1e-24))
        raw = cdots / jnp.maximum(q_norm * v_norm, 1e-12)
        score = (1.0 + raw) / 2.0
    else:
        q_sq = jnp.sum(queries * queries, axis=-1, keepdims=True)
        d_sq = jnp.maximum(q_sq - 2.0 * cdots + norms_sq[cand_safe], 0.0)
        score = 1.0 / (1.0 + d_sq)
    ok = (cand >= 0) & valid[cand_safe]
    score = jnp.where(ok, score, -jnp.inf)
    best, best_pos = jax.lax.top_k(score, k_eff)
    best_ids = jnp.take_along_axis(cand, best_pos, axis=1)
    return best, jnp.where(jnp.isfinite(best), best_ids, -1)


@functools.partial(
    jax.jit,
    static_argnames=("k", "nprobe", "rerank", "similarity", "chunk",
                     "adc_precision"),
)
def search(
    coarse: jnp.ndarray,       # [nlist, d]
    codebooks: jnp.ndarray,    # [m, ks, dsub]
    codes: jnp.ndarray,        # uint8 [nlist, L_pad, m]
    ids: jnp.ndarray,          # int32 [nlist, L_pad]
    mask: jnp.ndarray,         # bool [nlist, L_pad]
    vectors: jnp.ndarray,      # f32 [n_pad, d] full-precision (rescore)
    norms_sq: jnp.ndarray,     # f32 [n_pad]
    valid: jnp.ndarray,        # bool [n_pad] live & present
    queries: jnp.ndarray,      # f32 [B, d]
    *,
    k: int,
    nprobe: int,
    rerank: int,
    similarity: str = "l2_norm",
    chunk: int = 8,
    adc_precision: str = "fp32",
):
    """Fused IVF-PQ ADC search + exact fp32 rescore.

    Returns (scores [B, k] in k-NN score space, doc_ids [B, k], -1 pads).
    lax.map over query chunks bounds the [chunk, nprobe, L_pad, m] ADC
    working set regardless of request batch size. ``adc_precision``
    selects the ADC accumulation dtype (candidate RANKING only — the
    rescore below is always exact fp32).
    """
    if adc_precision not in ADC_PRECISIONS:
        raise ValueError(
            f"unknown adc_precision [{adc_precision}] "
            f"(choose from {list(ADC_PRECISIONS)})"
        )
    nlist, l_pad, m = codes.shape
    d = coarse.shape[1]
    similarity = knn_ops.canonical_similarity(similarity)
    nprobe = min(nprobe, nlist)
    # at most nprobe * l_pad candidates exist; clamp both cut points so
    # top_k never asks for more than the axis holds (k > candidates pads)
    k_eff = min(k, nprobe * l_pad)
    rerank = max(k_eff, min(rerank, nprobe * l_pad))
    B = queries.shape[0]

    c_sq = jnp.sum(coarse * coarse, axis=-1)

    def one_chunk(q):  # q: [chunk, d]
        qdots = jnp.einsum(
            "bd,ld->bl", q, coarse, preferred_element_type=jnp.float32
        )
        # negative l2^2 up to the constant ||q||^2
        _, probe = jax.lax.top_k(2.0 * qdots - c_sq[None, :], nprobe)  # [c, P]

        lut = lut_for_probes(q, coarse, codebooks, probe)     # [c,P,m,ks]

        pcodes = codes[probe].astype(jnp.int32)               # [c, P, L, m]
        pids = ids[probe]                                     # [c, P, L]
        pmask = mask[probe]
        # ADC: sum_m lut[c,p,m,code] — accumulation precision is the
        # ANNS-AMP knob; reduced precision only ranks candidates, the
        # exact fp32 rescore below restores score fidelity
        if adc_precision == "int8":
            # per-(query, probe) affine uint8 quantization of the LUT;
            # int32 accumulate, then dequantize so candidates stay
            # comparable ACROSS probes (each probe has its own affine)
            lo = jnp.min(lut, axis=(-2, -1), keepdims=True)   # [c,P,1,1]
            hi = jnp.max(lut, axis=(-2, -1), keepdims=True)
            scale = jnp.maximum(hi - lo, 1e-12) / 255.0
            lut_q = jnp.clip(
                jnp.round((lut - lo) / scale), 0.0, 255.0
            ).astype(jnp.uint8)
            # gather MOVES uint8 entries (the whole point of this mode:
            # 1/4 the LUT bytes through the gather); widen only the
            # gathered [c,P,L,m] values for the int32 accumulate
            gathered = jnp.take_along_axis(
                lut_q[:, :, None, :, :],                      # [c,P,1,m,ks]
                pcodes[..., None],                            # [c,P,L,m,1]
                axis=-1,
            )[..., 0]                                         # [c,P,L,m] u8
            acc = jnp.sum(gathered, axis=-1, dtype=jnp.int32)  # [c,P,L]
            adc = (acc.astype(jnp.float32) * scale[..., 0, 0][..., None]
                   + m * lo[..., 0, 0][..., None])
        elif adc_precision == "bf16":
            gathered = jnp.take_along_axis(
                lut.astype(jnp.bfloat16)[:, :, None, :, :],   # [c,P,1,m,ks]
                pcodes[..., None],                            # [c,P,L,m,1]
                axis=-1,
            )[..., 0]                                         # [c,P,L,m]
            adc = jnp.sum(gathered, axis=-1).astype(jnp.float32)
        else:
            gathered = jnp.take_along_axis(
                lut[:, :, None, :, :],                        # [c,P,1,m,ks]
                pcodes[..., None],                            # [c,P,L,m,1]
                axis=-1,
            )[..., 0]                                         # [c,P,L,m]
            adc = jnp.sum(gathered, axis=-1)                  # [c,P,L] ~ d^2
        adc = jnp.where(pmask, adc, jnp.inf)

        flat_adc = adc.reshape(q.shape[0], nprobe * l_pad)
        flat_ids = pids.reshape(q.shape[0], nprobe * l_pad)
        _, cand_pos = jax.lax.top_k(-flat_adc, rerank)
        cand = jnp.take_along_axis(flat_ids, cand_pos, axis=1)  # [c, R]

        best, best_ids = exact_rescore(
            q, cand, vectors, norms_sq, valid,
            similarity=similarity, k_eff=k_eff)
        if k_eff < k:  # fewer candidates than asked for: pad to [*, k]
            pad = ((0, 0), (0, k - k_eff))
            best = jnp.pad(best, pad, constant_values=-jnp.inf)
            best_ids = jnp.pad(best_ids, pad, constant_values=-1)
        return best, best_ids

    b_pad = -(-B // chunk) * chunk
    qp = jnp.pad(queries, ((0, b_pad - B), (0, 0)))
    vals, out_ids = jax.lax.map(
        one_chunk, qp.reshape(b_pad // chunk, chunk, d)
    )
    return (
        vals.reshape(b_pad, k)[:B],
        out_ids.reshape(b_pad, k)[:B],
    )


def default_rerank(k: int, rescore_multiplier: int | None = None) -> int:
    """Exact-rescore pool width before the candidate-count clamp."""
    mult = rescore_multiplier or DEFAULT_RESCORE_MULTIPLIER
    return max(mult * k, 64)


def rescore_pool(index: IVFPQIndex, k: int, nprobe: int,
                 rerank: int) -> int:
    """The EFFECTIVE rescore candidate count `search` will use for this
    index/shape (the same clamp the kernel applies) — surfaced by the
    profiler so "profile": true shows the real pool width."""
    nprobe = min(nprobe, index.params.nlist)
    cap = nprobe * index.l_pad
    k_eff = min(k, cap)
    return max(k_eff, min(rerank, cap))


def host_probe_select(index: IVFPQIndex, queries: np.ndarray,
                      nprobe: int) -> np.ndarray:
    """FusionANNS-style host routing: coarse quantization + probe
    selection in numpy over the cached host centroids. Returns the probe
    table [B, nprobe] int32, rows ordered by DESCENDING coarse score with
    list-id ascending tie-break (``lax.top_k``'s ordering, so the fused
    kernel's probe-major candidate order matches the device convention).
    The fused device program consumes this table as its scalar-prefetch
    operand — candidate-list assembly never touches the device."""
    coarse = index.coarse_host
    c_sq = index.coarse_sq_host
    if coarse is None or c_sq is None:  # pre-cooperative builds
        coarse = np.asarray(index.params.coarse, dtype=np.float32)
        c_sq = np.sum(coarse * coarse, axis=1)
        index.coarse_host, index.coarse_sq_host = coarse, c_sq
    nprobe = min(nprobe, index.params.nlist)
    # negative l2^2 up to the constant ||q||^2 — the same probe ranking
    # the device path's top_k uses
    score = 2.0 * (queries @ coarse.T) - c_sq[None, :]
    part = np.argpartition(-score, nprobe - 1, axis=1)[:, :nprobe]
    rows = np.take_along_axis(score, part, axis=1)
    # per-row ordering: score desc, then list id asc (lexsort is stable)
    order = np.stack([
        np.lexsort((part[i], -rows[i])) for i in range(part.shape[0])
    ])
    return np.take_along_axis(part, order, axis=1).astype(np.int32)


def select_probes(index: IVFPQIndex, queries, nprobe: int | None,
                  kernel: str):
    """The host's half of a launch under the RESOLVED serving policy
    ``kernel`` (search/ann.py resolve_kernel), as (queries, probes) for
    :func:`search_probed`. "pallas" is a cooperative split: the queries
    are normalized and coarse-quantized and the probes selected here, on
    the host (:func:`host_probe_select`). "xla" selects its probes on the
    device, so the queries pass through and probes is None."""
    if kernel != "pallas":
        return queries, None
    qh = np.asarray(queries, dtype=np.float32)
    if index.normalized:
        q_norm = np.linalg.norm(qh, axis=-1, keepdims=True)
        qh = qh / np.maximum(q_norm, 1e-12)
    nprobe = min(nprobe or DEFAULT_NPROBE, index.params.nlist)
    return qh, host_probe_select(index, qh, nprobe)


def search_probed(
    index: IVFPQIndex,
    vectors: jnp.ndarray,
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,
    queries,
    probes,
    *,
    k: int,
    nprobe: int | None = None,
    rerank: int | None = None,
    similarity: str = "l2_norm",
    adc_precision: str = "fp32",
    rescore_multiplier: int | None = None,
):
    """The device's half, on what :func:`select_probes` returned: with
    probes, ONE batched fused Pallas scan + exact rescore
    (ops/pallas_adc.adc_topr_auto; interpret-mode only on the CPU
    backend); without, the monolithic :func:`search` lowering. Returns
    device arrays; the caller's host copy is the fence."""
    if rerank is None:
        rerank = default_rerank(k, rescore_multiplier)
    similarity = knn_ops.canonical_similarity(similarity)
    if probes is not None:
        from opensearch_tpu.ops import pallas_adc

        return pallas_adc.adc_topr_auto(
            index.params.coarse, index.params.codebooks,
            index.codes, index.ids, index.mask,
            vectors, norms_sq, valid,
            jnp.asarray(queries), jnp.asarray(probes),
            k=k, rerank=rerank,
            similarity=similarity, adc_precision=adc_precision,
            impl="pallas")
    if index.normalized:
        q_norm = jnp.linalg.norm(queries, axis=-1, keepdims=True)
        queries = queries / jnp.maximum(q_norm, 1e-12)
    return search(
        index.params.coarse,
        index.params.codebooks,
        index.codes,
        index.ids,
        index.mask,
        vectors,
        norms_sq,
        valid,
        queries,
        k=k,
        nprobe=min(nprobe or DEFAULT_NPROBE, index.params.nlist),
        rerank=rerank,
        similarity=similarity,
        adc_precision=adc_precision,
    )


def search_index(
    index: IVFPQIndex,
    vectors: jnp.ndarray,
    norms_sq: jnp.ndarray,
    valid: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    nprobe: int | None = None,
    rerank: int | None = None,
    similarity: str = "l2_norm",
    adc_precision: str = "fp32",
    rescore_multiplier: int | None = None,
    kernel: str = "xla",
):
    """Convenience wrapper binding an IVFPQIndex's arrays to the selected
    ADC scan: :func:`select_probes`, then :func:`search_probed`. The
    serving closure calls the two halves itself, each in its own span."""
    queries, probes = select_probes(index, queries, nprobe, kernel)
    return search_probed(
        index, vectors, norms_sq, valid, queries, probes,
        k=k, nprobe=nprobe, rerank=rerank, similarity=similarity,
        adc_precision=adc_precision, rescore_multiplier=rescore_multiplier)
