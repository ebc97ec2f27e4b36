"""Fused Pallas TPU kernel: blockwise IVF-PQ ADC scan with a running top-R
candidate pool (ROADMAP item 2; the kernel PR the roofline report asked for).

WHY. PR 12's roofline report ranks the ADC scan's XLA lowering among the
top lost-time offenders and documents the ``ivfpq_search[int8]`` inversion:
int8 achieves less than fp32 on XLA's CPU backend against a
SMALLER modeled byte floor, because XLA widens the quantized LUT through
the ``take_along_axis`` gather — the byte saving never reaches HBM. A
hand-scheduled kernel controls residency directly: the per-(query, probe)
LUT stays in VMEM (fp32, or bf16 half-width for both reduced precisions),
each probe's PQ code block streams through VMEM exactly once, and only the
``[B, R]`` winners ever land in HBM — the ``[B, nprobe, L_pad]``
ADC-distance intermediate of the XLA lowering never exists.

SPLIT (FusionANNS-style host/device cooperative routing, PAPERS.md):
coarse quantization, probe selection and candidate-list assembly run
host-side in :func:`opensearch_tpu.ops.ivfpq.host_probe_select` — numpy
over cached host copies of the coarse centroids — and the device runs ONE
batched fused program: LUT build (XLA einsum over the host-chosen probes),
native-width quantization, the Pallas blockwise ADC scan, and the existing
exact fp32 rescore. The probe table rides the launch as a SCALAR-PREFETCH
operand (``pltpu.PrefetchScalarGridSpec``): each grid step's BlockSpec
index_map reads ``probes[b, p]`` to DMA exactly the probed inverted-list
block from the device-resident ``[nlist, L_pad, m]`` code slab — no
``codes[probes]`` gather materializes.

KERNEL. Grid ``(B, nprobe, L_pad // l_blk)`` (sequential on a TensorCore,
so VMEM scratch persists across iterations — the ``pallas_knn.py``
accumulation pattern). Per step: decode the ``[l_blk, m]`` code tile
against the resident m-major ``[1, m·ks]`` LUT row as ONE one-hot matmul
on the MXU (``[1, m·ks] × [l_blk, m·ks]ᵀ``, both contracted on lanes; the
one-hot operand is m lane-compares concatenated lane-wise — no gather), so
the block's candidates land on the result's lanes, mask ragged list tails,
and fold them into a running ``[1, R]`` top-R pool in VMEM scratch via
R extract-max rounds, guarded by the kth-best threshold early-exit so
steady-state tiles cost one decode + one row-max. Carried entries merge
FIRST, so score ties resolve to the earliest (probe-major) position —
exactly ``lax.top_k``'s tie-break over the XLA path's flattened
``[nprobe * L_pad]`` axis, which is what makes the interpret-mode parity
tests exact.

LAYOUT (what Mosaic accepts — checked without a chip by
tests/test_tpu_lowering.py): every row-shaped operand (LUT, ids, mask,
both outputs) carries a unit second-minor axis so its block's last two
dims span the array's or the (8, 128) tile; the probe table is flat in
SMEM; nothing is reshaped between lanes and sublanes inside the kernel.

PRECISION (ANNS-AMP): "fp32" accumulates f32; "bf16" keeps the LUT
resident in VMEM at half width and accumulates f32; "int8" quantizes each
QUERY's LUT affinely to uint8 (one shared affine across its probes, so
integer sums stay comparable ACROSS probes without a dequantize in the
scan), widens it to bf16 on the way in (0..255 is exact in bf16; Mosaic
has no uint8 -> bf16 convert) and rides the one-hot matmul with an f32
accumulator — sums are ≤ m·255 < 2^24, exactly representable in any
summation order, so the pool still ranks on integers and the exact fp32
rescore restores score fidelity. No gather ever widens the LUT per
candidate: that is the whole point.

SELECTION. Serving reaches this kernel only through
:func:`adc_topr_auto` / the ``search.knn.ann.kernel`` policy
(search/ann.py): ``pallas`` on TPU, the ``interpret=True`` parity path
only when the backend is the CPU (as ``pallas_knn.fused_impl`` has it), with
:func:`adc_scan_xla` as the bit-compatible XLA fallback the parity tests
diff against. tpulint TPU016 enforces the shape statically:
``pl.pallas_call`` lives only under ``ops/``, behind the one function of
its module that carries the platform/interpret guard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from opensearch_tpu.search.profile import profiled_kernel

# inverted-list block width streamed through VMEM per grid step; l_pad is
# a power of two, so min(L_BLOCK, l_pad) always divides it evenly
L_BLOCK = 256
_NEG_INF = float("-inf")


def _adc_scan_kernel(
    probes_ref,   # scalar prefetch [B * P] int32 (host-selected probe table)
    lut_ref,      # [1, m * ks] f32 / bf16 — this (query, probe)'s LUT, m-major
    codes_ref,    # [l_blk, m] uint8 — the probed inverted-list block
    ids_ref,      # [1, l_blk] int32 doc ids (-1 = padding)
    mask_ref,     # [1, l_blk] f32 (1.0 live slot; bool tiles are awkward)
    vals_out,     # [1, R] f32 candidate scores (-adc, higher is better)
    ids_out,      # [1, R] i32
    vals_scr,     # VMEM scratch [1, R] f32 — the running pool
    ids_scr,      # VMEM scratch [1, R] i32
    *,
    r: int,
    ks: int,
    n_lb: int,
    nprobe: int,
):
    del probes_ref  # consumed by the BlockSpec index_maps only
    p = pl.program_id(1)
    lb = pl.program_id(2)

    @pl.when((p == 0) & (lb == 0))
    def _init():
        vals_scr[:] = jnp.full((1, r), _NEG_INF)
        ids_scr[:] = jnp.full((1, r), -1, jnp.int32)

    codes = codes_ref[:].astype(jnp.int32)                # [l_blk, m]
    l_blk, m = codes.shape
    lut = lut_ref[:]                                      # [1, m * ks]
    iota_ks = jax.lax.broadcasted_iota(jnp.int32, (l_blk, ks), 1)
    # MXU one-hot decode: adc[l] = sum_m lut[m, code[l, m]] as ONE
    # [1, m*ks] x [l_blk, m*ks]^T matmul. The one-hot operand is m 2D
    # lane-compares concatenated lane-wise (no gather, LUT never leaves
    # VMEM) and the LUT arrives flattened m-major so lanes line up.
    # Contracting both operands on their lane axis puts the l_blk
    # candidates on the RESULT's lanes — the layout the [1, l_blk]
    # ids/mask rows and the pool merge below already use.
    onehot = jnp.concatenate(
        [(iota_ks == codes[:, mi:mi + 1]).astype(lut.dtype)
         for mi in range(m)], axis=1)                     # [l_blk, m * ks]
    # f32 LUT: HIGHEST is the MXU's fp32-faithful mode — products are
    # exact (one-hot), so only summation order can move. bf16 LUT entries
    # (native bf16, or uint8 0..255 widened exactly) ride one MXU pass
    # with an f32 accumulator; int8 sums are integers <= m * 255 < 2^24,
    # exact in ANY order, so the int8 pool stays bit-identical to integer
    # accumulation.
    adc = jax.lax.dot_general(
        lut, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if lut.dtype == jnp.float32 else None))  # [1, l_blk]
    # smaller ADC distance = better candidate; ragged tails -> -inf
    scores = jnp.where(mask_ref[:] > 0.5, -adc, _NEG_INF)
    cand_ids = ids_ref[:]                                 # [1, l_blk]

    # threshold early-exit (the pallas_knn pattern): the R-round merge
    # only runs when this block beats the pool's current Rth-best
    kth_best = vals_scr[:, r - 1]
    improves = jnp.any(jnp.max(scores, axis=1) > kth_best)

    @pl.when(improves)
    def _merge():
        # carried entries FIRST: argmax takes the first maximum, so on
        # ties the earlier (probe-major) candidate wins — lax.top_k's
        # tie-break over the XLA path's flattened candidate axis
        ext_vals = jnp.concatenate([vals_scr[:], scores], axis=1)
        ext_ids = jnp.concatenate([ids_scr[:], cand_ids], axis=1)
        width = ext_vals.shape[1]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        colr = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)

        def select_one(i, carry):
            ext, acc_v, acc_i = carry
            best = jnp.max(ext, axis=1, keepdims=True)
            arg = jnp.argmax(ext, axis=1).astype(jnp.int32)
            onehot = col == arg[:, None]
            best_id = jnp.sum(jnp.where(onehot, ext_ids, 0), axis=1,
                              keepdims=True)
            best_id = jnp.where(best > _NEG_INF, best_id, -1)
            sel = colr == i
            acc_v = jnp.where(sel, best, acc_v)
            acc_i = jnp.where(sel, best_id, acc_i)
            return jnp.where(onehot, _NEG_INF, ext), acc_v, acc_i

        _, acc_v, acc_i = jax.lax.fori_loop(
            0, r, select_one,
            (ext_vals,
             jnp.full((1, r), _NEG_INF, jnp.float32),
             jnp.full((1, r), -1, jnp.int32)))
        vals_scr[:] = acc_v
        ids_scr[:] = acc_i

    @pl.when((p == nprobe - 1) & (lb == n_lb - 1))
    def _emit():
        vals_out[:] = vals_scr[:]
        ids_out[:] = ids_scr[:]


def pallas_adc_topr(
    lut: jnp.ndarray,     # [B, P, m, ks] native width
    codes: jnp.ndarray,   # uint8 [nlist, L_pad, m] (device-resident slab)
    ids: jnp.ndarray,     # int32 [nlist, L_pad]
    maskf: jnp.ndarray,   # f32 [nlist, L_pad] (1.0 = live slot)
    probes: jnp.ndarray,  # int32 [B, P] host-selected probe table
    *,
    r: int,
    l_blk: int,
    interpret: bool = False,
):
    """(pool_vals [B, R] f32, pool_ids [B, R] i32): the running top-R
    candidate pool per query, scores = -adc (higher is better), slots past
    the candidate count carry (-inf, -1). Only these winners land in HBM.
    """
    B, P, m, ks = lut.shape
    nlist, l_pad, _ = codes.shape
    if l_pad % l_blk != 0:  # a truncated scan would be silently wrong
        raise ValueError(
            f"l_blk [{l_blk}] must divide l_pad [{l_pad}] — both are "
            f"powers of two on the serving path")
    n_lb = l_pad // l_blk
    if lut.dtype == jnp.uint8:
        # Mosaic has no uint8 -> bf16 convert; 0..255 is exact in bf16
        # (8 mantissa bits), so widen on the way in. The LUT is
        # B*P*m*ks entries — noise next to the streamed code blocks.
        lut = lut.astype(jnp.bfloat16)
    kernel = functools.partial(
        _adc_scan_kernel, r=r, ks=ks, n_lb=n_lb, nprobe=P)
    # Mosaic block rule: a block's last two dims are multiples of the
    # (8, 128) tile or span the array's. Row-shaped operands therefore
    # carry a unit second-minor axis ([.., 1, width]) and the leading
    # axes are squeezed (None) so the kernel sees plain [1, width] rows.
    # The probe table is flat: 2D SMEM pads every row to a full lane tile.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P, n_lb),
        in_specs=[
            pl.BlockSpec((None, None, 1, m * ks),
                         lambda b, p, l, pr: (b, p, 0, 0)),
            # the probed list block: the index_map reads the scalar-
            # prefetched probe table, so the DMA streams exactly the
            # blocks the host routed this query to
            pl.BlockSpec((None, l_blk, m),
                         lambda b, p, l, pr: (pr[b * P + p], l, 0)),
            pl.BlockSpec((None, 1, l_blk),
                         lambda b, p, l, pr: (pr[b * P + p], 0, l)),
            pl.BlockSpec((None, 1, l_blk),
                         lambda b, p, l, pr: (pr[b * P + p], 0, l)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, r), lambda b, p, l, pr: (b, 0, 0)),
            pl.BlockSpec((None, 1, r), lambda b, p, l, pr: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, r), jnp.float32),
            pltpu.VMEM((1, r), jnp.int32),
        ],
    )
    vals, out_ids = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, r), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, r), jnp.int32),
        ],
        interpret=interpret,
        name="fused_adc_search",
    )(probes.reshape(B * P), lut.reshape(B, P, 1, m * ks), codes,
      ids.reshape(nlist, 1, l_pad), maskf.reshape(nlist, 1, l_pad))
    return vals.reshape(B, r), out_ids.reshape(B, r)


def adc_scan_xla(lut, codes, ids, maskf, probes, *, r: int):
    """The fused pipeline's XLA fallback scan: same inputs, same candidate
    ordering (``lax.top_k`` over the probe-major flattened axis matches the
    pool's carried-first tie-break), via the gather lowering the kernel
    replaces. int8 pool values are bit-identical to the kernel's (integer
    accumulation); fp32/bf16 agree to summation order. On a TPU
    ``lax.top_k`` does not order EQUAL values by position, so among ties
    the two pools may list different ids (seen on a v5e); the kernel keeps
    the earliest, as the CPU's ``top_k`` does."""
    pcodes = codes[probes].astype(jnp.int32)       # [B, P, L, m]
    pids = ids[probes]                              # [B, P, L]
    pmask = maskf[probes] > 0.5
    wide = jnp.int32 if lut.dtype == jnp.uint8 else jnp.float32
    gathered = jnp.take_along_axis(
        lut.astype(wide)[:, :, None, :, :],         # [B, P, 1, m, ks]
        pcodes[..., None], axis=-1)[..., 0]         # [B, P, L, m]
    adc = jnp.sum(gathered, axis=-1)                # [B, P, L]
    score = jnp.where(pmask, -adc.astype(jnp.float32), _NEG_INF)
    B = lut.shape[0]
    flat = score.reshape(B, -1)
    flat_ids = pids.reshape(B, -1)
    vals, pos = jax.lax.top_k(flat, r)
    out_ids = jnp.take_along_axis(flat_ids, pos, axis=1)
    out_ids = jnp.where(vals > _NEG_INF, out_ids, -1)
    return vals, out_ids


def build_luts(queries, coarse, codebooks, probes, *, adc_precision: str):
    """Per-(query, probe) residual LUTs at NATIVE width from the
    host-selected probe table: the SHARED f32 LUT math
    (ops/ivfpq.lut_for_probes — score-space parity with the XLA lowering
    by construction), then downcast bf16, or a per-QUERY affine uint8
    quantization (one shared scale across a query's probes keeps integer
    ADC sums comparable across probes, so the scan never needs a
    dequantize)."""
    from opensearch_tpu.ops import ivfpq

    if adc_precision not in ivfpq.ADC_PRECISIONS:
        # same guard as ivfpq.search: an unknown precision must error,
        # never silently fall through to the fp32 LUT
        raise ValueError(
            f"unknown adc_precision [{adc_precision}] "
            f"(choose from {list(ivfpq.ADC_PRECISIONS)})"
        )
    lut = ivfpq.lut_for_probes(queries, coarse, codebooks, probes)
    if adc_precision == "bf16":
        return lut.astype(jnp.bfloat16)
    if adc_precision == "int8":
        lo = jnp.min(lut, axis=(1, 2, 3), keepdims=True)  # [B, 1, 1, 1]
        hi = jnp.max(lut, axis=(1, 2, 3), keepdims=True)
        scale = jnp.maximum(hi - lo, 1e-12) / 255.0
        return jnp.clip(
            jnp.round((lut - lo) / scale), 0.0, 255.0).astype(jnp.uint8)
    return lut


@functools.partial(
    jax.jit,
    static_argnames=("k", "rerank", "similarity", "adc_precision",
                     "use_pallas", "interpret", "l_blk"),
)
def fused_adc_search(
    coarse: jnp.ndarray,       # [nlist, d]
    codebooks: jnp.ndarray,    # [m, ks, dsub]
    codes: jnp.ndarray,        # uint8 [nlist, L_pad, m]
    ids: jnp.ndarray,          # int32 [nlist, L_pad]
    mask: jnp.ndarray,         # bool [nlist, L_pad]
    vectors: jnp.ndarray,      # f32 [n_pad, d] (exact rescore source)
    norms_sq: jnp.ndarray,     # f32 [n_pad]
    valid: jnp.ndarray,        # bool [n_pad]
    queries: jnp.ndarray,      # f32 [B, d] (normalized by the caller)
    probes: jnp.ndarray,       # int32 [B, P] host-selected probe table
    *,
    k: int,
    rerank: int,
    similarity: str = "l2_norm",
    adc_precision: str = "fp32",
    use_pallas: bool = True,
    interpret: bool = False,
    l_blk: int = L_BLOCK,
):
    """The ONE batched device program of the cooperative split: LUT build
    over the host-chosen probes, native-width quantization, the blockwise
    ADC scan (Pallas kernel or XLA fallback), and the exact fp32 rescore.
    Returns (scores [B, k] in k-NN score space, doc_ids [B, k], -1 pads)
    — the ``ops/ivfpq.search`` contract."""
    B = queries.shape[0]
    nlist, l_pad, m = codes.shape
    P = probes.shape[1]
    k_eff = min(k, P * l_pad)
    r = max(k_eff, min(rerank, P * l_pad))

    lut = build_luts(queries, coarse, codebooks, probes,
                     adc_precision=adc_precision)
    maskf = mask.astype(jnp.float32)
    if use_pallas:
        cand_vals, cand = pallas_adc_topr(
            lut, codes, ids, maskf, probes,
            r=r, l_blk=min(l_blk, l_pad), interpret=interpret)
    else:
        cand_vals, cand = adc_scan_xla(lut, codes, ids, maskf, probes, r=r)

    # exact fp32 rescore over the [B, R] winners — the SAME rescore stage
    # the XLA lowering runs (ops/ivfpq.exact_rescore), so scores land in
    # the same score space by construction
    from opensearch_tpu.ops import ivfpq

    best, best_ids = ivfpq.exact_rescore(
        queries, cand, vectors, norms_sq, valid,
        similarity=similarity, k_eff=k_eff)
    if k_eff < k:  # fewer candidates than asked for: pad to [*, k]
        pad = ((0, 0), (0, k - k_eff))
        best = jnp.pad(best, pad, constant_values=-jnp.inf)
        best_ids = jnp.pad(best_ids, pad, constant_values=-1)
    return best, best_ids


@profiled_kernel("ivfpq_adc_pallas")
def adc_topr_auto(
    coarse, codebooks, codes, ids, mask, vectors, norms_sq, valid,
    queries, probes, *,
    k: int,
    rerank: int,
    similarity: str = "l2_norm",
    adc_precision: str = "fp32",
    impl: str | None = None,
):
    """Platform-dispatch wrapper for the fused ADC search (the TPU016
    contract: Pallas kernels are reachable only through here). ``impl``:
    None (auto) runs the Pallas kernel natively on TPU and the XLA
    fallback scan elsewhere; "pallas" forces the kernel — interpret-mode
    only when the backend is the CPU, the tests' parity path; "xla" forces
    the fallback scan. ``profiled_kernel`` covers it like the exact
    entries, so the profiler's ``retraced`` oracle and the roofline fold
    see direct launches of the fused ADC program too."""
    platform = jax.devices()[0].platform
    if impl == "pallas":
        use_pallas, interpret = True, platform == "cpu"
    elif impl == "xla":
        use_pallas, interpret = False, False
    else:
        use_pallas, interpret = platform == "tpu", False
    return fused_adc_search(
        coarse, codebooks, codes, ids, mask, vectors, norms_sq, valid,
        queries, probes,
        k=k, rerank=rerank, similarity=similarity,
        adc_precision=adc_precision,
        use_pallas=use_pallas, interpret=interpret)
