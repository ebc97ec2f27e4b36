"""The one exact-kNN scan, `ops/pallas_knn.knn_fused` (ISSUE 19; the only
one since ISSUE 31): both lowerings against a numpy reference and each
other, the one rule that picks between them, the mesh
one-launch-per-node program, and the exact-path kernel policy.

Acceptance properties:
 - `knn_fused(impl="xla")`, what serves the CPU backend and k >
   FUSED_MAX_K, equals a plain numpy reference for the three similarities,
   on ties (lower doc id first) and with fewer live docs than k;
 - `fused_impl(policy, k)` is the only place the platform picks a
   lowering, and both serving paths obey its k cap;
 - interpret-mode parity vs the XLA reference per score precision: int8
   pools are BIT-identical (integer matmul + scalar dequant), fp32/bf16
   ids identical with scores equal to summation order, and every reduced
   precision ends in the exact fp32 rescore (serving score space);
 - padding (n not a block multiple), the valid mask, (-inf, -1) tail
   slots past the live-doc count, and lowest-doc-id tie-break all match
   the XLA path bit for bit;
 - the shard_map serving program (parallel/distributed) returns identical
   vals/gids/counts for kernel="pallas" vs the XLA reference at 1/2/4
   devices, and equals `knn_fused` per shard + a numpy merge;
 - ``search.knn.kernel`` / ``search.knn.score_precision`` round-trip
   /_cluster/settings with validation + None-deletion, apply live, ride
   the dispatch batch key (no cross-kernel merges), and serve through the
   executor's fused branch with roofline + ledger + retraced accounting.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax

from opensearch_tpu.common.errors import IllegalArgumentException
from opensearch_tpu.node import TpuNode
from opensearch_tpu.ops import pallas_knn
from opensearch_tpu.search import ann as ann_mod
from opensearch_tpu.search import distributed_serving
from opensearch_tpu.search import executor as executor_mod
from opensearch_tpu.search.batcher import KnnDispatchBatcher
from opensearch_tpu.telemetry import roofline

DIM = 16
N_DOCS = 700
PRECISIONS = pallas_knn.SCORE_PRECISIONS
SIMS = ("l2_norm", "cosine", "dot_product")


def _corpus(rng, n, d, n_centers=8, spread=5.0):
    centers = rng.standard_normal((n_centers, d)) * spread
    return (
        centers[rng.integers(0, n_centers, n)] + rng.standard_normal((n, d))
    ).astype(np.float32)


def _operands(rng, n=N_DOCS, d=DIM, b=6, n_dead=25):
    data = _corpus(rng, n, d)
    vecs = jnp.asarray(data)
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = np.ones(n, bool)
    valid[rng.choice(n, n_dead, replace=False)] = False
    queries = jnp.asarray(_corpus(rng, b, d))
    return vecs, norms, jnp.asarray(valid), queries, valid


# (query rows, precision): the B = 8 and B = 128 choices of the rule, and
# one per operand dtype (a narrower row streams more rows per grid step)
TILE_CASES = ((6, "fp32"), (128, "fp32"), (6, "bf16"), (6, "int8"))
N_KINDS = ("tile", "tile+64", "two-tiles", "below-block")


def _rule_tile(b: int, d: int, precision: str, n_pad: int = 1 << 30) -> int:
    """The tile `pallas_knn_fused` picks for `b` query rows of a `d`-wide
    `precision` column that is long enough not to bound it."""
    item = {"fp32": 4, "bf16": 2, "int8": 1}[precision]
    b_tile = min(pallas_knn.FK_QTILE, max(8, -(-b // 8) * 8))
    return pallas_knn.fused_tile(n_pad, d, item, b_tile)


def _n_for(kind: str, tile: int) -> int:
    return {"tile": tile, "tile+64": tile + 64, "two-tiles": 2 * tile,
            "below-block": pallas_knn.FK_BLOCK // 2}[kind]


def _assert_pallas_matches_xla(vecs, norms, valid, queries, precision,
                               similarity="l2_norm"):
    """The kernel and its XLA reference share the dot/transform/rescore
    math, so the [B, k] contract is identical — int8 bit-for-bit (integer
    accumulation + scalar dequant), floats to summation order."""
    out = {}
    for impl in ("pallas", "xla"):
        out[impl] = pallas_knn.knn_fused(
            vecs, norms, valid, queries, k=10, similarity=similarity,
            score_precision=precision, impl=impl, interpret=True)
    pv, pi = map(np.asarray, out["pallas"])
    xv, xi = map(np.asarray, out["xla"])
    assert np.array_equal(pi, xi)
    if precision == "int8":
        assert np.array_equal(pv, xv)
    else:
        assert np.allclose(pv, xv, atol=1e-6, equal_nan=True)


def _numpy_topk(vectors, valid, queries, k, similarity="l2_norm"):
    """Plain float64 reference of the contract: serving-space scores, the
    k best by (-score, doc id), (-inf, -1) past the live docs."""
    v = np.asarray(vectors, np.float64)
    q = np.asarray(queries, np.float64)
    dots = q @ v.T
    if similarity == "l2_norm":
        d_sq = ((q * q).sum(1)[:, None] - 2.0 * dots
                + (v * v).sum(1)[None, :])
        scores = 1.0 / (1.0 + np.maximum(d_sq, 0.0))
    elif similarity == "cosine":
        norm = (np.sqrt((q * q).sum(1))[:, None]
                * np.sqrt((v * v).sum(1))[None, :])
        scores = (1.0 + dots / np.maximum(norm, 1e-12)) / 2.0
    else:
        scores = np.where(dots >= 0, dots + 1.0, 1.0 / (1.0 - dots))
    scores = np.where(np.asarray(valid)[None, :], scores, -np.inf)
    n = scores.shape[1]
    if n < k:
        scores = np.pad(scores, ((0, 0), (0, k - n)),
                        constant_values=-np.inf)
    ids = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, ids, axis=1)
    return vals, np.where(np.isfinite(vals), ids, -1)


def _xla_twin(vecs, norms, valid, queries, k, similarity="l2_norm"):
    return map(np.asarray, pallas_knn.knn_fused(
        vecs, norms, valid, queries, k=k, similarity=similarity,
        score_precision="fp32", impl="xla", interpret=False))


# ---------------------------------------------------------------------------
# the XLA twin against the numpy reference: what serves the CPU backend and
# k > FUSED_MAX_K (the cases the deleted streaming / materializing lowerings
# were held to)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("similarity", SIMS)
def test_xla_twin_matches_numpy_reference(similarity):
    rng = np.random.default_rng(1)
    n, d = 3000, DIM
    vecs = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = np.ones(n, bool)
    valid[rng.choice(n, 40, replace=False)] = False
    queries = jnp.asarray(rng.standard_normal((7, d)).astype(np.float32))
    got_v, got_i = _xla_twin(vecs, norms, jnp.asarray(valid), queries, 5,
                             similarity)
    ref_v, ref_i = _numpy_topk(vecs, valid, queries, 5, similarity)
    assert np.array_equal(got_i, ref_i)
    np.testing.assert_allclose(got_v, ref_v, rtol=1e-5)


def test_xla_twin_tied_rows_tiles_apart_keep_the_lower_doc_id():
    """Two bit-equal rows more than one kernel tile apart tie exactly: the
    lower doc id ranks first, as the host merge's (-score, doc) order has
    it."""
    rng = np.random.default_rng(3)
    d = 8
    tile = _rule_tile(5, d, "fp32")
    n = 2 * tile + 100
    data = rng.standard_normal((n, d)).astype(np.float32)
    lo, hi = 17, tile + tile // 2 + 17
    assert hi - lo > tile
    data[hi] = data[lo]
    vecs = jnp.asarray(data)
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = np.ones(n, bool)
    queries = np.repeat(data[lo][None, :], 5, axis=0)
    queries[1:] += rng.standard_normal((4, d)).astype(np.float32) * 0.01
    got_v, got_i = _xla_twin(vecs, norms, jnp.asarray(valid),
                             jnp.asarray(queries), 10)
    ref_v, ref_i = _numpy_topk(vecs, valid, queries, 10)
    assert np.array_equal(got_i, ref_i)
    first = got_i[0].tolist()
    assert first[:2] == [lo, hi]
    assert got_v[0, 0] == got_v[0, 1]


def test_xla_twin_fewer_live_docs_than_k():
    vecs = jnp.asarray(np.random.default_rng(2).standard_normal(
        (3, 4)).astype(np.float32))
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = np.ones(3, bool)
    queries = jnp.asarray(np.ones((2, 4), np.float32))
    got_v, got_i = _xla_twin(vecs, norms, jnp.asarray(valid), queries, 8)
    ref_v, ref_i = _numpy_topk(vecs, valid, queries, 8)
    assert got_v.shape == (2, 8) and got_i.shape == (2, 8)
    assert np.array_equal(got_i, ref_i)
    assert np.all(got_i[:, 3:] == -1) and np.all(np.isneginf(got_v[:, 3:]))
    np.testing.assert_allclose(got_v[:, :3], ref_v[:, :3], rtol=1e-5)


# ---------------------------------------------------------------------------
# the one rule: policy x platform x k against the kernel's cap
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", (pallas_knn.FUSED_MAX_K,
                               pallas_knn.FUSED_MAX_K + 1))
@pytest.mark.parametrize("platform", ("tpu", "cpu"))
@pytest.mark.parametrize("policy", ("auto", "pallas", "xla"))
def test_fused_impl_rule(monkeypatch, policy, platform, k):
    """The kernel when the policy forces it or is "auto" on a TPU, and k is
    within its cap; else the XLA twin; interpret only for a forced kernel
    on the CPU backend."""
    monkeypatch.setattr(pallas_knn, "jax", types.SimpleNamespace(
        devices=lambda: [types.SimpleNamespace(platform=platform)]))
    wants_kernel = policy == "pallas" or (policy == "auto"
                                          and platform == "tpu")
    if wants_kernel and k <= pallas_knn.FUSED_MAX_K:
        want = ("pallas", platform == "cpu")
    else:
        want = ("xla", False)
    assert pallas_knn.fused_impl(policy, k) == want


# ---------------------------------------------------------------------------
# interpret-mode parity vs the XLA reference, per precision x similarity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("similarity", SIMS)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_parity_interpret_vs_xla(precision, similarity):
    rng = np.random.default_rng(3)
    vecs, norms, valid, queries, _ = _operands(rng)
    _assert_pallas_matches_xla(vecs, norms, valid, queries, precision,
                               similarity)


@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_recall_vs_exact_reference(precision):
    """fp32 must reproduce the numpy reference exactly; the reduced
    precisions widen the pool then rescore in exact fp32, holding
    recall@10 == 1.0 on the clustered corpus."""
    rng = np.random.default_rng(11)
    vecs, norms, valid, queries, valid_np = _operands(rng)
    ev, ei = _numpy_topk(vecs, valid_np, queries, 10)
    fv, fi = map(np.asarray, pallas_knn.knn_fused(
        vecs, norms, valid, queries, k=10, similarity="l2_norm",
        score_precision=precision, impl="pallas", interpret=True))
    if precision == "fp32":
        assert np.array_equal(fi, ei)
        assert np.allclose(fv, ev, rtol=1e-5)
    else:
        recall = np.mean([
            len(set(fi[b]) & set(ei[b])) / 10 for b in range(fi.shape[0])])
        assert recall == 1.0, f"{precision} recall@10 {recall} < 1.0"
        # the rescore is exact fp32: same winners carry the same
        # serving-space scores the reference computed
        assert np.allclose(np.sort(fv, axis=1), np.sort(ev, axis=1),
                           atol=1e-4)


@pytest.mark.parametrize("impl", ("pallas", "xla"))
def test_fused_fewer_live_docs_than_k_pads(impl):
    rng = np.random.default_rng(5)
    n, k = 300, 16
    data = _corpus(rng, n, DIM)
    vecs = jnp.asarray(data)
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = np.zeros(n, bool)
    valid[:5] = True
    queries = jnp.asarray(_corpus(rng, 3, DIM))
    vals, ids = map(np.asarray, pallas_knn.knn_fused(
        vecs, norms, jnp.asarray(valid), queries, k=k,
        similarity="l2_norm", score_precision="fp32", impl=impl,
        interpret=True))
    assert vals.shape == (3, k) and ids.shape == (3, k)
    for b in range(3):
        assert set(ids[b, :5]) == {0, 1, 2, 3, 4}
    assert np.all(ids[:, 5:] == -1)
    assert np.all(np.isneginf(vals[:, 5:]))


def test_fused_tie_break_prefers_lower_doc_id():
    """Duplicate vectors straddling a tile boundary: the carried-first
    pool merge must reproduce lax.top_k's lowest-index tie-break."""
    rng = np.random.default_rng(7)
    for precision in PRECISIONS:
        tile = _rule_tile(1, 8, precision)
        n = tile + 64
        data = rng.standard_normal((n, 8)).astype(np.float32)
        dup = data[3].copy()
        data[tile + 11] = dup  # same vector, a later tile
        vecs = jnp.asarray(data)
        norms = jnp.sum(vecs * vecs, axis=1)
        valid = jnp.asarray(np.ones(n, bool))
        queries = jnp.asarray(dup[None, :] + 0.0)
        pv, pi = map(np.asarray, pallas_knn.knn_fused(
            vecs, norms, valid, queries, k=4, similarity="l2_norm",
            score_precision=precision, impl="pallas", interpret=True))
        xv, xi = map(np.asarray, pallas_knn.knn_fused(
            vecs, norms, valid, queries, k=4, similarity="l2_norm",
            score_precision=precision, impl="xla", interpret=True))
        assert np.array_equal(pi, xi), precision
        both = {3, tile + 11}
        assert both <= set(pi[0].tolist()), precision
        # the duplicate pair ties exactly: lower doc id must rank first
        assert list(pi[0]).index(3) < list(pi[0]).index(tile + 11), precision


# ---------------------------------------------------------------------------
# the tiling rule: every tile it can choose, at and around a tile's edge
# ---------------------------------------------------------------------------

def test_fused_tile_rule_is_a_power_of_two_dividing_the_column():
    """Read from the operands alone; a power-of-two column (what the mesh
    bundle holds) is its own multiple, so nothing is ever padded to fit."""
    seen = set()
    for b, precision in TILE_CASES:
        for d in (8, DIM, 128, 768):
            tile = _rule_tile(b, d, precision)
            seen.add(tile)
            assert tile >= pallas_knn.FK_BLOCK and tile & (tile - 1) == 0
            for n_pad in (1024, 2048, 5 * 1024, 6 * 1024, 1 << 20):
                got = _rule_tile(b, d, precision, n_pad)
                assert got & (got - 1) == 0 and n_pad % got == 0
                assert got == min(tile, n_pad & -n_pad)
    assert len(seen) > 1, "the rule never adapts"


@pytest.mark.parametrize("n_kind", N_KINDS)
@pytest.mark.parametrize("b,precision", TILE_CASES)
def test_fused_parity_over_the_rules_tiles(b, precision, n_kind):
    rng = np.random.default_rng(13)
    n = _n_for(n_kind, _rule_tile(b, DIM, precision))
    vecs, norms, valid, queries, _ = _operands(rng, n=n, b=b, n_dead=n // 20)
    _assert_pallas_matches_xla(vecs, norms, valid, queries, precision)


@pytest.mark.parametrize("n_kind", N_KINDS)
@pytest.mark.parametrize("b,precision", TILE_CASES)
def test_fused_fewer_live_than_k_over_the_rules_tiles(b, precision, n_kind):
    """Live rows only in the LAST rows of the column (past every tile edge
    but the last): k - 5 slots come out (-inf, -1), never a finite score."""
    rng = np.random.default_rng(5)
    n, k = _n_for(n_kind, _rule_tile(b, DIM, precision)), 16
    vecs = jnp.asarray(_corpus(rng, n, DIM))
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = np.zeros(n, bool)
    live = [0, 1, n // 2, n - 2, n - 1]
    valid[live] = True
    queries = jnp.asarray(_corpus(rng, b, DIM))
    vals, ids = map(np.asarray, pallas_knn.knn_fused(
        vecs, norms, jnp.asarray(valid), queries, k=k,
        similarity="l2_norm", score_precision=precision, impl="pallas",
        interpret=True))
    assert vals.shape == (b, k) and ids.shape == (b, k)
    for row in range(b):
        assert set(ids[row, :5]) == set(live)
    assert np.all(np.isfinite(vals[:, :5]))
    assert np.all(ids[:, 5:] == -1)
    assert np.all(np.isneginf(vals[:, 5:]))


@pytest.mark.parametrize("twin_in", ("next-tile", "last-tile"))
@pytest.mark.parametrize("b,precision", TILE_CASES)
def test_fused_tie_break_over_the_rules_tiles(b, precision, twin_in):
    """The same vector in the first tile and in the next one, or in the
    last of four (carried through every merge between): the lower doc id
    ranks first, as lax.top_k's lowest-index tie-break has it."""
    rng = np.random.default_rng(7)
    d = 8
    tile = _rule_tile(b, d, precision)
    n = 4 * tile
    twin = tile + 11 if twin_in == "next-tile" else n - 5
    data = rng.standard_normal((n, d)).astype(np.float32)
    dup = data[3].copy()
    data[twin] = dup
    vecs = jnp.asarray(data)
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = jnp.asarray(np.ones(n, bool))
    queries = np.repeat(dup[None, :], b, axis=0)
    queries[1:] += rng.standard_normal((b - 1, d)).astype(np.float32) * 0.01
    out = {}
    for impl in ("pallas", "xla"):
        out[impl] = np.asarray(pallas_knn.knn_fused(
            vecs, norms, valid, jnp.asarray(queries), k=4,
            similarity="l2_norm", score_precision=precision, impl=impl,
            interpret=True)[1])
    assert np.array_equal(out["pallas"], out["xla"])
    first = out["pallas"][0].tolist()
    assert {3, twin} <= set(first)
    assert first.index(3) < first.index(twin)


# ---------------------------------------------------------------------------
# the lowered launch: the column and two lane-dense rows, nothing relaid
# ---------------------------------------------------------------------------


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (pjit, shard_map, the kernel's own body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def _trace_knn_fused(n, d, b, precision):
    def f(vectors, norms_sq, valid, queries):
        return pallas_knn.knn_fused(
            vectors, norms_sq, valid, queries, k=10, similarity="l2_norm",
            score_precision=precision, impl="pallas", interpret=True)

    return jax.make_jaxpr(f)(
        jnp.zeros((n, d)), jnp.zeros((n,)), jnp.zeros((n,), bool),
        jnp.zeros((b, d))), 1


def _trace_mesh_step(n, d, b, precision):
    from jax.sharding import Mesh

    from opensearch_tpu.parallel import distributed as dist_mod

    s = 2
    step = dist_mod.build_knn_serving_step(
        Mesh(np.array(jax.devices()[:1]), ("data",)), k_shard=10,
        k_final=10, similarity="l2_norm", kernel="pallas",
        score_precision=precision, interpret=True)
    return jax.make_jaxpr(step)(
        jnp.zeros((s, n, d)), jnp.zeros((s, n)), jnp.zeros((s, n), bool),
        jnp.zeros((b, d))), s


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("trace", (_trace_knn_fused, _trace_mesh_step),
                         ids=("knn_fused", "mesh_step"))
def test_fused_launch_has_no_column_shaped_side_operand_and_no_pad(
        trace, precision):
    """Counts and shapes of the traced launch, n = 4 tiles: the kernel is
    handed the [n, d] column and two [1, n] rows; nothing anywhere in the
    program has shape [n, 1] (on a TPU one value per 128-lane tile row:
    128x the bytes, and a relayout of the whole row on every launch), and
    the column is never padded."""
    d, b = DIM, 8
    tile = _rule_tile(b, d, precision)
    n = 4 * tile
    closed, launches = trace(n, d, b, precision)
    eqns = list(_walk_eqns(closed.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == launches
    for call in calls:
        shapes = [tuple(v.aval.shape) for v in call.invars]
        assert shapes.count((n, d)) == 1
        assert shapes.count((1, n)) == 2
        assert call.params["grid_mapping"].grid == (1, 4)
    for eqn in eqns:
        for var in (*eqn.invars, *eqn.outvars):
            shape = tuple(getattr(var.aval, "shape", ()))
            assert shape != (n, 1), f"{eqn.primitive.name} makes {shape}"
        if eqn.primitive.name == "pad":
            assert eqn.outvars[0].aval.shape[0] < n, \
                f"pad to {eqn.outvars[0].aval.shape}"


def test_fused_quantize_symmetric_int8_contract():
    rng = np.random.default_rng(9)
    x = rng.standard_normal((32, DIM)).astype(np.float32) * 3
    q, scale = pallas_knn.quantize_symmetric_int8(jnp.asarray(x))
    q, scale = np.asarray(q), float(scale)
    assert q.dtype == np.int8
    assert np.max(np.abs(q)) <= 127
    assert np.allclose(q * scale, x, atol=scale)


# ---------------------------------------------------------------------------
# mesh one-launch-per-node program: parity at 1/2/4 devices
# ---------------------------------------------------------------------------


def _mesh_inputs(rng, s, n, d, b):
    vectors = rng.standard_normal((s, n, d)).astype(np.float32)
    norms = np.sum(vectors * vectors, axis=2)
    valid = rng.random((s, n)) > 0.1
    queries = rng.standard_normal((b, d)).astype(np.float32)
    return (jnp.asarray(vectors), jnp.asarray(norms),
            jnp.asarray(valid), jnp.asarray(queries))


@pytest.mark.parametrize("n_dev", (1, 2, 4))
def test_mesh_fused_parity_across_shard_counts(n_dev):
    """build_knn_serving_step with kernel="pallas" (interpret on the CPU
    sim) and the XLA reference agree bit for bit on vals/gids/counts at
    every device count, at every precision."""
    from jax.sharding import Mesh

    from opensearch_tpu.parallel import distributed as dist_mod

    devices = np.array(jax.devices()[:n_dev])
    assert devices.size == n_dev
    rng = np.random.default_rng(21)
    s, n, d, b = 4, 256, DIM, 8
    vectors, norms, valid, queries = _mesh_inputs(rng, s, n, d, b)
    mesh = Mesh(devices, ("data",))
    for precision in PRECISIONS:
        out = {}
        for kernel in ("pallas", "xla"):
            step = dist_mod.build_knn_serving_step(
                mesh, k_shard=8, k_final=10, similarity="l2_norm",
                kernel=kernel, score_precision=precision,
                interpret=True)
            out[kernel] = dist_mod.unpack(
                step(vectors, norms, valid, queries), 10, s)
        pv, pg, pc = out["pallas"]
        xv, xg, xc = out["xla"]
        assert np.array_equal(pg, xg), (n_dev, precision)
        assert np.array_equal(pc, xc), (n_dev, precision)
        if precision == "int8":
            assert np.array_equal(pv, xv), n_dev
        else:
            assert np.allclose(pv, xv, atol=1e-6), (n_dev, precision)


def _three_output_reference(vectors, norms, valid, queries, *, k_shard,
                            k_final, kernel, precision,
                            similarity="l2_norm"):
    """What the step computes, plainly: every shard scanned by `knn_fused`,
    one after the other on one device, the merge on the host in (-score,
    shard, rank) order. No mesh, no pack."""
    s, n = valid.shape
    per_v, per_g = [], []
    for si in range(s):
        v, i = map(np.asarray, pallas_knn.knn_fused(
            vectors[si], norms[si], valid[si], queries, k=k_shard,
            similarity=similarity, score_precision=precision,
            impl=kernel, interpret=kernel == "pallas"))
        per_v.append(v)
        per_g.append(np.where(i >= 0, i + si * n, -1).astype(np.int32))
    counts = np.stack([np.isfinite(v).sum(axis=-1) for v in per_v])
    all_v = np.concatenate(per_v, axis=1)              # [B, S * k_shard]
    all_g = np.concatenate(per_g, axis=1)
    pos = np.argsort(-all_v, axis=1, kind="stable")[:, :k_final]
    return (np.take_along_axis(all_v, pos, axis=1),
            np.take_along_axis(all_g, pos, axis=1),
            counts.astype(np.int32))


@pytest.mark.parametrize("n_dev", (1, 4))
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("kernel", ("pallas", "xla"))
def test_mesh_step_hands_back_one_packed_array(kernel, precision, n_dev):
    """The step's whole result is ONE int32 [B, 2 * k_final + S] array (one
    device -> host transfer a launch), and `unpack` of it is bit for bit
    the three arrays of a plain three-output reference: B < b_pad (the
    padding rows are sliced off before `unpack`, as `mesh_knn_batch`
    does), one shard with fewer valid rows than k_shard, so that
    (-inf, -1) slots and a short count cross the pack."""
    from jax.sharding import Mesh

    from opensearch_tpu.parallel import distributed as dist_mod

    rng = np.random.default_rng(30)
    s, n, d, b, b_pad, k_shard, k_final = 4, 256, DIM, 3, 4, 8, 10
    vectors, norms, valid, queries = _mesh_inputs(rng, s, n, d, b_pad)
    queries = queries.at[b:].set(0.0)
    valid = valid.at[1].set(False).at[1, jnp.array([7, 90, 201])].set(True)
    step = dist_mod.build_knn_serving_step(
        Mesh(np.array(jax.devices()[:n_dev]), ("data",)), k_shard=k_shard,
        k_final=k_final, similarity="l2_norm", kernel=kernel,
        score_precision=precision, interpret=True)
    packed = step(vectors, norms, valid, queries)
    assert isinstance(packed, jax.Array)
    assert packed.dtype == jnp.int32
    assert packed.shape == (b_pad, 2 * k_final + s)
    assert packed.is_fully_replicated
    got = dist_mod.unpack(np.asarray(packed)[:b], k_final, s)
    rv, rg, rc = _three_output_reference(
        vectors, norms, valid, queries, k_shard=k_shard, k_final=k_final,
        kernel=kernel, precision=precision)
    want = (rv[:b], rg[:b], rc[:, :b])
    for name, g, w in zip(("vals", "gids", "counts"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.ascontiguousarray(g).tobytes() == w.tobytes(), name
    vals, gids, counts = got
    assert vals.dtype == np.float32 and vals.shape == (b, k_final)
    assert counts.shape == (s, b) and (counts[1] == 3).all()
    assert (counts[[0, 2, 3]] == k_shard).all()
    # a shard's (-inf, -1) slots survive the pack: take k_final past the
    # finite winners of a step that only sees the short shard
    lone = dist_mod.build_knn_serving_step(
        Mesh(np.array(jax.devices()[:1]), ("data",)), k_shard=k_shard,
        k_final=k_shard, similarity="l2_norm", kernel=kernel,
        score_precision=precision, interpret=True)
    lv, lg, lc = dist_mod.unpack(
        lone(vectors[1:2], norms[1:2], valid[1:2], queries), k_shard, 1)
    assert np.isneginf(lv[:, 3:]).all() and np.isfinite(lv[:, :3]).all()
    assert (lc == 3).all()
    assert (lg[:, 3:] == -1).all()


@pytest.mark.parametrize("similarity", SIMS)
def test_mesh_step_on_four_devices_is_knn_fused_per_shard(similarity):
    """The program the CPU tests run is the one the chip runs, with the
    lowering the rule gives here: on 4 virtual devices it equals `knn_fused`
    shard by shard + a numpy merge, bit for bit, in every score space."""
    from jax.sharding import Mesh

    from opensearch_tpu.parallel import distributed as dist_mod

    impl, interpret = pallas_knn.fused_impl("auto", 8)
    rng = np.random.default_rng(41)
    s, n, b, k_shard, k_final = 4, 256, 4, 8, 10
    vectors, norms, valid, queries = _mesh_inputs(rng, s, n, DIM, b)
    step = dist_mod.build_knn_serving_step(
        Mesh(np.array(jax.devices()[:4]), ("data",)), k_shard=k_shard,
        k_final=k_final, similarity=similarity, kernel=impl,
        score_precision="fp32", interpret=interpret)
    got = dist_mod.unpack(step(vectors, norms, valid, queries), k_final, s)
    want = _three_output_reference(
        vectors, norms, valid, queries, k_shard=k_shard, k_final=k_final,
        kernel=impl, precision="fp32", similarity=similarity)
    for name, g, w in zip(("vals", "gids", "counts"), got, want):
        assert np.ascontiguousarray(g).tobytes() == w.tobytes(), name
    # and the scan it wraps is right: shard 2's winners against numpy
    ref_v, ref_i = _numpy_topk(vectors[2], np.asarray(valid[2]), queries,
                               k_shard, similarity)
    sv, si = map(np.asarray, pallas_knn.knn_fused(
        vectors[2], norms[2], valid[2], queries, k=k_shard,
        similarity=similarity, score_precision="fp32", impl=impl,
        interpret=interpret))
    assert np.array_equal(si, ref_i)
    np.testing.assert_allclose(sv, ref_v, rtol=1e-5)


# ---------------------------------------------------------------------------
# settings: round-trip, validation, live application, batch-key isolation
# ---------------------------------------------------------------------------


@pytest.fixture()
def exact_node(tmp_path):
    prev_peaks = roofline.current_peaks()
    roofline.set_peaks(roofline.stub_peaks(seed=3))
    n = TpuNode(tmp_path / "node")
    n.create_index("ex", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "x": {"type": "knn_vector", "dimension": DIM}}},
    })
    rng = np.random.default_rng(17)
    data = _corpus(rng, 200, DIM)
    n.bulk([
        ("index", {"_index": "ex", "_id": str(i)},
         {"x": data[i].round(3).tolist()})
        for i in range(200)
    ], refresh=True)
    n._test_data = data
    yield n
    ann_mod.default_config.configure(
        exact_kernel="auto", score_precision="fp32", kernel="auto")
    distributed_serving.enabled = True
    n.close()
    if prev_peaks is not None:
        roofline.set_peaks(prev_peaks)


def test_exact_kernel_settings_roundtrip(exact_node):
    exact_node.put_cluster_settings({"persistent": {"search": {"knn": {
        "kernel": "pallas", "score_precision": "int8"}}}})
    assert ann_mod.default_config.exact_kernel == "pallas"
    assert ann_mod.default_config.score_precision == "int8"
    st = exact_node.knn_batcher.snapshot_stats()
    assert st["ann"]["exact_kernel"] == "pallas"
    assert st["ann"]["score_precision"] == "int8"

    for bad in ({"kernel": "mosaic"}, {"score_precision": "int4"}):
        with pytest.raises(IllegalArgumentException):
            exact_node.put_cluster_settings(
                {"persistent": {"search": {"knn": bad}}})

    # null deletion restores the defaults
    exact_node.put_cluster_settings({"persistent": {"search": {"knn": {
        "kernel": None, "score_precision": None}}}})
    assert ann_mod.default_config.exact_kernel == "auto"
    assert ann_mod.default_config.score_precision == "fp32"


def test_served_fused_path_accounting(exact_node):
    """kernel=pallas on the CPU sim serves the exact path through the
    fused branch end to end: same hits as the XLA path, knn_path_stats
    counts it, the roofline recorder sees knn_fused_pallas[precision]
    with a non-zero achieved fraction, the padded query batch lands in
    the ledger's transient counters, and the steady state does not
    retrace."""
    from opensearch_tpu.telemetry.device_ledger import default_ledger

    data = exact_node._test_data
    distributed_serving.enabled = False
    try:
        body = {"size": 10, "query": {
            "knn": {"x": {"vector": data[5].tolist(), "k": 10}}}}
        truth = [h["_id"] for h in
                 exact_node.search("ex", body)["hits"]["hits"]]

        exact_node.put_cluster_settings({"persistent": {"search": {"knn": {
            "kernel": "pallas"}}}})
        fams0 = roofline.default_recorder.snapshot_stats()["families"]
        before = sum(r["launches"] for f, r in fams0.items()
                     if f.startswith("knn_fused_pallas["))
        fused_before = executor_mod.knn_path_stats["fused"]
        transients0 = default_ledger.snapshot_stats()["transient_uploads"]

        got = [h["_id"] for h in
               exact_node.search("ex", body)["hits"]["hits"]]
        assert got == truth

        assert executor_mod.knn_path_stats["fused"] > fused_before
        fams1 = roofline.default_recorder.snapshot_stats()["families"]
        after = sum(r["launches"] for f, r in fams1.items()
                    if f.startswith("knn_fused_pallas["))
        assert after > before
        assert default_ledger.snapshot_stats()["transient_uploads"] \
            > transients0

        # /_roofline ranks the family with non-zero achieved fractions
        from opensearch_tpu.rest.handlers import build_router

        router = build_router()
        handler, params = router.resolve("GET", "/_roofline")
        status, report = handler(exact_node, params, {}, None)
        assert status == 200
        rows = {r["family"]: r for r in report["families"]}
        assert "knn_fused_pallas[fp32]" in rows
        row = rows["knn_fused_pallas[fp32]"]
        assert row["achieved_gflops"] > 0
        assert 0.0 < row["roofline_fraction"] <= 1.0
        assert row["bound"] in ("memory", "compute")

        # steady state: the same shape does not retrace, and the kernel
        # row carries the policy annotations + roofline fields
        resp = exact_node.search("ex", {**body, "profile": True})

        def kernel_rows(entry):
            yield from entry.get("kernels", [])
            for child in entry.get("children", []):
                yield from kernel_rows(child)

        recs = [rec for sp in resp["profile"]["shards"]
                for entry in sp["searches"][0]["query"]
                for rec in kernel_rows(entry)
                if rec["name"] == "knn_fused_pallas"]
        assert recs, "profiled search must report the fused kernel"
        for rec in recs:
            assert rec["retraces"] == 0, "steady state must not retrace"
            assert rec["kernel"] == "pallas"
            assert rec["score_precision"] == "fp32"
    finally:
        distributed_serving.enabled = True


def _brute_ids(data, query, k, keep=None):
    d_sq = ((data.round(3).astype(np.float64) - np.asarray(query)) ** 2
            ).sum(1)
    if keep is not None:
        d_sq = np.where(keep, d_sq, np.inf)
    return [str(i) for i in np.argsort(d_sq, kind="stable")[:k]]


def test_executor_serves_every_exact_query_through_the_fused_scan(exact_node):
    """Default policy, per-shard path, a segment of any size, k under and
    over the kernel's cap: the one scan serves it (nothing else is left to),
    and the hits are brute force's."""
    data = exact_node._test_data
    distributed_serving.enabled = False
    try:
        for k in (7, 150):
            query = data[9].round(3).tolist()
            before = dict(executor_mod.knn_path_stats)
            resp = exact_node.search("ex", {"size": k, "query": {
                "knn": {"x": {"vector": query, "k": k}}}})
            after = executor_mod.knn_path_stats
            assert set(after) == {"ann", "fused"}
            assert after["fused"] == before["fused"] + 1
            assert after["ann"] == before["ann"]
            assert [h["_id"] for h in resp["hits"]["hits"]] == \
                _brute_ids(data, query, k)
    finally:
        distributed_serving.enabled = True


def test_executor_fused_scan_honours_the_knn_filter(exact_node):
    """The filter's mask is folded into `valid` BEFORE the top-k: every hit
    passes it and the hits are brute force's over the rows that pass."""
    data = exact_node._test_data
    exact_node.create_index("fx", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "x": {"type": "knn_vector", "dimension": DIM},
            "n": {"type": "long"}}},
    })
    exact_node.bulk([
        ("index", {"_index": "fx", "_id": str(i)},
         {"x": data[i].round(3).tolist(), "n": i})
        for i in range(64)
    ], refresh=True)
    query = data[40].round(3).tolist()
    body = {"size": 5, "query": {"knn": {"x": {
        "vector": query, "k": 5,
        "filter": {"range": {"n": {"lt": 20}}}}}}}
    distributed_serving.enabled = False
    try:
        fused_before = executor_mod.knn_path_stats["fused"]
        resp = exact_node.search("fx", body)
        assert executor_mod.knn_path_stats["fused"] == fused_before + 1
    finally:
        distributed_serving.enabled = True
    hits = resp["hits"]["hits"]
    assert all(h["_source"]["n"] < 20 for h in hits)
    assert [h["_id"] for h in hits] == \
        _brute_ids(data[:64], query, 5, keep=np.arange(64) < 20)


def test_mesh_path_obeys_the_kernel_k_cap(exact_node):
    """search.knn.kernel = pallas over the mesh: k within FUSED_MAX_K
    launches the kernel, k above it the XLA twin (the cap the per-shard
    path always had; before ISSUE 31 the mesh compiled a k-round merge)."""
    from opensearch_tpu.cluster.shard_mesh import default_registry

    data = exact_node._test_data
    exact_node.put_cluster_settings({"persistent": {"search": {"knn": {
        "kernel": "pallas"}}}})
    query = data[3].round(3).tolist()
    for k, want in ((10, "pallas"), (pallas_knn.FUSED_MAX_K + 22, "xla")):
        launches = default_registry.snapshot_stats()["launches"]
        resp = exact_node.search("ex", {"size": k, "query": {
            "knn": {"x": {"vector": query, "k": k}}}})
        st = default_registry.snapshot_stats()
        assert st["launches"] == launches + 1, "not served by the mesh"
        assert st["last_kernel"] == want, k
        assert [h["_id"] for h in resp["hits"]["hits"]] == \
            _brute_ids(data, query, k)


def test_mesh_serving_uses_fused_family_under_policy(exact_node):
    """A multi-shard knn search with kernel=pallas runs the fused
    shard_map program: hits identical to the host merge, the
    mesh_knn_fused roofline family fed, and the shard-mesh registry
    pinned to the serving kernel/precision."""
    from opensearch_tpu.cluster.shard_mesh import default_registry

    rng = np.random.default_rng(29)
    data = _corpus(rng, 120, DIM)
    exact_node.create_index("m4", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {
            "x": {"type": "knn_vector", "dimension": DIM}}},
    })
    exact_node.bulk([
        ("index", {"_index": "m4", "_id": str(i)},
         {"x": data[i].round(3).tolist()})
        for i in range(120)
    ], refresh=True)
    body = {"size": 10, "query": {
        "knn": {"x": {"vector": data[7].tolist(), "k": 10}}}}

    exact_node.put_cluster_settings({"persistent": {"search": {"knn": {
        "kernel": "pallas", "score_precision": "bf16"}}}})
    fams0 = roofline.default_recorder.snapshot_stats()["families"]
    before = sum(r["launches"] for f, r in fams0.items()
                 if f.startswith("mesh_knn_fused["))
    dist = exact_node.search("m4", body)

    distributed_serving.enabled = False
    try:
        host = exact_node.search("m4", body)
    finally:
        distributed_serving.enabled = True
    assert [h["_id"] for h in dist["hits"]["hits"]] == \
        [h["_id"] for h in host["hits"]["hits"]]

    fams1 = roofline.default_recorder.snapshot_stats()["families"]
    after = sum(r["launches"] for f, r in fams1.items()
                if f.startswith("mesh_knn_fused["))
    assert after > before
    st = default_registry.snapshot_stats()
    assert st["fused_launches"] > 0
    assert st["last_kernel"] == "pallas"
    assert st["last_score_precision"] == "bf16"


def test_policy_flip_never_merges_inflight_batches():
    """Keys differing ONLY in (kernel, score_precision) never share a
    launch: a live flip of search.knn.kernel or score_precision cannot
    re-rank queries already batched under the other program."""
    batcher = KnnDispatchBatcher(max_batch_size=8, max_wait_ms=300)
    seen: dict[tuple, list] = {}
    lock = threading.Lock()

    def launch_for(variant):
        def launch(payloads):
            with lock:
                seen.setdefault(variant, []).append(sorted(payloads))
            return [f"{variant[0]}/{variant[1]}:{p}" for p in payloads], False
        return launch

    variants = [("pallas", "fp32"), ("pallas", "int8"),
                ("xla", "fp32"), ("xla", "int8")]
    barrier = threading.Barrier(len(variants))
    out = {}

    def run(kernel, precision, payload):
        key = ("knn_fused", 4321, 7, 10, "l2_norm", precision, kernel)
        barrier.wait()
        out[(kernel, precision)] = batcher.dispatch(
            key, payload, launch_for((kernel, precision)),
            kind="exact").value

    threads = [
        threading.Thread(target=run, args=(k, p, f"{k}-{p}"))
        for k, p in variants
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for kernel, precision in variants:
        assert out[(kernel, precision)] == \
            f"{kernel}/{precision}:{kernel}-{precision}"
    for variant, batches in seen.items():
        for batch in batches:
            assert batch == [f"{variant[0]}-{variant[1]}"], \
                "cross-variant payloads merged into one launch"


# ---------------------------------------------------------------------------
# roofline cost models for the two new families
# ---------------------------------------------------------------------------


def test_cost_models_rank_fused_families_with_nonzero_fractions():
    rec = roofline.RooflineRecorder()
    roofline.set_peaks(roofline.stub_peaks(seed=0))
    knn_shape = dict(b=8, n=4096, d=DIM, k=10, r=40)
    rec.record("knn_fused_pallas[fp32]", 4_000_000,
               params=dict(knn_shape, precision="fp32"))
    rec.record("knn_fused_pallas[int8]", 2_500_000,
               params=dict(knn_shape, precision="int8"))
    rec.record("mesh_knn_fused[bf16]", 6_000_000, params=dict(
        s=4, n_flat=1024, d=DIM, b=8, k_shard=8, devices=4,
        precision="bf16"))
    report = rec.report()
    rows = {r["family"]: r for r in report["families"]}
    for fam in ("knn_fused_pallas[fp32]", "knn_fused_pallas[int8]",
                "mesh_knn_fused[bf16]"):
        assert fam in rows, fam
        assert rows[fam]["achieved_gflops"] > 0, fam
        assert 0.0 < rows[fam]["roofline_fraction"] <= 1.0, fam
        assert rows[fam]["bound"] in ("memory", "compute")
    losses = [r["lost_ms"] for r in report["families"]]
    assert losses == sorted(losses, reverse=True)
    # the reduced-precision byte model charges the per-launch quantize
    # pass (prep read+write and the rescore gather), so int8 carries a
    # HIGHER modeled byte floor than fp32 — the model is honest about
    # nothing being cached across launches
    int8 = rows["knn_fused_pallas[int8]"]
    fp32 = rows["knn_fused_pallas[fp32]"]
    assert int8["bytes"] > fp32["bytes"]


# ---------------------------------------------------------------------------
# the driver contract points at the served program
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("call", ("check_entry()", "dryrun_multichip(4)"))
def test_graft_entry_runs_the_served_program(call):
    """`entry()` jits (knn_fused, the platform's lowering) and the dry run
    serves a 4-shard search over 4 virtual CPU devices; each in a child of
    its own, as `__graft_entry__`'s docstring prescribes."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", f"import __graft_entry__ as g; g.{call}"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_NUM_CPU_DEVICES": "4"})
    assert done.returncode == 0, done.stderr[-2000:]
    assert " OK" in done.stdout
