"""The served Pallas kernels must lower — and compile — for a TPU, checked
without a chip.

Interpret mode checks a kernel's arithmetic and nothing about whether a TPU
can run it: block shapes, casts and relayouts are only judged when the
kernel is lowered for the `tpu` platform. Two layers, both on this CPU:

 1. `jax.export` for platforms=["tpu"] runs the Pallas -> Mosaic lowering.
    It refuses block shapes that break the (8, 128) tiling rule and casts
    Mosaic has no rule for — the three refusals PR 21 met in
    `ops/pallas_adc` were all of this kind.
 2. A compile-only TPU client (`jax.experimental.topologies`, from the
    installed libtpu, no device) runs the Mosaic compiler itself on the
    same programs: layout inference, VMEM allocation, unsupported reshapes.
    Skipped where libtpu cannot describe a v5e topology.

Neither replaces the compile and the run on the chip (`chip_smoke.py`):
a kernel can compile and still answer wrongly, and the chip's own libtpu
is the one that counts.

Shapes are the ones `chip_smoke.py` launches: d = 128, k = 10; exact scan
over n = 2^20 (phase A) and 2^18 rows; B in {1, 8, 16}; IVF-PQ defaults
(nlist 128, m 8, ks 256) with nprobe 8 and 32. And the one the benchmark's
`hybrid-bm25-knn` launches (PR 37): the exact scan at d = 768, the width
text is embedded with, over 2^18 rows, fp32, B in {1, 8}; and the one
`knn-nested-chunks` launches: the nested scan (`knn_nested`, the same
kernel whose pool holds documents) over 2^19 chunk rows at d = 768, B in
{1, 8, 32}. The hybrid cell's lexical
half is no Pallas kernel but one XLA program (`ops/bm25.bm25_term_scores`,
PR 38): layer 2 compiles it at the cell's shapes (2^25 posting entries,
2^18 rows, rows of `executor.BM25_CHUNK` entries, the ladder's least and
greatest rungs of term rows, with and without the count column) and reads
the compiled program: no element gather over a posting column may be left
in it. On the CPU its lowered HLO is read too: one scatter-add uncounted,
two counted, and no gather of an `[n_pad]` column (each posting's document
length is read from a posting column).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

jnp = pytest.importorskip("jax.numpy")
import jax

from opensearch_tpu.ops import bm25, ivfpq, pallas_adc, pallas_knn
from opensearch_tpu.search import executor

REPO = Path(__file__).resolve().parent.parent
D, K = 128, 10
PRECISIONS = ("fp32", "bf16", "int8")
BATCHES = (1, 8, 16)


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def knn_fused_case(n: int, b: int, precision: str, sharding=None,
                   d: int = D):
    def f(vectors, norms_sq, valid, queries):
        return pallas_knn.knn_fused(
            vectors, norms_sq, valid, queries, k=K, similarity="l2_norm",
            score_precision=precision, impl="pallas", interpret=False)

    s = sharding
    return f, (_sds((n, d), jnp.float32, s), _sds((n,), jnp.float32, s),
               _sds((n,), jnp.bool_, s), _sds((b, d), jnp.float32, s))


def knn_nested_case(n: int, b: int, sharding=None, d: int = 768):
    def f(vectors, norms_sq, valid, parent, queries):
        return pallas_knn.knn_nested(
            vectors, norms_sq, valid, parent, queries, k=K, n_docs=n // 4,
            similarity="l2_norm", impl="pallas", interpret=False)

    s = sharding
    return f, (_sds((n, d), jnp.float32, s), _sds((n,), jnp.float32, s),
               _sds((n,), jnp.bool_, s), _sds((n,), jnp.int32, s),
               _sds((b, d), jnp.float32, s))


def adc_case(b: int, nprobe: int, precision: str, sharding=None,
             n: int = 1 << 18, l_pad: int = 4096):
    nlist, m, ks = ivfpq.DEFAULT_NLIST, ivfpq.DEFAULT_M, ivfpq.DEFAULT_KS
    k_bucket = 16 if nprobe == ivfpq.DEFAULT_NPROBE else 32

    def f(*args):
        return pallas_adc.fused_adc_search(
            *args, k=k_bucket, rerank=ivfpq.default_rerank(k_bucket),
            similarity="l2_norm", adc_precision=precision,
            use_pallas=True, interpret=False)

    s = sharding
    return f, (
        _sds((nlist, D), jnp.float32, s),
        _sds((m, ks, D // m), jnp.float32, s),
        _sds((nlist, l_pad, m), jnp.uint8, s),
        _sds((nlist, l_pad), jnp.int32, s),
        _sds((nlist, l_pad), jnp.bool_, s),
        _sds((n, D), jnp.float32, s), _sds((n,), jnp.float32, s),
        _sds((n,), jnp.bool_, s),
        _sds((b, D), jnp.float32, s), _sds((b, nprobe), jnp.int32, s))


def all_cases(sharding=None):
    for b in (1, 8):
        yield (f"knn_fused[fp32] n={1 << 18} d=768 B={b}",
               *knn_fused_case(1 << 18, b, "fp32", sharding, d=768))
    for b in (1, 8, 32):
        yield (f"knn_nested[fp32] n={1 << 19} d=768 B={b}",
               *knn_nested_case(1 << 19, b, sharding))
    for precision in PRECISIONS:
        for b in BATCHES:
            for n in (1 << 20, 1 << 18):
                yield (f"knn_fused[{precision}] n={n} B={b}",
                       *knn_fused_case(n, b, precision, sharding))
            for nprobe in (8, 32):
                yield (f"fused_adc_search[{precision}] nprobe={nprobe} B={b}",
                       *adc_case(b, nprobe, precision, sharding))


CASES = list(all_cases())


@pytest.mark.parametrize("name,f,args", CASES, ids=[c[0] for c in CASES])
def test_served_kernel_lowers_for_tpu(name, f, args):
    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


def test_mosaic_compiles_served_kernels_for_v5e():
    """Layer 2, in a child: loading libtpu for a compile-only client is
    kept out of the test process, and a libtpu that stalls looking for a
    TPU host costs a skip, not the suite."""
    try:
        proc = subprocess.run(
            [sys.executable, __file__], capture_output=True, text=True,
            timeout=240, cwd=str(REPO),
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": str(REPO)})
    except subprocess.TimeoutExpired:
        pytest.skip("compile-only TPU client did not answer in 240s")
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"no report from the compile child:\n{proc.stderr[-2000:]}"
    report = json.loads(lines[-1])
    if "skip" in report:
        pytest.skip(report["skip"])
    assert report["compiled"] > 0
    assert report["failed"] == {}, json.dumps(report["failed"], indent=1)


def bm25_case(rows: int, sharding=None, postings: int = 1 << 25,
              n: int = 1 << 18, window: int = executor.BM25_CHUNK,
              counts: bool = True):
    def f(*args):
        return bm25.bm25_term_scores(*args, n_pad=n, window=window,
                                     counts=counts)

    s = sharding
    return f, (_sds((postings,), jnp.int32, s), _sds((postings,), jnp.float32, s),
               _sds((postings,), jnp.float32, s), _sds((rows,), jnp.int32, s),
               _sds((rows,), jnp.int32, s), _sds((rows,), jnp.float32, s),
               _sds((), jnp.float32, s))


@pytest.mark.parametrize("counts", [False, True])
def test_the_bm25_launch_scatters_once_a_column_and_gathers_no_doc_column(
        counts):
    """The launch's HLO as lowered on this CPU: a scatter-add into the score
    column, and into the count column only where the caller counts; every
    gather is a row's slice of a `[P_pad]` posting column, none reads the
    `[n_pad]` doc-length column."""
    postings, n, window, rows = 4096, 256, 128, 8     # P_pad != n_pad
    f, args = bm25_case(rows, postings=postings, n=n, window=window,
                        counts=counts)
    hlo = jax.jit(f).trace(*args).lower().as_text(dialect="hlo")
    shape = dict(re.findall(r"^\s*(?:ROOT )?([\w.-]+) = \w+\[([\d,]*)\]",
                            hlo, re.M))
    ops = re.findall(r" = \w+\[[\d,]*\]\{[\d,]*\} (\w+)\(([\w.-]+)", hlo)
    scatters = [operand for op, operand in ops if op == "scatter"]
    gathers = [shape[operand] for op, operand in ops if op == "gather"]
    assert [shape[operand] for operand in scatters] == [str(n)] * (1 + counts)
    assert gathers == [str(postings)] * 3
    assert hlo.count("slice_sizes={128}") == 3


def _posting_column_gathers(hlo: str, postings: int = 1 << 25) -> list[str]:
    """The compiled program's gathers whose operand is a posting column
    (HLO names an operand; its shape stands where it is defined)."""
    shape = dict(re.findall(r"(%[\w.-]+) = \w+\[([\d,]*)\]", hlo))
    return [ln.strip()[:200] for ln in hlo.splitlines()
            for operand in re.findall(r" gather\((%[\w.-]+),", ln)
            if shape.get(operand) == str(postings)]


def _compile_all_for_v5e() -> dict:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topology = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu / no such topology
        return {"skip": f"no compile-only v5e topology: {str(e)[:200]}"}
    sharding = SingleDeviceSharding(topology.devices[0])
    failed, compiled = {}, 0
    lexical = {f"bm25_term_scores rows={rows} counts={counts}":
               bm25_case(rows, sharding, counts=counts)
               for rows in (executor.BM25_ROW_RUNGS[0],
                            executor.BM25_ROW_RUNGS[-1])
               for counts in (True, False)}
    cases = [*all_cases(sharding),
             *((name, f, args) for name, (f, args) in lexical.items())]
    for name, f, args in cases:
        try:
            program = jax.jit(f).trace(*args).lower(
                lowering_platforms=("tpu",)).compile()
            compiled += 1
        except Exception as e:  # noqa: BLE001 - reported per kernel
            failed[name] = str(e)[:600]
            continue
        if name in lexical and (
                gathers := _posting_column_gathers(program.as_text())):
            failed[name] = f"a posting column is gathered: {gathers}"
    return {"compiled": compiled, "failed": failed}


if __name__ == "__main__":
    print(json.dumps(_compile_all_for_v5e()))
