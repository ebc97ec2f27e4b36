"""Serving-path microbenchmark: end-to-end `_search` QPS through TpuNode.

Unlike bench.py (which times the raw fused programs), this drives the REAL
serving stack — REST-body parse, query DSL, the distributed device merge
(search/distributed_serving), fetch phase, response building — the analog
of the reference's whole-request benchmark (ContextIndexSearcher.search +
SearchPhaseController merge + fetch), not just its scorer.

Measures, on one in-process node (4 shards to exercise the cross-shard
merge):
  serving_knn_qps          one knn _search at a time (B=1 device dispatch)
  serving_msearch_qps      B knn sub-searches per msearch → ONE batched
                           device dispatch (round-5 widening)
  serving_filtered_knn_qps filtered knn (mask folded into the device program)

Run: python benchmarks/serving_micro.py [n_docs] (default 20_000)
Prints one JSON line per metric.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main() -> None:
    n_docs = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000
    dims = 64
    k = 10
    batch = 16          # msearch sub-searches per request
    import tempfile

    import jax

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import distributed_serving

    platform = jax.devices()[0].platform

    tmp = tempfile.mkdtemp(prefix="serving_micro_")
    node = TpuNode(tmp)
    node.create_index("vecs", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": dims, "space_type": "l2"},
            "n": {"type": "long"},
        }},
    })
    rng = np.random.default_rng(11)
    ops = []
    for i in range(n_docs):
        ops.append(("index", {"_index": "vecs", "_id": f"d{i}"},
                    {"v": rng.standard_normal(dims).astype(np.float32).tolist(),
                     "n": i}))
        if len(ops) == 2_000:
            node.bulk(ops)
            ops = []
    if ops:
        node.bulk(ops)
    node.refresh("vecs")

    queries = rng.standard_normal((256, dims)).astype(np.float32)

    def body(q, flt=None):
        spec = {"vector": q.tolist(), "k": k}
        if flt is not None:
            spec["filter"] = flt
        return {"query": {"knn": {"v": spec}}, "size": k}

    def timed(fn, reps):
        fn()  # warmup (compiles + populates the bundle cache)
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls))

    out = []

    # --- one knn search per request ---
    qi = iter(range(10**9))
    wall = timed(lambda: node.search(
        "vecs", body(queries[next(qi) % 256])), reps=20)
    out.append({"metric": "serving_knn_qps", "value": round(1.0 / wall, 1),
                "unit": "requests/s", "p50_ms": round(wall * 1e3, 2)})

    # --- batched msearch: B sub-searches, ONE device dispatch ---
    def msearch_once():
        base = next(qi) % 128
        searches = [({"index": "vecs"}, body(queries[base + j]))
                    for j in range(batch)]
        before = distributed_serving.stats["distributed_searches"]
        resp = node.msearch(searches)
        assert len(resp["responses"]) == batch
        assert distributed_serving.stats["distributed_searches"] == before + 1, \
            "msearch did not batch into one dispatch"

    wall = timed(msearch_once, reps=10)
    out.append({"metric": "serving_msearch_knn_qps",
                "value": round(batch / wall, 1),
                "unit": "queries/s", "batch": batch,
                "p50_batch_ms": round(wall * 1e3, 2)})

    # --- filtered knn through the device program ---
    flt = {"range": {"n": {"lt": n_docs // 2}}}
    wall = timed(lambda: node.search(
        "vecs", body(queries[next(qi) % 256], flt)), reps=10)
    assert distributed_serving.stats["filtered"] > 0
    out.append({"metric": "serving_filtered_knn_qps",
                "value": round(1.0 / wall, 1),
                "unit": "requests/s", "p50_ms": round(wall * 1e3, 2)})

    for line in out:
        line["platform"] = platform
        line["n_docs"] = n_docs
        print(json.dumps(line))


if __name__ == "__main__":
    from opensearch_tpu.bootstrap import configure_compile_cache

    configure_compile_cache()
    main()
