"""Kernel-level exact k-NN timing on one device vs a numpy-CPU baseline.

BASELINE config #1 shape (SIFT-1M-class: 1M x 128-d, L2, script-score exact
k-NN, single shard), autotuned across the two exact fused programs:
 - "materializing": ops/fused.knn_topk (full [B, n] scores + blockwise
   top-k)
 - "streaming": ops/fused.knn_topk_streaming (corpus-chunked scan with a
   running [B, k] state; never materializes [B, n])

This is a bare call into ops/fused — no REST, executor, batcher or mesh —
and so is not a measurement of the served system (`chip_smoke.py` drives
that; the workloads benchmark of ROADMAP queue 1 item 1 will measure it).

One process per chip: this file is a PARENT that never imports jax; all
jax work runs in child processes, one at a time, under subprocess
timeouts. The parent first PROBES the default backend with a short
watchdog (a tiny matmul). A probe that fails, times out, or finds only a
CPU when JAX_PLATFORMS did not ask for one ends the run: a `bench_error`
line and a non-zero exit. There is no fallback device and no re-emitted
result — every line printed was measured by this invocation and names
the platform it ran on. A fresh result is also filed under its platform
in BENCH_CACHE.json, which only `--gate` reads, as its baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Measurement notes (child):
- corpus generated ON device, padded to 2^20 rows so power-of-two block
  sizes divide it exactly;
- every timed wall includes result materialization to host (np.asarray),
  which is the fence;
- throughput is ONE dispatch processing 16x500-query chunks (lax.map), so
  per-dispatch fixed cost is paid once per 8,000 queries;
- the CPU baseline is a BLAS exact scan over a device-pulled subsample
  (stand-in for FAISS-CPU flat), which also provides the recall
  reference; both fused paths are exact incl. doc-id tie-break, so
  recall must be 1.0.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

CACHE = Path(__file__).resolve().parent / "BENCH_CACHE.json"
PROFILE_OUT = Path(__file__).resolve().parent / "BENCH_PROFILE.json"
CONCURRENCY_OUT = Path(__file__).resolve().parent / "BENCH_CONCURRENCY.json"
MESH_OUT = Path(__file__).resolve().parent / "BENCH_MESH.json"
BUDGET_S = int(os.environ.get("BENCH_BUDGET_S", "1100"))
PROBE_S = int(os.environ.get("BENCH_PROBE_S", "90"))
PROFILE_BUDGET_S = int(os.environ.get("BENCH_PROFILE_BUDGET_S", "600"))
CONCURRENCY_BUDGET_S = int(os.environ.get("BENCH_CONC_BUDGET_S", "900"))
CONC_CLIENTS = int(os.environ.get("BENCH_CONC_CLIENTS", "16"))
CONC_QUERIES = int(os.environ.get("BENCH_CONC_QUERIES", "125"))


def _assert_ledger_identity() -> None:
    """Gate-child epilogue: the device-residency ledger's accounting
    identity (resident == allocated − freed == sum of live bytes) must
    hold after a full bench workload — a broken identity fails the gate
    here, not in a later session's stats mystery (ISSUE 10)."""
    from opensearch_tpu.telemetry.device_ledger import default_ledger

    default_ledger.verify_identity()


def _load_book(path: Path) -> dict:
    """Platform-keyed result book (BENCH_MESH.json); corrupt == fresh."""
    if not path.exists():
        return {}
    try:
        return json.loads(path.read_text())
    except Exception:  # noqa: BLE001 - corrupt book == fresh book
        return {}


def _run(args: list, timeout_s: int, platform_env=None, extra_env=None):
    """Run a child mode; return (last JSON dict or None, failure reason)."""
    env = os.environ.copy()
    if platform_env:
        env["JAX_PLATFORMS"] = platform_env
    if extra_env:
        env.update(extra_env)
    try:
        proc = subprocess.run(
            [sys.executable, __file__] + args,
            stdout=subprocess.PIPE,  # stderr passes through: a child's
            timeout=timeout_s,       # backend warnings stay visible
            env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"child exceeded {timeout_s}s watchdog and was killed"
    except Exception as e:  # noqa: BLE001
        return None, str(e)[:200]
    line = None
    for cand in reversed(proc.stdout.decode().splitlines()):
        cand = cand.strip()
        if cand.startswith("{"):
            line = cand
            break
    if line is None:
        return None, f"child exited {proc.returncode} without a result"
    try:
        parsed = json.loads(line)
    except Exception:  # noqa: BLE001
        return None, "child emitted unparseable output"
    if parsed.get("metric") == "bench_error":
        return None, str(parsed.get("detail", "child error"))[:200]
    if proc.returncode != 0:
        return None, f"child exited {proc.returncode}"
    return parsed, None


def parent() -> int:
    t_start = time.monotonic()
    forced_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"

    fresh = None
    if forced_cpu:  # an explicit request, labelled "platform": "cpu"
        fresh, reason = _run(["--child"], BUDGET_S)
    else:
        probe, probe_err = _run(["--probe"], PROBE_S)
        if probe is not None and probe.get("platform") not in (None, "cpu"):
            remaining = max(60, BUDGET_S - int(time.monotonic() - t_start))
            fresh, reason = _run(["--child"], remaining)
        else:
            reason = f"accelerator probe failed: {probe_err or probe}"

    if fresh is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": reason or "unknown failure",
        }))
        return 1
    book = _load_book(CACHE)  # --gate's baseline, keyed by platform
    book[fresh.get("platform", "cpu")] = fresh
    CACHE.write_text(json.dumps(book, indent=1) + "\n")
    print(json.dumps(fresh))
    return 0


GATE_BUDGET_S = int(os.environ.get("BENCH_GATE_BUDGET_S", "300"))
# CPU-backend-aware tolerances: shared-container CPU throughput is noisy
# (co-tenancy, turbo states), so the CPU gate only fails on a clearly real
# regression; TPU numbers are tighter. Override per-run with
# BENCH_GATE_TOLERANCE=0.3 etc.
GATE_TOLERANCE = {"cpu": 0.45, "tpu": 0.25}


def gate_parent() -> int:
    """`bench.py --gate`: the check.sh perf-regression gate. Runs a QUICK
    same-shape measurement (streaming variant only, reduced reps) in a
    watchdogged child and compares against the SAME PLATFORM's entry in
    BENCH_CACHE.json. Exits 1 when fresh QPS falls below
    cached * (1 - tolerance) — a PR that slows the hot path fails visibly
    instead of silently. No cached entry for the platform => pass with a
    note (nothing to ratchet against)."""
    platform = _detect_platform()
    fresh, reason = _run(
        ["--gate-child"], GATE_BUDGET_S,
        platform_env="cpu" if platform == "cpu" else None,
    )
    if fresh is None:
        print(json.dumps({
            "metric": "bench_gate", "value": 0, "unit": "error",
            "vs_baseline": 0,
            "detail": f"gate child failed: {reason}", "ok": False,
        }))
        return 1
    out, ok = _gate_compare(
        "bench_gate", fresh.get("value", 0), _load_book(CACHE).get(platform),
        platform, "hot-path regression")
    print(json.dumps(out))
    return 0 if ok else 1


def _detect_platform() -> str:
    """cpu unless a probe child sees a real accelerator; JAX_PLATFORMS=cpu
    short-circuits the probe (the tests/CI configuration)."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return "cpu"
    probe, _probe_err = _run(["--probe"], PROBE_S)
    if probe is not None and probe.get("platform") not in (None, "cpu"):
        return "tpu"
    return "cpu"


def _gate_compare(metric: str, fresh_value, cached: dict | None,
                  platform: str, what: str) -> tuple[dict, bool]:
    """Shared floor check for the regression gates: fresh QPS must stay
    within the platform tolerance of the cached same-platform baseline.
    No baseline => pass with a note (nothing to ratchet against)."""
    tol = float(os.environ.get(
        "BENCH_GATE_TOLERANCE", GATE_TOLERANCE.get(platform, 0.45)))
    out = {
        "metric": metric, "unit": "queries/s", "platform": platform,
        "value": fresh_value, "vs_baseline": 0, "tolerance": tol,
    }
    if cached is None or not cached.get("value"):
        out.update({"ok": True,
                    "detail": f"no cached {platform} baseline to gate "
                              f"against"})
        return out, True
    floor = float(cached["value"]) * (1.0 - tol)
    ok = float(fresh_value or 0) >= floor
    out.update({
        "cached": cached["value"], "floor": round(floor, 1), "ok": ok,
        "vs_baseline": round(float(fresh_value or 0)
                             / float(cached["value"]), 3),
    })
    if not ok:
        out["detail"] = (
            f"{what}: fresh {fresh_value} qps < floor "
            f"{round(floor, 1)} (cached {cached['value']} - {tol:.0%})")
    return out, ok


def gate_child() -> None:
    """Reduced same-shape measurement for the gate: the streaming fused
    kNN scan (the cached CPU baseline's winning variant) over the same
    corpus shape as child(), fewer reps, no recall/baseline section."""
    jax = _child_jax()
    import functools

    import jax.numpy as jnp
    import numpy as np

    from opensearch_tpu.ops.fused import knn_topk_streaming

    d, k = 128, 10
    chunk_q = 500
    rng = np.random.default_rng(7)
    platform = jax.devices()[0].platform
    on_cpu = platform == "cpu"
    n = 1_000_000 if not on_cpu else 100_000
    n_pad = 1 << (n - 1).bit_length()

    key = jax.random.PRNGKey(7)
    vectors = jax.random.normal(key, (n, d), dtype=jnp.float32)
    vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
    norms = jnp.sum(vectors * vectors, axis=-1)
    valid = jnp.arange(n_pad) < n

    f = functools.partial(knn_topk_streaming, k=k, similarity="l2_norm",
                          chunk=32_768)

    def run(v, nrm, ok, qs):
        return jax.lax.map(lambda q: f(v, nrm, ok, q), qs)

    jfn = jax.jit(run)
    n_chunks = 16 if not on_cpu else 4
    qs = jnp.asarray(
        rng.standard_normal((n_chunks, chunk_q, d)).astype(np.float32))
    total_q = n_chunks * chunk_q
    np.asarray(jfn(vectors, norms, valid, qs)[0])  # compile + warm
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jfn(vectors, norms, valid, qs)[0])
        walls.append(time.perf_counter() - t0)
    wall = float(np.median(walls))
    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"gate_knn_qps_{n // 1000}k_{d}d_top{k}",
        "value": round(total_q / wall, 1),
        "unit": "queries/s",
        "vs_baseline": 0,
        "platform": platform,
        "variant": "streaming_32k",
    }))


def profile_parent() -> int:
    """`bench.py --profile`: run ONE profiled query per workload in a
    child (same subprocess watchdog scheme as the QPS bench) and write the
    kernel-time/transfer-bytes breakdown to BENCH_PROFILE.json next to the
    BENCH json — future perf PRs diff this file to attribute regressions
    to a kernel, a transfer, or a retrace."""
    result, reason = _run(["--profile-child"], PROFILE_BUDGET_S)
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"profile child failed: {reason}",
        }))
        return 1
    try:
        PROFILE_OUT.write_text(json.dumps(result, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0


def profile_child() -> None:
    """Build a small two-workload corpus (BM25 text + exact kNN vectors)
    through the real node API and run one `"profile": true` search per
    workload; emit the per-workload device-time/transfer/retrace rollup."""
    import tempfile

    _child_jax()
    from opensearch_tpu.node import TpuNode

    d, n_docs = 64, 3_000
    import numpy as np

    rng = np.random.default_rng(11)
    node = TpuNode(Path(tempfile.mkdtemp(prefix="bench_profile_")))
    node.create_index("bench", {"mappings": {"properties": {
        "msg": {"type": "text"},
        "v": {"type": "knn_vector", "dimension": d},
    }}})
    words = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta"]
    for i in range(n_docs):
        node.index_doc("bench", str(i), {
            "msg": " ".join(rng.choice(words, 5).tolist()),
            "v": rng.standard_normal(d).astype(np.float32).tolist(),
        })
    node.refresh("bench")

    workloads = {
        "bm25_match": {"query": {"match": {"msg": "alpha beta"}}},
        "exact_knn": {"query": {"knn": {"v": {
            "vector": rng.standard_normal(d).astype(np.float32).tolist(),
            "k": 10,
        }}}},
    }
    out_workloads = {}
    for name, body in workloads.items():
        # warm pass first so the recorded run reflects steady state; the
        # warm pass's retrace flag is reported separately
        warm = node.search("bench", {**body, "profile": True})
        cold_shard = warm["profile"]["shards"][0]
        resp = node.search("bench", {**body, "profile": True})
        shard = resp["profile"]["shards"][0]
        kernels: dict[str, dict] = {}

        def walk(ops):
            for op in ops:
                for k in op.get("kernels", []):
                    cell = kernels.setdefault(k["name"], {
                        "calls": 0, "time_in_nanos": 0, "transfer_bytes": 0})
                    cell["calls"] += k["calls"]
                    cell["time_in_nanos"] += k["time_in_nanos"]
                    cell["transfer_bytes"] += k["transfer_bytes"]
                walk(op.get("children", []))

        walk(shard["searches"][0]["query"])
        out_workloads[name] = {
            "took_ms": resp["took"],
            "tpu": shard["tpu"],
            "cold_tpu": cold_shard["tpu"],
            "kernels": kernels,
        }
    import jax

    print(json.dumps({
        "metric": "profile_breakdown",
        "value": sum(w["tpu"]["device_time_in_nanos"]
                     for w in out_workloads.values()),
        "unit": "device_nanos_total",
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
        "corpus": {"docs": n_docs, "dim": d},
        "workloads": out_workloads,
    }))


MESH_BUDGET_S = int(os.environ.get("BENCH_MESH_BUDGET_S", "900"))
MESH_SHARDS = int(os.environ.get("BENCH_MESH_SHARDS", "8"))
MESH_CLIENTS = int(os.environ.get("BENCH_MESH_CLIENTS", "8"))
MESH_QUERIES = int(os.environ.get("BENCH_MESH_QUERIES", "40"))


def _mesh_env(platform: str) -> dict:
    """On the CPU backend, simulate the 8-device node the mesh shards
    over (the MULTICHIP harness's recipe); a real accelerator keeps its
    own device set."""
    if platform != "cpu":
        return {}
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={MESH_SHARDS}"
    if want in flags:
        return {}
    return {"XLA_FLAGS": (flags + " " + want).strip()}


def mesh_parent() -> int:
    """`bench.py --mesh`: multi-shard CLUSTER-MODE kNN bench — one
    single-node ClusterServer, MESH_SHARDS shards, MESH_CLIENTS concurrent
    clients, shard-mesh launch ON vs the serialized per-shard baseline
    (distributed_serving disabled). Writes BENCH_MESH.json keyed by
    platform; the headline value is mesh-on QPS, vs_baseline the speedup
    over the per-shard loop at equal (verified 1.0) recall."""
    platform = _detect_platform()
    result, reason = _run(["--mesh-child"], MESH_BUDGET_S,
                          platform_env="cpu" if platform == "cpu" else None,
                          extra_env=_mesh_env(platform))
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"mesh child failed: {reason}",
        }))
        return 1
    book = _load_book(MESH_OUT)
    book[result.get("platform", "cpu")] = result
    try:
        MESH_OUT.write_text(json.dumps(book, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0


def mesh_gate_parent() -> int:
    """`bench.py --mesh-gate`: the check.sh regression gate for the
    shard-mesh path — a QUICK mesh run must stay within the platform
    tolerance of BENCH_MESH.json's entry (same contract as the streaming
    gate). No recorded baseline => pass with a note."""
    platform = _detect_platform()
    result, reason = _run(
        ["--mesh-child"], MESH_BUDGET_S,
        platform_env="cpu" if platform == "cpu" else None,
        extra_env={**_mesh_env(platform), "BENCH_MESH_QUERIES": "12"},
    )
    if result is None:
        print(json.dumps({
            "metric": "mesh_gate", "value": 0, "unit": "error",
            "vs_baseline": 0,
            "detail": f"mesh gate child failed: {reason}", "ok": False,
        }))
        return 1
    out, ok = _gate_compare(
        "mesh_gate", result.get("value", 0),
        _load_book(MESH_OUT).get(platform), platform,
        "shard-mesh regression")
    print(json.dumps(out))
    return 0 if ok else 1


def _free_ports(n: int) -> list:
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def mesh_child() -> None:
    """One single-node cluster server, MESH_SHARDS shards of exact-kNN
    vectors, MESH_CLIENTS concurrent clients through the facade (the HTTP
    handlers' API): measure QPS with the shard-mesh launch ON (one
    search[node] -> one shard_map launch over all shards) vs OFF (the
    serialized per-shard Python loop + host merge), and verify recall
    parity (identical top-k ids) between the two paths."""
    import asyncio
    import tempfile
    import threading

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.search import distributed_serving
    from opensearch_tpu.server import ClusterServer

    platform = jax.devices()[0].platform
    n_devices = len(jax.devices())
    d = 64
    docs_per_shard = 1_200 if platform == "cpu" else 16_000
    n_docs = MESH_SHARDS * docs_per_shard
    n_queries = int(os.environ.get("BENCH_MESH_QUERIES", MESH_QUERIES))

    tport, hport = _free_ports(2)
    tmp = tempfile.mkdtemp(prefix="bench_mesh_")
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    server = ClusterServer(
        "n0", Path(tmp) / "n0", "127.0.0.1", tport, hport,
        {"n0": ("127.0.0.1", tport)}, loop=loop,
    )
    asyncio.run_coroutine_threadsafe(
        server.start(bootstrap=["n0"]), loop).result(60)
    deadline = time.monotonic() + 60
    while not server.node.is_leader:
        if time.monotonic() > deadline:
            raise RuntimeError("single-node cluster never elected itself")
        time.sleep(0.05)
    facade = server.facade

    facade.create_index("mesh", {
        "settings": {"number_of_shards": MESH_SHARDS,
                     "number_of_replicas": 0},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": d, "space_type": "l2"},
        }},
    })
    rng = np.random.default_rng(23)
    chunk = 2_000
    for start in range(0, n_docs, chunk):
        ops = [
            ("index", {"_index": "mesh", "_id": str(i)},
             {"v": rng.standard_normal(d).astype(np.float32).tolist()})
            for i in range(start, min(start + chunk, n_docs))
        ]
        resp = facade.bulk(ops)
        if resp.get("errors"):
            raise RuntimeError(f"bulk errors at {start}")
    facade.refresh("mesh")

    queries = [
        rng.standard_normal(d).astype(np.float32).tolist()
        for _ in range(MESH_CLIENTS * n_queries)
    ]

    def knn_body(q):
        return {"size": 10,
                "query": {"knn": {"v": {"vector": q, "k": 10}}}}

    def run_config(mesh_on: bool) -> dict:
        distributed_serving.enabled = mesh_on
        before = distributed_serving.stats["distributed_searches"]
        # warm: compile the program shapes this config uses (and upload
        # the resident slabs for the mesh config)
        for q in queries[:2]:
            facade.search("mesh", knn_body(q))
        lat: list[list[float]] = [[] for _ in range(MESH_CLIENTS)]
        barrier = threading.Barrier(MESH_CLIENTS + 1)

        def client(ci: int) -> None:
            mine = queries[ci * n_queries:(ci + 1) * n_queries]
            barrier.wait()
            for q in mine:
                t0 = time.perf_counter()
                facade.search("mesh", knn_body(q))
                lat[ci].append(time.perf_counter() - t0)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(MESH_CLIENTS)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        flat = sorted(x for chunk_ in lat for x in chunk_)
        return {
            "mesh_enabled": mesh_on,
            "clients": MESH_CLIENTS,
            "queries_per_client": n_queries,
            "qps": round(len(flat) / wall, 1),
            "p50_ms": round(1000 * flat[len(flat) // 2], 2),
            "p99_ms": round(1000 * flat[int(len(flat) * 0.99)], 2),
            "mesh_launches": (
                distributed_serving.stats["distributed_searches"] - before),
        }

    # recall parity first (both paths are exact; ids must agree)
    agree = 0
    sample = queries[:16]
    for q in sample:
        distributed_serving.enabled = True
        mesh_ids = [h["_id"] for h in
                    facade.search("mesh", knn_body(q))["hits"]["hits"]]
        distributed_serving.enabled = False
        host_ids = [h["_id"] for h in
                    facade.search("mesh", knn_body(q))["hits"]["hits"]]
        agree += mesh_ids == host_ids
    recall = agree / len(sample)

    off = run_config(False)
    on = run_config(True)
    distributed_serving.enabled = True

    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"mesh_knn_qps_{MESH_SHARDS}shards_{MESH_CLIENTS}clients",
        "value": on["qps"],
        "unit": "queries/s",
        "vs_baseline": round(on["qps"] / max(off["qps"], 1e-9), 2),
        "platform": platform,
        "devices": n_devices,
        "corpus": {"docs": n_docs, "dim": d, "shards": MESH_SHARDS},
        "recall_vs_host": recall,
        "mesh_on": on,
        "mesh_off": off,
    }))


OTEL_OUT = Path(__file__).resolve().parent / "BENCH_OTEL.json"
OTEL_BUDGET_S = int(os.environ.get("BENCH_OTEL_BUDGET_S", "600"))
# observability must be near-free: the gate fails if turning the span
# exporter ON (file sink, sample-everything worst case) costs more than
# this fraction of streaming kNN QPS
OTEL_TOLERANCE = float(os.environ.get("BENCH_OTEL_TOLERANCE", "0.05"))


def otel_parent() -> int:
    """`bench.py --otel-overhead`: streaming kNN QPS with the span
    exporter OFF vs ON (file sink, sample_ratio 1.0 — every trace
    exported, the worst case), in a watchdogged child. Writes
    BENCH_OTEL.json beside this file and exits 1 when the overhead
    exceeds OTEL_TOLERANCE (default 5%, env BENCH_OTEL_TOLERANCE) — wired
    into scripts/check.sh --bench so an expensive exporter change fails
    the gate, not the next perf round."""
    result, reason = _run(["--otel-child"], OTEL_BUDGET_S)
    if result is None:
        print(json.dumps({
            "metric": "otel_overhead", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"otel child failed: {reason}",
            "ok": False,
        }))
        return 1
    overhead = float(result.get("overhead_pct", 100.0))
    ok = overhead <= OTEL_TOLERANCE * 100.0
    result["ok"] = ok
    result["tolerance_pct"] = OTEL_TOLERANCE * 100.0
    if not ok:
        result["detail"] = (
            f"span export costs {overhead:.1f}% QPS "
            f"(> {OTEL_TOLERANCE:.0%} budget)")
    try:
        OTEL_OUT.write_text(json.dumps(result, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0 if ok else 1


def otel_child() -> None:
    """One node, concurrent kNN clients, exporter off vs on. Configs run
    in ALTERNATING repeats (off, on, off, on, ...) and report per-config
    medians, so a co-tenant CPU burst hits both sides instead of poisoning
    one — the 5%-budget comparison needs that symmetry."""
    import tempfile
    import threading

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import executor
    from opensearch_tpu.telemetry.export import apply_tracing_settings

    platform = jax.devices()[0].platform
    d = 64
    n_docs = 20_000 if platform != "cpu" else 3_000
    clients = int(os.environ.get("BENCH_OTEL_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_OTEL_QUERIES", "40"))
    # 9 alternating off/on repeats: shared-container CPU throughput drifts
    # enough that 5-rep medians swung the measured overhead 0-17% run to
    # run (observed while gating ISSUE 10) — with 9 the medians settle at
    # the real ~2-3% and the 5% gate stops flapping
    reps = int(os.environ.get("BENCH_OTEL_REPS", "9"))
    executor.STREAMING_MIN_DOCS = min(executor.STREAMING_MIN_DOCS, 1_024)

    rng = np.random.default_rng(17)
    tmp = Path(tempfile.mkdtemp(prefix="bench_otel_"))
    node = TpuNode(tmp / "node")
    node.create_index("bench", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": d, "space_type": "l2"},
        }},
    })
    node.bulk([
        ("index", {"_index": "bench", "_id": str(i)},
         {"v": rng.standard_normal(d).astype(np.float32).tolist()})
        for i in range(n_docs)
    ], refresh=True)
    queries = [
        rng.standard_normal(d).astype(np.float32).tolist()
        for _ in range(clients * per_client)
    ]

    exported_total = 0

    def harvest_exported() -> None:
        # each off-toggle DISCARDS the exporter (mode none detaches and
        # closes), so the ledger must be banked before every rebuild —
        # flush first so queued spans count
        nonlocal exported_total
        exporter = node.telemetry.tracer.exporter
        if exporter is not None:
            exporter.flush()
            exported_total += exporter.snapshot_stats().get(
                "spans_exported", 0)

    def set_exporter(enabled: bool) -> None:
        harvest_exported()
        flat = ({"telemetry.tracing.exporter": "file",
                 "telemetry.tracing.sample_ratio": 1.0,
                 "telemetry.tracing.slow_threshold_ms": 0}
                if enabled else {})
        apply_tracing_settings(node.telemetry, flat, tmp / "node")

    def one_round() -> float:
        lat_done = [0] * clients
        barrier = threading.Barrier(clients + 1)

        def client(ci: int) -> None:
            mine = queries[ci * per_client:(ci + 1) * per_client]
            barrier.wait()
            for q in mine:
                node.search("bench", {"size": 10, "query": {
                    "knn": {"v": {"vector": q, "k": 10}}}})
                lat_done[ci] += 1

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return sum(lat_done) / wall

    # warm both configs (compile batch-width programs, open the sink)
    for enabled in (False, True):
        set_exporter(enabled)
        for q in queries[:4]:
            node.search("bench", {"size": 10, "query": {
                "knn": {"v": {"vector": q, "k": 10}}}})
    walls: dict[bool, list] = {False: [], True: []}
    for _ in range(reps):
        for enabled in (False, True):
            set_exporter(enabled)
            walls[enabled].append(one_round())
    qps_off = float(np.median(walls[False]))
    qps_on = float(np.median(walls[True]))
    harvest_exported()  # bank the final ON round's ledger post-flush
    node.close()
    overhead_pct = max(0.0, (1.0 - qps_on / max(qps_off, 1e-9)) * 100.0)
    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"otel_overhead_knn_{clients}x{per_client}",
        "value": round(qps_on, 1),
        "unit": "queries/s",
        "vs_baseline": round(qps_on / max(qps_off, 1e-9), 3),
        "platform": platform,
        "qps_exporter_off": round(qps_off, 1),
        "qps_exporter_on": round(qps_on, 1),
        "overhead_pct": round(overhead_pct, 2),
        "spans_exported": exported_total,
        "corpus": {"docs": n_docs, "dim": d},
    }))


HEAT_OUT = Path(__file__).resolve().parent / "BENCH_HEAT.json"
HEAT_BUDGET_S = int(os.environ.get("BENCH_HEAT_BUDGET_S", "600"))
# heat/touch accounting must be near-free: the gate fails if recording a
# touch per launch (telemetry/device_ledger.touch) costs more than this
# fraction of streaming kNN QPS
HEAT_TOLERANCE = float(os.environ.get("BENCH_HEAT_TOLERANCE", "0.05"))


def heat_parent() -> int:
    """`bench.py --heat-overhead`: streaming kNN QPS with heat/touch
    recording OFF vs ON (the default), in a watchdogged child. Writes
    BENCH_HEAT.json beside this file and exits 1 when the overhead
    exceeds HEAT_TOLERANCE (default 5%, env BENCH_HEAT_TOLERANCE) — wired
    into scripts/check.sh --bench so an expensive touch-path change fails
    the gate, not the next perf round."""
    result, reason = _run(["--heat-child"], HEAT_BUDGET_S)
    if result is None:
        print(json.dumps({
            "metric": "heat_overhead", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"heat child failed: {reason}",
            "ok": False,
        }))
        return 1
    overhead = float(result.get("overhead_pct", 100.0))
    ok = overhead <= HEAT_TOLERANCE * 100.0
    result["ok"] = ok
    result["tolerance_pct"] = HEAT_TOLERANCE * 100.0
    if not ok:
        result["detail"] = (
            f"heat recording costs {overhead:.1f}% QPS "
            f"(> {HEAT_TOLERANCE:.0%} budget)")
    try:
        HEAT_OUT.write_text(json.dumps(result, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0 if ok else 1


def heat_child() -> None:
    """One node, concurrent kNN clients, touch recording off vs on.
    Configs run in ALTERNATING repeats (off, on, off, on, ...) and report
    per-config medians, so a co-tenant CPU burst hits both sides instead
    of poisoning one — the same symmetry recipe as the otel bench."""
    import tempfile
    import threading

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import executor
    from opensearch_tpu.telemetry.device_ledger import default_ledger

    platform = jax.devices()[0].platform
    d = 64
    n_docs = 20_000 if platform != "cpu" else 3_000
    clients = int(os.environ.get("BENCH_HEAT_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_HEAT_QUERIES", "40"))
    # 9 alternating off/on repeats: the otel bench showed 5-rep medians
    # swing the measured overhead run-to-run on this shared container
    reps = int(os.environ.get("BENCH_HEAT_REPS", "9"))
    executor.STREAMING_MIN_DOCS = min(executor.STREAMING_MIN_DOCS, 1_024)

    rng = np.random.default_rng(19)
    tmp = Path(tempfile.mkdtemp(prefix="bench_heat_"))
    node = TpuNode(tmp / "node")
    node.create_index("bench", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": d, "space_type": "l2"},
        }},
    })
    node.bulk([
        ("index", {"_index": "bench", "_id": str(i)},
         {"v": rng.standard_normal(d).astype(np.float32).tolist()})
        for i in range(n_docs)
    ], refresh=True)
    queries = [
        rng.standard_normal(d).astype(np.float32).tolist()
        for _ in range(clients * per_client)
    ]

    def one_round() -> float:
        lat_done = [0] * clients
        barrier = threading.Barrier(clients + 1)

        def client(ci: int) -> None:
            mine = queries[ci * per_client:(ci + 1) * per_client]
            barrier.wait()
            for q in mine:
                node.search("bench", {"size": 10, "query": {
                    "knn": {"v": {"vector": q, "k": 10}}}})
                lat_done[ci] += 1

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return sum(lat_done) / wall

    # warm both configs (compile batch-width programs)
    for enabled in (False, True):
        default_ledger.configure_heat(enabled=enabled)
        for q in queries[:4]:
            node.search("bench", {"size": 10, "query": {
                "knn": {"v": {"vector": q, "k": 10}}}})
    walls: dict[bool, list] = {False: [], True: []}
    for _ in range(reps):
        for enabled in (False, True):
            default_ledger.configure_heat(enabled=enabled)
            walls[enabled].append(one_round())
    default_ledger.configure_heat(enabled=True)
    qps_off = float(np.median(walls[False]))
    qps_on = float(np.median(walls[True]))
    touches = default_ledger.heat_counters["touches"]
    node.close()
    overhead_pct = max(0.0, (1.0 - qps_on / max(qps_off, 1e-9)) * 100.0)
    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"heat_overhead_knn_{clients}x{per_client}",
        "value": round(qps_on, 1),
        "unit": "queries/s",
        "vs_baseline": round(qps_on / max(qps_off, 1e-9), 3),
        "platform": platform,
        "qps_heat_off": round(qps_off, 1),
        "qps_heat_on": round(qps_on, 1),
        "overhead_pct": round(overhead_pct, 2),
        "touches_recorded": touches,
        "corpus": {"docs": n_docs, "dim": d},
    }))


def concurrency_parent() -> int:
    """`bench.py --concurrency`: the concurrent-clients serving workload
    (CONC_CLIENTS threads x CONC_QUERIES kNN searches each through the real
    node API) with the dispatch batcher ON vs OFF, in a watchdogged child.
    Reports QPS, p50/p99 latency, and mean merged batch size per config;
    persists BENCH_CONCURRENCY.json alongside the other BENCH_* metrics."""
    result, reason = _run(["--concurrency-child"], CONCURRENCY_BUDGET_S)
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"concurrency child failed: {reason}",
        }))
        return 1
    try:
        CONCURRENCY_OUT.write_text(json.dumps(result, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0


def concurrency_child() -> None:
    """Serve CONC_CLIENTS concurrent kNN clients against one node, batcher
    on vs off, and emit the comparison. The corpus is sized to make the
    per-dispatch overhead visible (the quantity batching amortizes) while
    staying inside the CPU-backend budget."""
    import tempfile
    import threading

    _child_jax()
    import numpy as np

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import executor

    import jax

    platform = jax.devices()[0].platform
    d = 64
    n_docs = 20_000 if platform != "cpu" else 3_000
    # every segment must take the streaming program (the serving hot path)
    executor.STREAMING_MIN_DOCS = min(executor.STREAMING_MIN_DOCS, 1_024)

    rng = np.random.default_rng(13)
    node = TpuNode(Path(tempfile.mkdtemp(prefix="bench_conc_")))
    node.create_index("bench", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": d, "space_type": "l2"},
        }},
    })
    node.bulk([
        ("index", {"_index": "bench", "_id": str(i)},
         {"v": rng.standard_normal(d).astype(np.float32).tolist()})
        for i in range(n_docs)
    ], refresh=True)

    queries = [
        rng.standard_normal(d).astype(np.float32).tolist()
        for _ in range(CONC_CLIENTS * CONC_QUERIES)
    ]
    body = {"size": 10}

    def run_config(enabled: bool) -> dict:
        node.knn_batcher.configure(
            enabled=enabled, max_batch_size=CONC_CLIENTS, max_wait_ms=3,
            max_queue=4 * CONC_CLIENTS * CONC_QUERIES,
        )
        node.knn_batcher.reset()
        # warm: a short concurrent round compiles the batch-width program
        # shapes this config will use, so the measured run is steady-state
        warm_barrier = threading.Barrier(CONC_CLIENTS)

        def warm(ci: int) -> None:
            warm_barrier.wait()
            for q in queries[ci::CONC_CLIENTS][:4]:
                node.search("bench", {**body, "query": {
                    "knn": {"v": {"vector": q, "k": 10}}}})

        warm_threads = [threading.Thread(target=warm, args=(ci,))
                        for ci in range(CONC_CLIENTS)]
        for t in warm_threads:
            t.start()
        for t in warm_threads:
            t.join()
        node.knn_batcher.reset()
        lat: list[list[float]] = [[] for _ in range(CONC_CLIENTS)]
        barrier = threading.Barrier(CONC_CLIENTS + 1)

        def client(ci: int) -> None:
            mine = queries[ci * CONC_QUERIES:(ci + 1) * CONC_QUERIES]
            barrier.wait()
            for q in mine:
                t0 = time.perf_counter()
                node.search("bench", {**body, "query": {
                    "knn": {"v": {"vector": q, "k": 10}}}})
                lat[ci].append(time.perf_counter() - t0)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(CONC_CLIENTS)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        flat = sorted(x for chunk in lat for x in chunk)
        stats = node.knn_batcher.snapshot_stats()
        return {
            "batcher_enabled": enabled,
            "clients": CONC_CLIENTS,
            "queries_per_client": CONC_QUERIES,
            "qps": round(len(flat) / wall, 1),
            "p50_ms": round(1000 * flat[len(flat) // 2], 2),
            "p99_ms": round(1000 * flat[int(len(flat) * 0.99)], 2),
            "mean_merged_batch": round(stats["mean_merged_batch"], 2),
            "dispatches": stats["dispatches"],
            "rejections": stats["rejections"],
        }

    off = run_config(False)
    on = run_config(True)
    print(json.dumps({
        "metric": f"concurrent_knn_qps_{CONC_CLIENTS}x{CONC_QUERIES}",
        "value": on["qps"],
        "unit": "queries/s",
        "vs_baseline": round(on["qps"] / max(off["qps"], 1e-9), 2),
        "platform": platform,
        "corpus": {"docs": n_docs, "dim": d},
        "batcher_on": on,
        "batcher_off": off,
    }))


ANN_OUT = Path(__file__).resolve().parent / "BENCH_ANN.json"
ANN_BUDGET_S = int(os.environ.get("BENCH_ANN_BUDGET_S", "900"))
ANN_CLIENTS = int(os.environ.get("BENCH_ANN_CLIENTS", "16"))
ANN_QUERIES = int(os.environ.get("BENCH_ANN_QUERIES", "60"))
# the recall ratchet (ISSUE 9 acceptance): ANN serving may never silently
# buy speed with recall — batched IVF-PQ must hold recall@10 vs the exact
# scan at or above this floor, at EVERY adc precision
ANN_RECALL_FLOOR = float(os.environ.get("BENCH_ANN_RECALL_FLOOR", "0.95"))
# and the batched path must actually amortize launches: batched/unbatched
# QPS at the default precision
ANN_MIN_SPEEDUP = float(os.environ.get("BENCH_ANN_MIN_SPEEDUP", "1.3"))
# measurement tolerance for the TPU-only fused int8/bf16-vs-fp32 QPS
# assertion: the inversion it guards against was ~31% (204 vs 296), so a
# 5% band kills the flake without ever excusing a real inversion
ANN_FUSED_TOLERANCE = float(os.environ.get("BENCH_ANN_FUSED_TOLERANCE",
                                           "0.05"))


def ann_parent() -> int:
    """`bench.py --ann`: batched IVF-PQ serving bench — ANN_CLIENTS
    concurrent clients against one ivf_pq index, dispatch batcher ON vs
    OFF, per ADC precision (fp32/bf16/int8), with recall@10 of the SERVED
    ANN path measured against the exact scan on an identical corpus.
    Writes BENCH_ANN.json keyed by platform. Headline value is batched
    fp32 QPS; vs_baseline the batched/unbatched speedup. Exits 1 when the
    recall ratchet (>= ANN_RECALL_FLOOR at every precision) or the
    speedup floor (>= ANN_MIN_SPEEDUP) fails."""
    platform = _detect_platform()
    result, reason = _run(["--ann-child"], ANN_BUDGET_S,
                          platform_env="cpu" if platform == "cpu" else None)
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"ann child failed: {reason}",
        }))
        return 1
    recalls = result.get("recall_at_10", {})
    min_recall = min(recalls.values()) if recalls else 0.0
    speedup = float(result.get("vs_baseline", 0.0))
    ok = min_recall >= ANN_RECALL_FLOOR and speedup >= ANN_MIN_SPEEDUP
    result["ok"] = ok
    result["recall_floor"] = ANN_RECALL_FLOOR
    result["min_speedup"] = ANN_MIN_SPEEDUP
    if not ok:
        result["detail"] = (
            f"recall@10 min {min_recall:.3f} (floor {ANN_RECALL_FLOOR}) / "
            f"batched speedup {speedup:.2f}x (floor {ANN_MIN_SPEEDUP}x)")
    book = _load_book(ANN_OUT)
    book[result.get("platform", "cpu")] = result
    try:
        ANN_OUT.write_text(json.dumps(book, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0 if ok else 1


def ann_gate_parent() -> int:
    """`bench.py --ann-gate`: the check.sh gate for the ANN serving path —
    a QUICK run must (a) hold the recall@10 ratchet at every precision on
    BOTH the XLA and the fused Pallas path, (b) keep the batched speedup
    above ANN_MIN_SPEEDUP, and (c) stay within the platform tolerance of
    BENCH_ANN.json's recorded QPS (same contract as the streaming/mesh
    gates; no baseline => (c) passes with a note). On a TPU backend the
    gate ALSO asserts the int8 inversion is resolved where the fused
    kernel actually runs: fused int8/bf16 QPS >= fused fp32 QPS. The CPU
    sim serves the fused path in interpret mode, which is a parity tool,
    not a speed claim — there the fused assertion is recall-only."""
    platform = _detect_platform()
    result, reason = _run(
        ["--ann-child"], ANN_BUDGET_S,
        platform_env="cpu" if platform == "cpu" else None,
        extra_env={"BENCH_ANN_QUERIES": "30"},
    )
    if result is None:
        print(json.dumps({
            "metric": "ann_gate", "value": 0, "unit": "error",
            "vs_baseline": 0,
            "detail": f"ann gate child failed: {reason}", "ok": False,
        }))
        return 1
    recalls = result.get("recall_at_10", {})
    min_recall = min(recalls.values()) if recalls else 0.0
    speedup = float(result.get("vs_baseline", 0.0))
    out, floor_ok = _gate_compare(
        "ann_gate", result.get("value", 0),
        _load_book(ANN_OUT).get(platform), platform,
        "batched ANN regression")
    ratchet_ok = min_recall >= ANN_RECALL_FLOOR
    speed_ok = speedup >= ANN_MIN_SPEEDUP
    fused = result.get("fused", {})
    fused_recalls = fused.get("recall_at_10", {})
    fused_min = min(fused_recalls.values()) if fused_recalls else 0.0
    fused_recall_ok = fused_min >= ANN_RECALL_FLOOR
    # the inversion gate only binds where the fused KERNEL runs (TPU):
    # reduced precision must never lose QPS against fp32 on its own path
    # (within the measurement tolerance — every other QPS check here has
    # one, and the real inversion was far outside any noise band)
    fused_qps = fused.get("qps", {})
    if platform == "tpu" and fused_qps:
        fused_floor = fused_qps.get("fp32", 0.0) * (1.0 - ANN_FUSED_TOLERANCE)
        fused_inversion_ok = all(
            fused_qps.get(p, 0.0) >= fused_floor
            for p in ("bf16", "int8"))
    else:
        fused_inversion_ok = True
    ok = (floor_ok and ratchet_ok and speed_ok
          and fused_recall_ok and fused_inversion_ok)
    out.update({
        "ok": ok,
        "recall_at_10": recalls,
        "recall_floor": ANN_RECALL_FLOOR,
        "batched_speedup": speedup,
        "min_speedup": ANN_MIN_SPEEDUP,
        "fused": fused,
    })
    if not ratchet_ok:
        out["detail"] = (f"recall@10 ratchet broken: min {min_recall:.3f} "
                         f"< {ANN_RECALL_FLOOR}")
    elif not speed_ok:
        out["detail"] = (f"batched ANN speedup {speedup:.2f}x below "
                         f"{ANN_MIN_SPEEDUP}x floor")
    elif not fused_recall_ok:
        out["detail"] = (f"fused-path recall@10 ratchet broken: min "
                         f"{fused_min:.3f} < {ANN_RECALL_FLOOR}")
    elif not fused_inversion_ok:
        out["detail"] = (f"int8 inversion NOT resolved on the fused path: "
                         f"fused qps {fused_qps} (bf16/int8 must stay "
                         f"within {ANN_FUSED_TOLERANCE:.0%} of fp32 where "
                         f"the kernel runs)")
    print(json.dumps(out))
    return 0 if ok else 1


def ann_child() -> None:
    """One node, twin indices over an identical clustered corpus — `ann`
    (ivf_pq) and `exact` (flat scan, the ground truth) — serving
    ANN_CLIENTS concurrent clients. Measures, through the REAL search
    API: recall@10 of the served ANN path per adc precision, unbatched
    ANN QPS (batcher off), and batched ANN QPS per precision."""
    import tempfile
    import threading

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import ann as ann_mod

    platform = jax.devices()[0].platform
    d = 64
    n_docs = 4_000 if platform == "cpu" else 50_000
    clients = ANN_CLIENTS
    per_client = int(os.environ.get("BENCH_ANN_QUERIES", ANN_QUERIES))
    n_recall_q = 48

    # clustered corpus: IVF coarse quantization needs real cluster
    # structure for nprobe lists to cover the true neighbors
    rng = np.random.default_rng(23)
    n_centers = 16
    centers = rng.standard_normal((n_centers, d)).astype(np.float32) * 5.0
    data = (centers[rng.integers(0, n_centers, n_docs)]
            + rng.standard_normal((n_docs, d))).astype(np.float32)

    tmp = Path(tempfile.mkdtemp(prefix="bench_ann_"))
    node = TpuNode(tmp / "node")
    node.create_index("ann", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"v": {
            "type": "knn_vector", "dimension": d,
            "method": {"name": "ivf_pq", "parameters": {
                "nlist": 32, "m": 8, "nprobe": 8}},
        }}},
    })
    node.create_index("exact", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": d},
        }},
    })
    for index in ("ann", "exact"):
        node.bulk([
            ("index", {"_index": index, "_id": str(i)},
             {"v": data[i].round(4).tolist()})
            for i in range(n_docs)
        ], refresh=True)

    queries = [
        (centers[rng.integers(0, n_centers)]
         + rng.standard_normal(d)).astype(np.float32).tolist()
        for _ in range(max(clients * per_client, n_recall_q))
    ]

    def search(index, q):
        return node.search(index, {"size": 10, "query": {
            "knn": {"v": {"vector": q, "k": 10}}}})

    def hit_ids(resp):
        return {h["_id"] for h in resp["hits"]["hits"]}

    truth = [hit_ids(search("exact", q)) for q in queries[:n_recall_q]]

    def recall_round() -> float:
        got = [hit_ids(search("ann", q)) for q in queries[:n_recall_q]]
        return float(np.mean([
            len(g & t) / max(len(t), 1) for g, t in zip(got, truth)
        ]))

    def qps_round() -> float:
        done = [0] * clients
        barrier = threading.Barrier(clients + 1)

        def client(ci):
            mine = queries[ci * per_client:(ci + 1) * per_client]
            barrier.wait()
            for q in mine:
                search("ann", q)
                done[ci] += 1

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        return sum(done) / (time.perf_counter() - t0)

    def configure_batcher(enabled: bool) -> None:
        node.knn_batcher.configure(
            enabled=enabled, max_batch_size=clients, max_wait_ms=3,
            max_queue=4 * clients * per_client,
        )
        node.knn_batcher.reset()

    def warm_concurrent() -> None:
        # compile every power-of-two batch width this config can produce
        # BEFORE the timed round (arrivals split unpredictably, and a
        # retrace inside the measurement would bill compile time as
        # serving time)
        barrier = threading.Barrier(clients)

        def warm(ci):
            barrier.wait()
            for q in queries[ci::clients][:4]:
                search("ann", q)

        threads = [threading.Thread(target=warm, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # the serving knob pair under test: widened exact-rescore pool (the
    # ANNS-AMP recall recovery) on top of each ADC precision
    ann_mod.default_config.configure(rescore_multiplier=8)
    recalls: dict = {}
    qps_batched: dict = {}
    for precision in ("fp32", "bf16", "int8"):
        ann_mod.default_config.configure(adc_precision=precision)
        configure_batcher(True)
        recalls[precision] = round(recall_round(), 4)  # solo-width warm
        warm_concurrent()
        node.knn_batcher.reset()
        qps_batched[precision] = round(qps_round(), 1)

    # the headline comparison runs in ALTERNATING repeats (off, on, ...)
    # with per-config medians — a co-tenant CPU burst hits both sides
    # instead of poisoning one (same symmetry recipe as the otel bench)
    ann_mod.default_config.configure(adc_precision="fp32")
    reps = int(os.environ.get("BENCH_ANN_REPS", "3"))
    walls: dict = {False: [], True: []}
    configure_batcher(False)
    for q in queries[:4]:
        search("ann", q)  # warm the solo program shapes
    for _ in range(reps):
        for enabled in (False, True):
            configure_batcher(enabled)
            walls[enabled].append(qps_round())
    qps_unbatched = round(float(np.median(walls[False])), 1)
    qps_batched["fp32"] = round(float(np.median(walls[True])), 1)

    # the FUSED Pallas blockwise ADC scan (ISSUE 14), behind the explicit
    # selection policy: on a TPU backend it is the real kernel and its
    # QPS rows are the int8-inversion resolution evidence (the gate
    # asserts int8/bf16 >= fp32 THERE); on the CPU sim kernel="pallas"
    # runs the interpret parity path, so only recall/parity is recorded —
    # interpret mode is NOT a speed claim
    fused: dict = {"kernel": "pallas", "interpret": platform != "tpu",
                   "recall_at_10": {}}
    configure_batcher(True)
    for precision in ("fp32", "bf16", "int8"):
        ann_mod.default_config.configure(
            adc_precision=precision, kernel="pallas")
        fused["recall_at_10"][precision] = round(recall_round(), 4)
        if platform == "tpu":
            warm_concurrent()
            node.knn_batcher.reset()
            fused.setdefault("qps", {})[precision] = round(qps_round(), 1)
    ann_mod.default_config.configure(adc_precision="fp32", kernel="auto")
    node.close()

    speedup = qps_batched["fp32"] / max(qps_unbatched, 1e-9)
    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"ann_knn_batched_{clients}x{per_client}",
        "value": qps_batched["fp32"],
        "unit": "queries/s",
        "vs_baseline": round(speedup, 3),
        "platform": platform,
        "qps_batched": qps_batched,
        "qps_unbatched_fp32": qps_unbatched,
        "recall_at_10": recalls,
        "fused": fused,
        "corpus": {"docs": n_docs, "dim": d, "nlist": 32, "nprobe": 8},
    }))


# ---------------------------------------------------------------------------
# fused exact-kNN bench (ISSUE 19): the fused blockwise MXU kernel vs the
# legacy XLA exact scorer, QPS/p50 per score precision, recall through the
# REAL served path
# ---------------------------------------------------------------------------

FUSED_KNN_OUT = Path(__file__).resolve().parent / "BENCH_KNN_FUSED.json"
FUSED_KNN_BUDGET_S = int(os.environ.get("BENCH_FUSED_KNN_BUDGET_S", "600"))
# off-TPU the fused math benches as its XLA reference lowering (same
# blockwise program the interpret path checks parity against) — it must
# not LOSE qps to the legacy scorer; this is the noise band on that >= 1x
# assertion, not a license to regress (the real speed claim is TPU-only)
FUSED_KNN_TOLERANCE = float(os.environ.get("BENCH_FUSED_KNN_TOLERANCE",
                                           "0.15"))
# reduced-precision served recall floor; fp32 is NOT covered by this knob
# — the exact path must be exact (recall 1.0, asserted unconditionally)
FUSED_KNN_RECALL_FLOOR = float(os.environ.get(
    "BENCH_FUSED_KNN_RECALL_FLOOR", "0.99"))


def _fused_knn_check(result: dict) -> tuple[bool, str]:
    """Shared acceptance for --fused-knn and its gate: exact recall 1.0
    at fp32, reduced precisions above the floor, fused >= 1x XLA within
    the platform tolerance."""
    recalls = result.get("recall_at_10", {})
    if recalls.get("fp32") != 1.0:
        return False, (f"exact path must be exact: served fp32 recall@10 "
                       f"{recalls.get('fp32')} != 1.0")
    low = {p: r for p, r in recalls.items()
           if p != "fp32" and r < FUSED_KNN_RECALL_FLOOR}
    if low:
        return False, (f"reduced-precision recall@10 below "
                       f"{FUSED_KNN_RECALL_FLOOR}: {low}")
    speedup = float(result.get("vs_baseline", 0.0))
    if speedup < 1.0 - FUSED_KNN_TOLERANCE:
        return False, (f"fused fp32 {speedup:.2f}x XLA — below the 1.0x "
                       f"floor (tolerance {FUSED_KNN_TOLERANCE:.0%})")
    return True, ""


def fused_knn_parent() -> int:
    """`bench.py --fused-knn`: fused-vs-XLA exact-kNN bench — QPS and
    p50 per score precision (fp32/bf16/int8) at the kernel layer, served
    recall@10 through the real search API under the exact-kernel policy
    flip. Writes BENCH_KNN_FUSED.json keyed by platform; headline value
    is fused fp32 QPS, vs_baseline the fused/XLA ratio. On TPU the
    `fused.qps` rows are the real Pallas kernel; off-TPU they are the XLA
    reference lowering of the same blockwise program."""
    platform = _detect_platform()
    result, reason = _run(["--fused-knn-child"], FUSED_KNN_BUDGET_S,
                          platform_env="cpu" if platform == "cpu" else None)
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0,
            "detail": f"fused-knn child failed: {reason}",
        }))
        return 1
    ok, detail = _fused_knn_check(result)
    result["ok"] = ok
    result["recall_floor"] = FUSED_KNN_RECALL_FLOOR
    result["tolerance"] = FUSED_KNN_TOLERANCE
    if not ok:
        result["detail"] = detail
    book = _load_book(FUSED_KNN_OUT)
    book[result.get("platform", "cpu")] = result
    try:
        FUSED_KNN_OUT.write_text(json.dumps(book, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0 if ok else 1


def fused_knn_gate_parent() -> int:
    """`bench.py --fused-knn-gate`: the check.sh gate for the fused exact
    path — a QUICK run must (a) keep the served exact path EXACT (fp32
    recall@10 == 1.0 under kernel=pallas), (b) hold reduced-precision
    recall above the floor, (c) keep fused >= 1.0x the legacy XLA scorer
    within FUSED_KNN_TOLERANCE, and (d) stay within the platform
    tolerance of BENCH_KNN_FUSED.json's recorded QPS (no baseline => (d)
    passes with a note, same contract as the other gates)."""
    platform = _detect_platform()
    result, reason = _run(
        ["--fused-knn-child"], FUSED_KNN_BUDGET_S,
        platform_env="cpu" if platform == "cpu" else None,
        extra_env={"BENCH_FUSED_KNN_REPS": "2",
                   "BENCH_FUSED_KNN_RECALL_Q": "24"},
    )
    if result is None:
        print(json.dumps({
            "metric": "fused_knn_gate", "value": 0, "unit": "error",
            "vs_baseline": 0, "ok": False,
            "detail": f"fused-knn gate child failed: {reason}",
        }))
        return 1
    out, floor_ok = _gate_compare(
        "fused_knn_gate", result.get("value", 0),
        _load_book(FUSED_KNN_OUT).get(platform), platform,
        "fused exact-kNN regression")
    check_ok, detail = _fused_knn_check(result)
    ok = floor_ok and check_ok
    out.update({
        "ok": ok,
        "recall_at_10": result.get("recall_at_10", {}),
        "recall_floor": FUSED_KNN_RECALL_FLOOR,
        "fused_vs_xla": result.get("vs_baseline", 0.0),
        "fused": result.get("fused", {}),
        "xla": result.get("xla", {}),
    })
    if not check_ok:
        out["detail"] = detail
    print(json.dumps(out))
    return 0 if ok else 1


def fused_knn_child() -> None:
    """One node, one exact knn_vector index over a clustered corpus.
    Recall@10 of the SERVED fused path (search.knn.kernel="pallas", per
    score precision) against the same node's default-policy truth, then
    kernel-layer QPS/p50 rounds: the legacy XLA exact scorer
    (fused.knn_topk) vs the fused blockwise program (knn_fused_auto —
    real Pallas on TPU, its XLA reference lowering elsewhere), run in
    alternating repeats with per-config medians."""
    import tempfile

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.ops import fused as fused_ops
    from opensearch_tpu.ops import pallas_knn as pallas_knn_ops
    from opensearch_tpu.search import ann as ann_mod

    platform = jax.devices()[0].platform
    d = 64
    n_docs = 4_000 if platform == "cpu" else 50_000
    batch = 8
    k = 10
    reps = int(os.environ.get("BENCH_FUSED_KNN_REPS", "3"))
    launches = int(os.environ.get("BENCH_FUSED_KNN_LAUNCHES", "12"))
    n_recall_q = int(os.environ.get("BENCH_FUSED_KNN_RECALL_Q", "48"))

    rng = np.random.default_rng(29)
    n_centers = 16
    centers = rng.standard_normal((n_centers, d)).astype(np.float32) * 5.0
    data = (centers[rng.integers(0, n_centers, n_docs)]
            + rng.standard_normal((n_docs, d))).astype(np.float32)

    # --- served recall: the REAL search API under the policy flip ---
    tmp = Path(tempfile.mkdtemp(prefix="bench_fused_knn_"))
    node = TpuNode(tmp / "node")
    node.create_index("vec", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"v": {
            "type": "knn_vector", "dimension": d,
        }}},
    })
    node.bulk([
        ("index", {"_index": "vec", "_id": str(i)},
         {"v": data[i].round(4).tolist()})
        for i in range(n_docs)
    ], refresh=True)

    queries_f = (centers[rng.integers(0, n_centers, n_recall_q)]
                 + rng.standard_normal((n_recall_q, d))).astype(np.float32)

    def search(q):
        return node.search("vec", {"size": k, "query": {
            "knn": {"v": {"vector": q.tolist(), "k": k}}}})

    def hit_ids(resp):
        return {h["_id"] for h in resp["hits"]["hits"]}

    truth = [hit_ids(search(q)) for q in queries_f]  # default policy
    recalls: dict = {}
    for precision in pallas_knn_ops.SCORE_PRECISIONS:
        ann_mod.default_config.configure(
            exact_kernel="pallas", score_precision=precision)
        got = [hit_ids(search(q)) for q in queries_f]
        recalls[precision] = round(float(np.mean([
            len(g & t) / max(len(t), 1) for g, t in zip(got, truth)
        ])), 4)
    ann_mod.default_config.configure(
        exact_kernel="auto", score_precision="fp32")
    node.close()

    # --- kernel-layer QPS/p50: legacy XLA scorer vs the fused program ---
    import jax.numpy as jnp

    vecs = jnp.asarray(data)
    norms_sq = jnp.sum(vecs * vecs, axis=-1)
    valid = jnp.ones((n_docs,), dtype=bool)
    qbatch = jnp.asarray(
        (centers[rng.integers(0, n_centers, batch)]
         + rng.standard_normal((batch, d))).astype(np.float32))

    def time_round(fn) -> tuple[float, float]:
        walls = []
        for _ in range(launches):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        p50 = float(np.median(walls))
        return batch * launches / sum(walls), p50 * 1e3

    def xla_fn():
        return fused_ops.knn_topk(
            vecs, norms_sq, valid, qbatch, k=k, similarity="l2_norm")

    def fused_fn(precision):
        return pallas_knn_ops.knn_fused_auto(
            vecs, norms_sq, valid, qbatch, k=k, similarity="l2_norm",
            score_precision=precision)

    # warm every program shape before any timed round
    jax.block_until_ready(xla_fn())
    for precision in pallas_knn_ops.SCORE_PRECISIONS:
        jax.block_until_ready(fused_fn(precision))

    # alternating repeats with per-config medians (the ann/otel symmetry
    # recipe): a co-tenant burst hits both sides, not one
    xla_rounds: list = []
    fused_rounds: dict = {p: [] for p in pallas_knn_ops.SCORE_PRECISIONS}
    for _ in range(reps):
        xla_rounds.append(time_round(xla_fn))
        for precision in pallas_knn_ops.SCORE_PRECISIONS:
            fused_rounds[precision].append(
                time_round(lambda p=precision: fused_fn(p)))

    def med(rounds, idx):
        return round(float(np.median([r[idx] for r in rounds])), 2)

    xla = {"qps": med(xla_rounds, 0), "p50_ms": med(xla_rounds, 1)}
    fused = {
        "kernel": "pallas" if platform == "tpu" else "xla-reference",
        "interpret_recall_path": platform != "tpu",
        "qps": {p: med(r, 0) for p, r in fused_rounds.items()},
        "p50_ms": {p: med(r, 1) for p, r in fused_rounds.items()},
    }
    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"fused_knn_b{batch}_k{k}",
        "value": fused["qps"]["fp32"],
        "unit": "queries/s",
        "vs_baseline": round(fused["qps"]["fp32"]
                             / max(xla["qps"], 1e-9), 3),
        "platform": platform,
        "recall_at_10": recalls,
        "xla": xla,
        "fused": fused,
        "corpus": {"docs": n_docs, "dim": d, "batch": batch, "k": k},
    }))


# ---------------------------------------------------------------------------
# tail-latency bench (ISSUE 11): interactive p99 under mixed background flood,
# with the control plane (lanes + batch-wait auto-tuning + residency routing)
# ON vs OFF
# ---------------------------------------------------------------------------

TAIL_OUT = Path(__file__).resolve().parent / "BENCH_TAIL.json"
TAIL_BUDGET_S = int(os.environ.get("BENCH_TAIL_BUDGET_S", "900"))
TAIL_SHARDS = int(os.environ.get("BENCH_TAIL_SHARDS", "4"))
TAIL_INT_CLIENTS = int(os.environ.get("BENCH_TAIL_INT_CLIENTS", "4"))
TAIL_INT_QUERIES = int(os.environ.get("BENCH_TAIL_INT_QUERIES", "40"))
TAIL_BG_CLIENTS = int(os.environ.get("BENCH_TAIL_BG_CLIENTS", "4"))
TAIL_BG_BODIES = int(os.environ.get("BENCH_TAIL_BG_BODIES", "6"))
# acceptance: interactive p99 must improve at least this much with the
# control plane ON, at no aggregate-QPS regression beyond the tolerance,
# and ZERO interactive sheds/errors in either configuration
TAIL_MIN_P99_SPEEDUP = float(os.environ.get("BENCH_TAIL_MIN_SPEEDUP", "1.5"))
TAIL_QPS_TOLERANCE = float(os.environ.get("BENCH_TAIL_QPS_TOLERANCE", "0.15"))


def tail_parent() -> int:
    """`bench.py --tail`: mixed interactive+background tail-latency bench
    — one single-node ClusterServer on the 8-device CPU sim, background
    msearch+bulk flood running the whole time, interactive kNN clients
    measuring p50/p99/p999 with the tail control plane ON vs OFF. Records
    BENCH_TAIL.json keyed by platform; headline value is the interactive
    p99 speedup (off/on)."""
    platform = _detect_platform()
    result, reason = _run(["--tail-child"], TAIL_BUDGET_S,
                          platform_env="cpu" if platform == "cpu" else None,
                          extra_env=_mesh_env(platform))
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"tail child failed: {reason}",
        }))
        return 1
    book = _load_book(TAIL_OUT)
    book[result.get("platform", "cpu")] = result
    try:
        TAIL_OUT.write_text(json.dumps(book, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0


def tail_gate_parent() -> int:
    """`bench.py --tail-gate`: the check.sh acceptance gate — a QUICK
    tail run must show interactive p99 improving >= TAIL_MIN_P99_SPEEDUP
    with the control plane on, no aggregate-QPS regression beyond the
    tolerance, and zero interactive sheds in either config. The verdict
    comes from the FRESH paired run (on and off measured back to back in
    one child), not a recorded baseline — the comparison is internal."""
    platform = _detect_platform()
    result, reason = _run(
        ["--tail-child"], TAIL_BUDGET_S,
        platform_env="cpu" if platform == "cpu" else None,
        extra_env={**_mesh_env(platform),
                   "BENCH_TAIL_INT_QUERIES": "16"},
    )
    if result is None:
        print(json.dumps({
            "metric": "tail_gate", "value": 0, "unit": "error",
            "vs_baseline": 0,
            "detail": f"tail gate child failed: {reason}", "ok": False,
        }))
        return 1
    speedup = result.get("p99_speedup", 0)
    qps_ratio = result.get("aggregate_qps_ratio", 0)
    sheds = result.get("interactive_sheds", 1)
    ok = (speedup >= TAIL_MIN_P99_SPEEDUP
          and qps_ratio >= 1.0 - TAIL_QPS_TOLERANCE
          and sheds == 0)
    print(json.dumps({
        "metric": "tail_gate", "value": speedup, "unit": "x p99 speedup",
        "vs_baseline": qps_ratio, "ok": ok,
        "detail": (f"p99 {result.get('on', {}).get('p99_ms')}ms on vs "
                   f"{result.get('off', {}).get('p99_ms')}ms off; "
                   f"aggregate qps ratio {qps_ratio}; "
                   f"interactive sheds {sheds} "
                   f"(need >= {TAIL_MIN_P99_SPEEDUP}x, "
                   f">= {1.0 - TAIL_QPS_TOLERANCE}, 0)"),
    }))
    return 0 if ok else 1


def tail_child() -> None:
    """One single-node cluster server under mixed flood: TAIL_BG_CLIENTS
    background msearch loops + one bulk loop run for the WHOLE measurement
    window while TAIL_INT_CLIENTS interactive clients issue kNN searches;
    interactive latency distribution measured with the control plane
    (lanes + auto-tuner + residency routing) ON vs OFF."""
    import asyncio
    import tempfile
    import threading

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.cluster import residency as residency_mod
    from opensearch_tpu.search import batcher as batcher_mod
    from opensearch_tpu.search import lanes as lanes_mod
    from opensearch_tpu.server import ClusterServer

    platform = jax.devices()[0].platform
    d = 32
    docs_per_shard = 700 if platform == "cpu" else 8_000
    n_docs = TAIL_SHARDS * docs_per_shard
    n_int_queries = int(os.environ.get("BENCH_TAIL_INT_QUERIES",
                                       TAIL_INT_QUERIES))

    tport, hport = _free_ports(2)
    tmp = tempfile.mkdtemp(prefix="bench_tail_")
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    server = ClusterServer(
        "n0", Path(tmp) / "n0", "127.0.0.1", tport, hport,
        {"n0": ("127.0.0.1", tport)}, loop=loop,
    )
    asyncio.run_coroutine_threadsafe(
        server.start(bootstrap=["n0"]), loop).result(60)
    deadline = time.monotonic() + 60
    while not server.node.is_leader:
        if time.monotonic() > deadline:
            raise RuntimeError("single-node cluster never elected itself")
        time.sleep(0.05)
    facade = server.facade

    facade.create_index("tailvec", {
        "settings": {"number_of_shards": TAIL_SHARDS,
                     "number_of_replicas": 0},
        "mappings": {"properties": {
            "v": {"type": "knn_vector", "dimension": d, "space_type": "l2"},
        }},
    })
    rng = np.random.default_rng(31)
    for start in range(0, n_docs, 2_000):
        ops = [
            ("index", {"_index": "tailvec", "_id": str(i)},
             {"v": rng.standard_normal(d).astype(np.float32).tolist()})
            for i in range(start, min(start + 2_000, n_docs))
        ]
        if facade.bulk(ops).get("errors"):
            raise RuntimeError(f"bulk errors at {start}")
    facade.refresh("tailvec")

    def vec():
        return rng.standard_normal(d).astype(np.float32).tolist()

    def knn_body(q, k=10, size=10):
        return {"size": size,
                "query": {"knn": {"v": {"vector": q, "k": k}}}}

    int_queries = [vec() for _ in range(TAIL_INT_CLIENTS * n_int_queries)]

    def set_control_plane(on: bool) -> None:
        lanes_mod.default_config.configure(enabled=on)
        batcher_mod.default_batcher.configure(auto_tune=on)
        residency_mod.default_config.configure(enabled=on)

    # warm both paths (compile + resident slabs) before either timed run
    for on in (False, True):
        set_control_plane(on)
        facade.search("tailvec", knn_body(int_queries[0]))
        facade.msearch([({"index": "tailvec"}, knn_body(vec(), k=4, size=4))
                        for _ in range(TAIL_BG_BODIES)])

    def run_config(on: bool) -> dict:
        set_control_plane(on)
        stop = threading.Event()
        bg_ops = [0] * (TAIL_BG_CLIENTS + 1)
        int_errors = [0]
        lat: list[list[float]] = [[] for _ in range(TAIL_INT_CLIENTS)]
        barrier = threading.Barrier(TAIL_INT_CLIENTS + TAIL_BG_CLIENTS + 2)

        def bg_msearch(bi: int) -> None:
            barrier.wait()
            while not stop.is_set():
                searches = [({"index": "tailvec"},
                             knn_body(vec(), k=4, size=4))
                            for _ in range(TAIL_BG_BODIES)]
                try:
                    facade.msearch(searches)
                    bg_ops[bi] += TAIL_BG_BODIES
                except Exception:  # noqa: BLE001 - flood pressure may shed
                    pass

        def bg_bulk() -> None:
            barrier.wait()
            i = [n_docs]
            while not stop.is_set():
                ops = [("index",
                        {"_index": "tailvec", "_id": f"b{i[0] + j}"},
                        {"v": vec()}) for j in range(8)]
                i[0] += 8
                try:
                    facade.bulk(ops)
                    bg_ops[TAIL_BG_CLIENTS] += 1
                except Exception:  # noqa: BLE001 - flood pressure may shed
                    pass

        def interactive(ci: int) -> None:
            mine = int_queries[ci * n_int_queries:(ci + 1) * n_int_queries]
            barrier.wait()
            for q in mine:
                t0 = time.perf_counter()
                try:
                    resp = facade.search("tailvec", knn_body(q))
                    if resp.get("_shards", {}).get("failed"):
                        int_errors[0] += 1
                except Exception:  # noqa: BLE001 - counted, gate fails on it
                    int_errors[0] += 1
                lat[ci].append(time.perf_counter() - t0)

        threads = (
            [threading.Thread(target=bg_msearch, args=(bi,))
             for bi in range(TAIL_BG_CLIENTS)]
            + [threading.Thread(target=bg_bulk)]
            + [threading.Thread(target=interactive, args=(ci,))
               for ci in range(TAIL_INT_CLIENTS)]
        )
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads[TAIL_BG_CLIENTS + 1:]:
            t.join()
        stop.set()
        for t in threads[: TAIL_BG_CLIENTS + 1]:
            t.join()
        wall = time.perf_counter() - t0
        flat = sorted(x for chunk in lat for x in chunk)

        def pct(p: float) -> float:
            return round(1000 * flat[min(len(flat) - 1,
                                         int(len(flat) * p))], 2)

        total_ops = len(flat) + sum(bg_ops)
        return {
            "control_plane": on,
            "interactive_queries": len(flat),
            "background_ops": sum(bg_ops),
            "aggregate_qps": round(total_ops / wall, 1),
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "p999_ms": pct(0.999),
            "interactive_errors": int_errors[0],
        }

    off = run_config(False)
    on = run_config(True)
    set_control_plane(True)

    tail = server.node.tail_stats()
    interactive_sheds = (
        tail["lanes"]["interactive"]["shed"]
        + tail.get("http_lanes", {}).get("interactive", {}).get("shed", 0)
        + off["interactive_errors"] + on["interactive_errors"])
    speedup = round(off["p99_ms"] / max(on["p99_ms"], 1e-9), 2)
    qps_ratio = round(on["aggregate_qps"] / max(off["aggregate_qps"], 1e-9),
                      3)
    _assert_ledger_identity()
    print(json.dumps({
        "metric": f"tail_p99_speedup_{TAIL_SHARDS}shards_"
                  f"{TAIL_INT_CLIENTS}int_{TAIL_BG_CLIENTS}bg",
        "value": speedup,
        "unit": "x interactive p99 (off/on)",
        "vs_baseline": speedup,
        "p99_speedup": speedup,
        "aggregate_qps_ratio": qps_ratio,
        "interactive_sheds": interactive_sheds,
        "platform": platform,
        "devices": len(jax.devices()),
        "corpus": {"docs": n_docs, "dim": d, "shards": TAIL_SHARDS},
        "on": on,
        "off": off,
        "lanes": tail["lanes"],
        "auto_tune": server.node.knn_batcher.snapshot_stats()["auto_tune"],
    }))


ROOFLINE_OUT = Path(__file__).resolve().parent / "BENCH_ROOFLINE.json"
ROOFLINE_BUDGET_S = int(os.environ.get("BENCH_ROOFLINE_BUDGET_S", "600"))


def roofline_parent() -> int:
    """`bench.py --roofline`: run the exact-streaming, materializing,
    mesh, and ANN (all three adc precisions) serving workloads plus a
    profiled BM25 scan in a watchdogged child, record every family's
    achieved FLOP/s + roofline fraction to BENCH_ROOFLINE.json, and FAIL
    unless the sanity gate holds (fractions in (0, 1], all expected
    families modeled, `accounted_flops == Σ per-family model FLOPs`).
    check.sh --bench runs this as the roofline gate."""
    platform = _detect_platform()
    result, reason = _run(["--roofline-child"], ROOFLINE_BUDGET_S,
                          platform_env="cpu" if platform == "cpu" else None)
    if result is None:
        print(json.dumps({
            "metric": "bench_error", "value": 0, "unit": "error",
            "vs_baseline": 0, "detail": f"roofline child failed: {reason}",
        }))
        return 1
    book = _load_book(ROOFLINE_OUT)
    book[result.get("platform", "cpu")] = result
    try:
        ROOFLINE_OUT.write_text(json.dumps(book, indent=1) + "\n")
    except OSError as e:
        result["write_error"] = str(e)
    print(json.dumps(result))
    return 0


def roofline_child() -> None:
    """One node, every kernel family the registry models, measured
    through the REAL search API: filtered kNN over a small column
    (materializing exact scan) and a streaming-sized column (chunked
    streaming scan), bare kNN over a 2-shard index (the mesh program),
    IVF-PQ at each adc precision under BOTH lowerings (the monolithic XLA
    path and the fused Pallas blockwise scan — interpret mode on the CPU
    sim), and a profiled BM25 match. Asserts the roofline sanity gate
    (including the int8-inversion note clearing once the fused rows are
    present) before printing."""
    import tempfile

    _child_jax()
    import numpy as np

    import jax

    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import ann as ann_mod
    from opensearch_tpu.search import executor as executor_mod
    from opensearch_tpu.telemetry import roofline

    platform = jax.devices()[0].platform
    reps = int(os.environ.get("BENCH_ROOFLINE_QUERIES", "12"))
    d = 64
    rng = np.random.default_rng(31)

    peaks = roofline.calibrate(force=True)
    roofline.default_recorder.reset()

    # the streaming scan engages at this (lowered) corpus size so the
    # bench stays quick; the cost model is size-agnostic
    executor_mod.STREAMING_MIN_DOCS = 1024

    tmp = Path(tempfile.mkdtemp(prefix="bench_roofline_"))
    node = TpuNode(tmp / "node")

    def vec_index(name, n_docs, shards=1, method=None):
        mapping: dict = {"type": "knn_vector", "dimension": d}
        if method is not None:
            mapping["method"] = method
        node.create_index(name, {
            "settings": {"number_of_shards": shards},
            "mappings": {"properties": {
                "v": mapping, "g": {"type": "integer"}}},
        })
        data = rng.standard_normal((n_docs, d)).astype(np.float32)
        node.bulk([
            ("index", {"_index": name, "_id": str(i)},
             {"v": data[i].round(4).tolist(), "g": i % 2})
            for i in range(n_docs)
        ], refresh=True)

    vec_index("exact", 512)          # < streaming floor: materializing
    vec_index("stream", 2048)        # >= streaming floor: chunked scan
    vec_index("mesh2", 512, shards=2)
    vec_index("annv", 2048, method={
        "name": "ivf_pq", "parameters": {"nlist": 16, "m": 8, "nprobe": 4}})
    node.create_index("lex", {"mappings": {"properties": {
        "msg": {"type": "text"}}}})
    node.bulk([
        ("index", {"_index": "lex", "_id": str(i)},
         {"msg": f"common token w{i} w{i % 7}"})
        for i in range(256)
    ], refresh=True)

    def run_queries(index, n=None):
        for _ in range(n or reps):
            q = rng.standard_normal(d).astype(np.float32).round(4).tolist()
            node.search(index, {"size": 5, "query": {
                "knn": {"v": {"vector": q, "k": 5}}}})

    # per-shard scan families: the mesh serves every bare (and filtered)
    # exact body since PR 7, so the ops kill switch is what exposes the
    # materializing + streaming executor launches to measurement
    from opensearch_tpu.search import distributed_serving

    distributed_serving.enabled = False
    try:
        run_queries("exact")               # knn_exact_scores
        run_queries("stream")              # knn_topk_streaming
    finally:
        distributed_serving.enabled = True
    run_queries("mesh2")                   # mesh_knn
    for precision in ("fp32", "bf16", "int8"):
        ann_mod.default_config.configure(adc_precision=precision)
        run_queries("annv")                # ivfpq_search[precision]
    # the fused Pallas blockwise scan (ISSUE 14): kernel="pallas" is the
    # interpret parity path on the CPU sim, so fewer reps — the cost
    # model is what's under test here, not the interpret wall clock
    for precision in ("fp32", "bf16", "int8"):
        ann_mod.default_config.configure(
            adc_precision=precision, kernel="pallas")
        run_queries("annv", n=min(reps, 4))  # ivfpq_adc_pallas[precision]
    ann_mod.default_config.configure(adc_precision="fp32", kernel="auto")
    for _ in range(reps):
        node.search("lex", {"query": {"match": {"msg": "common"}},
                            "profile": True})  # bm25_term_scores

    report = roofline.default_recorder.report()
    families = {row["family"]: row for row in report["families"]}

    # --- sanity gate -------------------------------------------------------
    expected = {"knn_exact_scores", "knn_topk_streaming", "mesh_knn",
                "bm25_term_scores", "ivfpq_search[fp32]",
                "ivfpq_search[bf16]", "ivfpq_search[int8]",
                "ivfpq_adc_pallas[fp32]", "ivfpq_adc_pallas[bf16]",
                "ivfpq_adc_pallas[int8]"}
    missing = expected - set(families)
    assert not missing, f"families missing from the report: {missing}"
    # with the fused path recorded, the int8-inversion note (when the
    # legacy rows still invert) must point at the fused rows instead of
    # naming a standing offender — the swap landed and the report says so
    int8_note = families["ivfpq_search[int8]"].get("note", "")
    assert (not int8_note) or ("ivfpq_adc_pallas" in int8_note), (
        f"int8-inversion note did not clear: {int8_note}")
    bad = {name: row["roofline_fraction"] for name, row in families.items()
           if not (0.0 < row["roofline_fraction"] <= 1.0)}
    assert not bad, f"roofline fractions outside (0, 1]: {bad}"
    assert report["identity_ok"], "accounted_flops != sum of family FLOPs"
    counters = report["counters"]
    assert counters["unmodeled_launches"] == 0, (
        f"unmodeled launches: {counters['unmodeled_launches']}")
    _assert_ledger_identity()
    node.close()

    print(json.dumps({
        "metric": "roofline_families",
        "value": len(families),
        "unit": "modeled kernel families",
        "vs_baseline": 1.0,
        "platform": platform,
        "peaks": peaks.to_dict(),
        "top_offender": report["top_offender"],
        "identity_ok": report["identity_ok"],
        "families": {
            name: {k: row[k] for k in (
                "launches", "achieved_gflops", "ewma_gflops", "intensity",
                "roofline_fraction", "bound", "lost_ms")}
            for name, row in families.items()
        },
        "ok": True,
    }))


def _child_jax():
    """Every child's first touch of JAX: place the persistent compile
    cache (opensearch_tpu/bootstrap.py), then hand back the module."""
    from opensearch_tpu.bootstrap import configure_compile_cache

    configure_compile_cache()
    import jax

    return jax


def probe() -> None:
    """Tiny device claim + matmul; prints {"platform": ...}."""
    jax = _child_jax()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    x = jnp.ones((128, 128), dtype=jnp.float32)
    np.asarray(x @ x)
    print(json.dumps({"platform": dev.platform}))


def child() -> None:
    jax = _child_jax()
    import jax.numpy as jnp
    import numpy as np

    from opensearch_tpu.ops.fused import jit_knn, knn_topk, knn_topk_streaming

    d, k = 128, 10
    chunk_q = 500          # queries per on-device chunk
    rng = np.random.default_rng(7)

    platform = jax.devices()[0].platform
    on_cpu = platform == "cpu"
    n = 1_000_000 if not on_cpu else 100_000
    n_pad = 1 << (n - 1).bit_length()  # next power of two

    # corpus lives its whole life in HBM; padding rows are zero vectors and
    # are excluded ONLY by the valid mask (their L2 score 1/(1+||q||^2) is
    # not self-suppressing — do not weaken the mask)
    key = jax.random.PRNGKey(7)
    vectors = jax.random.normal(key, (n, d), dtype=jnp.float32)
    vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
    norms = jnp.sum(vectors * vectors, axis=-1)
    valid = jnp.arange(n_pad) < n

    fn = jit_knn(k=k, similarity="l2_norm")

    # ---- single-batch latency (one dispatch + one fetch) ----
    queries0 = jnp.asarray(rng.standard_normal((100, d)).astype(np.float32))
    np.asarray(fn(vectors, norms, valid, queries0)[0])  # warmup/compile
    lat = []
    for _ in range(8):
        t0 = time.perf_counter()
        np.asarray(fn(vectors, norms, valid, queries0)[0])
        lat.append(time.perf_counter() - t0)
    p50_batch = float(np.median(lat))

    # ---- throughput autotune: many chunks in ONE dispatch, one fetch ----
    import functools

    def many(base_fn, **kw):
        f = functools.partial(base_fn, k=k, similarity="l2_norm", **kw)

        def run(v, nrm, ok, qs):  # qs [n_chunks, chunk_q, d]
            return jax.lax.map(lambda q: f(v, nrm, ok, q), qs)

        return jax.jit(run)

    variants = {
        "materializing": many(knn_topk),
        "streaming_32k": many(knn_topk_streaming, chunk=32_768),
    }
    if not on_cpu:
        variants["streaming_128k"] = many(knn_topk_streaming, chunk=131_072)

    n_chunks = 16 if not on_cpu else 4
    qs = jnp.asarray(
        rng.standard_normal((n_chunks, chunk_q, d)).astype(np.float32)
    )
    total_q = n_chunks * chunk_q

    def timed(jfn, reps):
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(jfn(vectors, norms, valid, qs)[0])
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls))

    picks = {}
    errors = {}
    for name, jfn in variants.items():
        try:
            np.asarray(jfn(vectors, norms, valid, qs)[0])  # compile+warm
            picks[name] = timed(jfn, 2)
        except Exception as e:  # noqa: BLE001 - a variant may OOM; skip it
            errors[name] = str(e)[:120]
            print(f"bench variant [{name}] failed: {e}", file=sys.stderr)
    if not picks:
        # surface the per-variant failures: stderr is discarded by the
        # parent, so the reasons must ride the JSON error line
        raise RuntimeError(f"all variants failed: {errors}")
    best = min(picks, key=picks.get)
    wall = timed(variants[best], 5)
    qps = total_q / wall

    # ---- CPU baseline + recall reference over a device-pulled subsample ----
    sub = min(n, 100_000)
    sub_vec = np.asarray(vectors[:sub])
    sub_norms = np.asarray(norms[:sub])
    q_host = np.asarray(queries0)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        dots = q_host @ sub_vec.T
        d_sq = (q_host**2).sum(-1, keepdims=True) - 2 * dots + sub_norms[None, :]
        cpu_scores = 1.0 / (1.0 + np.maximum(d_sq, 0.0))
        _ = np.argpartition(-cpu_scores, k, axis=1)[:, :k]
    cpu_dt = (time.perf_counter() - t0) / reps
    cpu_qps = 100 / (cpu_dt * (n / sub))  # extrapolated to full corpus

    sub_pad = 1 << (sub - 1).bit_length()
    sub_vecs_dev = jnp.pad(vectors[:sub], ((0, sub_pad - sub), (0, 0)))
    sub_ids = np.asarray(
        fn(sub_vecs_dev, jnp.sum(sub_vecs_dev * sub_vecs_dev, -1),
           jnp.arange(sub_pad) < sub, queries0)[1]
    )
    recall_hits = 0
    for i in range(100):
        exact = set(np.lexsort((np.arange(sub), -cpu_scores[i]))[:k].tolist())
        recall_hits += len(exact & set(sub_ids[i].tolist()))
    recall = recall_hits / (100 * k)

    print(json.dumps({
        "metric": f"exact_knn_qps_{n // 1000}k_{d}d_top{k}",
        "value": round(qps, 1),
        "unit": "queries/s",
        "vs_baseline": round(qps / cpu_qps, 2),
        "p50_batch100_ms": round(p50_batch * 1000, 2),
        f"dispatch_wall_ms_{total_q}q": round(wall * 1000, 2),
        "recall_at_10": round(recall, 4),
        "platform": platform,
        "variant": best,
        "variant_walls_ms": {k_: round(v_ * 1000, 1)
                             for k_, v_ in picks.items()},
        "variant_errors": errors,
    }))


if __name__ == "__main__":
    if "--mesh-child" in sys.argv:
        try:
            mesh_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--mesh-gate" in sys.argv:
        sys.exit(mesh_gate_parent())
    if "--mesh" in sys.argv:
        sys.exit(mesh_parent())
    if "--profile-child" in sys.argv:
        try:
            profile_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--concurrency-child" in sys.argv:
        try:
            concurrency_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--gate-child" in sys.argv:
        try:
            gate_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--otel-child" in sys.argv:
        try:
            otel_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--ann-child" in sys.argv:
        try:
            ann_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--ann-gate" in sys.argv:
        sys.exit(ann_gate_parent())
    if "--ann" in sys.argv:
        sys.exit(ann_parent())
    if "--fused-knn-child" in sys.argv:
        try:
            fused_knn_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--fused-knn-gate" in sys.argv:
        sys.exit(fused_knn_gate_parent())
    if "--fused-knn" in sys.argv:
        sys.exit(fused_knn_parent())
    if "--tail-child" in sys.argv:
        try:
            tail_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--tail-gate" in sys.argv:
        sys.exit(tail_gate_parent())
    if "--tail" in sys.argv:
        sys.exit(tail_parent())
    if "--roofline-child" in sys.argv:
        try:
            roofline_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--roofline" in sys.argv:
        sys.exit(roofline_parent())
    if "--heat-child" in sys.argv:
        try:
            heat_child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--heat-overhead" in sys.argv:
        sys.exit(heat_parent())
    if "--otel-overhead" in sys.argv:
        sys.exit(otel_parent())
    if "--gate" in sys.argv:
        sys.exit(gate_parent())
    if "--concurrency" in sys.argv:
        sys.exit(concurrency_parent())
    if "--profile" in sys.argv:
        sys.exit(profile_parent())
    if "--probe" in sys.argv:
        try:
            probe()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    if "--child" in sys.argv:
        try:
            child()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": "bench_error", "value": 0, "unit": "error",
                "vs_baseline": 0, "detail": str(e)[:200],
            }))
            sys.exit(1)
        sys.exit(0)
    sys.exit(parent())
