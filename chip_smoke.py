#!/usr/bin/env python3
"""chip_smoke.py — start the node on the chip and check its answers.

The quickest proof that the served path still runs on a TPU: this parent
(numpy + stdlib only — it never imports JAX, because a chip belongs to one
process) starts the normal single-node entry point as its one child,

    python -m opensearch_tpu.cli --node-name smoke --http-port P --data DIR

with JAX_PLATFORMS=tpu in the child's environment, so JAX itself cannot
fall back to a CPU, and drives it over HTTP the way a client would:
PUT /{index}, POST /_bulk, POST /{index}/_refresh, GET /{index}/_count,
POST /{index}/_search. Every answer is compared with a numpy brute-force
reference over the same vectors. Each phase sends one cold search, 32
sequential ones, a burst of 16 concurrent ones at the node's defaults, and
the same burst again with the dispatch batcher told to hold arrivals
(two of its dynamic settings, put back afterwards) so that the device
programs also run, and are checked, at a batch wider than one query.

  A  exact kNN, BASELINE.json config 1 (SIFT-1M class): 1,000,000 x 128-d
     float32, space_type l2, 1 shard, 0 replicas, k = 10, size = 10
  B  the shard-mesh program: the first 253,952 rows in a 4-shard index
     (63,488 a shard: every shard inside one padding bucket, so that what
     each chip holds says how the shards were placed, not how they padded)
  C  ANN: 262,144 rows mapped {"method": {"name": "ivf_pq"}} (defaults:
     nlist 128, m 8, ks 256, nprobe 8), recall@10 >= 0.95 against the same
     brute-force reference. On this mixture the index defaults stop near
     0.8 (1,024 clusters over 128 lists: a query's neighbours sit in more
     than 8 lists), so the judged requests carry k = 32 (a 128-candidate
     rescore pool) and method_parameters.nprobe = 32; 16 more requests at
     the defaults are answered, checked and reported without the floor.

Data (from --seed): a SIFT-like clustered mixture — 1,024 cluster centres
with gamma(2, 18) coordinates, each point its centre plus a 12-dimensional
latent offset (sigma 5) through one shared random basis plus N(0, 3)
noise, clipped to [0, 255] and rounded to integers as SIFT descriptors
are. Clustered, so IVF recall means something; low intrinsic dimension, so
nearest neighbours are not equidistant. Queries come from the same
mixture but are NOT rounded: a matmul that drops to one bf16 pass shows up
as a score error. Rows are generated in fixed blocks keyed by
(seed, block), so phase B's rows are phase A's first rows at any --docs.

Stdout is two lines of JSON. The first is the report: the device, per
phase what was loaded, timed, checked and launched, resident bytes, the
compile cache. The LAST line is the verdict, in the shape the chip check
reads and nothing more:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the node reported it from `jax.devices()`. Exit 0 only
if the child came up on a TPU and every check of every phase passed; a run
that never reached a device prints neither line. `--cpu-dry-run` (never
automatic) runs the same script against a CPU child at a small --docs so
it can be developed without a chip; its report says "dry_run": true,
"platform": "cpu", its verdict "ok": false with a cpu device, and neither
can be taken for a pass on the chip.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
DIMS = 128
K = 10
BLOCK_ROWS = 65_536
N_CENTERS = 1024
LATENT = 12
LATENT_SIGMA = 5.0
BULK_DOCS = 10_000
N_SEQUENTIAL = 32
N_BURST = 16
RECALL_FLOOR = 0.95          # the repo's own ratchet (BASELINE.json's floor)
# phase C's judged requests: the index defaults (nprobe 8, 64-candidate
# pool) do not reach the floor on this mixture — see the module docstring
ANN_REQUEST = {"k": 32, "method_parameters": {"nprobe": 32}}
SCORE_RTOL = 1e-5
FULL_PART_DOCS = 262_144     # phases B and C
# phase B leaves a 32nd out: 262,144 rows over 4 shards come to 65,536 a
# shard give or take the routing hash, and a shard of 65,537 rows pads to
# twice the rows of one of 65,535
MESH_PART = 31 / 32
# the second burst: the batcher holds arrivals while a launch is in flight
# and flushes them as one batch. At the defaults its per-key tuner has
# just learned from the sequential searches that this traffic is solo,
# gives every arrival a zero wait, and launches each of the 16 alone.
HOLD_ARRIVALS = {"search.knn.batch.auto_tune": False,
                 "search.knn.batch.max_wait_ms": "100ms"}
DEADLINE_S = 1150            # the contract allows 1200, compilation included
BOOT_TIMEOUT_S = 300


T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------


class Mixture:
    def __init__(self, seed: int):
        self.seed = seed
        root = np.random.default_rng([seed, 0])
        self.centers = root.gamma(2.0, 18.0, (N_CENTERS, DIMS))
        self.basis = root.standard_normal((LATENT, DIMS))

    def _draw(self, rng: np.random.Generator, rows: int) -> np.ndarray:
        a = rng.integers(0, N_CENTERS, rows)
        z = rng.standard_normal((rows, LATENT)) * LATENT_SIGMA
        x = self.centers[a] + z @ self.basis + rng.normal(0, 3.0, (rows, DIMS))
        return np.clip(x, 0.0, 255.0)

    def corpus(self, n: int) -> np.ndarray:
        """[n, 128] float32, integer-valued; row i is the same at any n."""
        out = np.empty((n, DIMS), np.float32)
        for block, lo in enumerate(range(0, n, BLOCK_ROWS)):
            rng = np.random.default_rng([self.seed, 1, block])
            rows = self._draw(rng, BLOCK_ROWS)
            hi = min(lo + BLOCK_ROWS, n)
            out[lo:hi] = np.rint(rows[: hi - lo])
        return out

    def queries(self, stream: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 2, stream])
        return self._draw(rng, n).astype(np.float32)


class Reference:
    """Brute-force fp32 kNN over the same vectors, independent of the code
    under test: one BLAS pass ranks, then the candidates' distances are
    recomputed in float64 so the comparison has no rounding of its own."""

    def __init__(self, corpus: np.ndarray):
        self.corpus = corpus
        self.norms = np.einsum("nd,nd->n", corpus, corpus, dtype=np.float64)

    def d2(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        diff = self.corpus[ids].astype(np.float64) - q.astype(np.float64)
        return np.einsum("nd,nd->n", diff, diff)

    def topk(self, q: np.ndarray, margin: int = 64):
        """(ids [K] ascending distance then id, exact d2 of each)."""
        approx = self.norms - 2.0 * (self.corpus @ q).astype(np.float64)
        cand = np.argpartition(approx, margin)[:margin]
        exact = self.d2(q, cand)
        order = np.lexsort((cand, exact))[:K]
        return cand[order], exact[order]


# --------------------------------------------------------------------------
# the child and its HTTP surface
# --------------------------------------------------------------------------


class Client:
    """One keep-alive connection; one per thread."""

    def __init__(self, port: int, timeout: float = 900.0):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def call(self, method: str, path: str, body=None,
             ndjson: bool = False) -> dict:
        data = None
        if body is not None:
            data = body if isinstance(body, bytes) else json.dumps(body).encode()
        ctype = "application/x-ndjson" if ndjson else "application/json"
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": ctype})
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status >= 300:
            raise SmokeFailure(
                f"{method} {path} -> HTTP {resp.status}: {payload[:400]!r}")
        return json.loads(payload)

    def close(self) -> None:
        self.conn.close()


class Child:
    def __init__(self, platform: str, data_dir: Path, port: int):
        env = dict(os.environ)
        # the guard against a hidden CPU is JAX's own: with this set a
        # process that finds no such device dies at its first touch of JAX.
        # JAX_COMPILATION_CACHE_DIR passes through unchanged.
        env["JAX_PLATFORMS"] = platform
        env["PYTHONPATH"] = os.pathsep.join(
            [str(HERE)] + [p for p in [env.get("PYTHONPATH")] if p])
        self.port = port
        self.lines: list[str] = []
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "opensearch_tpu.cli", "--node-name",
             "smoke", "--http-port", str(port), "--data", str(data_dir)],
            cwd=str(HERE), env=env, stdout=subprocess.PIPE, text=True)
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))
            print("[child]", line.rstrip("\n"), file=sys.stderr, flush=True)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def started(self) -> dict | None:
        for line in list(self.lines):
            m = re.search(r"started=(\{.*\})\s*$", line)
            if m:
                return json.loads(m.group(1))
        return None

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while time.monotonic() < deadline:
            if not self.alive():
                raise SmokeFailure(
                    f"node exited with code {self.proc.returncode} before "
                    f"it served /_cluster/health")
            c = Client(self.port, timeout=5.0)
            try:
                c.call("GET", "/_cluster/health")
                return
            except (OSError, http.client.HTTPException):
                time.sleep(0.5)
            finally:
                c.close()
        raise SmokeFailure(f"no /_cluster/health in {BOOT_TIMEOUT_S}s")

    def stop(self) -> None:
        # SIGTERM, then SIGKILL. (Not SIGINT: a shell that started this
        # script in the background leaves SIGINT ignored in its children.)
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=20)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for e in os.scandir(path) if e.is_file())


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def ingest(client: Client, child: Child, index: str,
           corpus: np.ndarray) -> float:
    t0 = time.monotonic()
    for lo in range(0, corpus.shape[0], BULK_DOCS):
        check(child.alive(), "node died during ingest")
        rows = corpus[lo:lo + BULK_DOCS].astype(np.int32).tolist()
        lines = []
        for i, row in enumerate(rows, start=lo):
            lines.append('{"index":{"_id":"%d"}}' % i)
            lines.append('{"v":[%s]}' % ",".join(map(str, row)))
        resp = client.call("POST", f"/{index}/_bulk",
                           ("\n".join(lines) + "\n").encode(), ndjson=True)
        check(resp.get("errors") is False,
              f"_bulk into [{index}] at doc {lo} reported errors")
        check(len(resp["items"]) == len(rows),
              f"_bulk into [{index}] acknowledged the wrong item count")
    return time.monotonic() - t0


def knn_body(q: np.ndarray, params: dict) -> dict:
    knn = {"vector": [float(x) for x in q], "k": K, **params}
    return {"size": K, "query": {"knn": {"v": knn}}}


def judge(resp: dict, q: np.ndarray, ref: Reference, n_docs: int,
          n_shards: int, k_req: int, exact: bool) -> dict:
    """Hold one answer to the reference. Returns {recall, score_err}."""
    shards = resp["_shards"]
    check(shards["failed"] == 0, f"_shards.failed = {shards['failed']}")
    check(shards["total"] == n_shards and shards["successful"] == n_shards,
          f"_shards = {shards}, expected {n_shards} successful")
    total = resp["hits"]["total"]["value"]
    # the k-NN plugin's k applies per shard
    check(K <= total <= min(k_req * n_shards, n_docs),
          f"hits.total = {total} for k={k_req} over {n_shards} shard(s)")
    hits = resp["hits"]["hits"]
    check(len(hits) == K, f"{len(hits)} hits returned, expected {K}")
    ids = np.asarray([int(h["_id"]) for h in hits])
    scores = np.asarray([h["_score"] for h in hits], np.float64)
    check(len(set(ids.tolist())) == K, f"duplicate ids in {ids.tolist()}")
    check(bool(np.all(np.diff(scores) <= 0)), "hits not in score order")
    d2 = ref.d2(q, ids)
    want = 1.0 / (1.0 + d2)
    rel_err = np.abs(scores - want) / want
    ref_ids, ref_d2 = ref.topk(q)
    recall = len(set(ids.tolist()) & set(ref_ids.tolist())) / K
    if exact:
        # fp32 tolerance, set from the dtype: the served score is
        # 1 / (1 + |q|^2 - 2 q.x + |x|^2) in float32, whose rounding is
        # eps * (|q|^2 + |x|^2) absolute — relative to a small distance
        # between two long vectors that exceeds 1e-5, so the bound is the
        # larger of the two. One bf16 pass is ~1e4 times outside it.
        tol = np.maximum(SCORE_RTOL, 4 * 2.0 ** -23 * (
            float(q.astype(np.float64) @ q) + ref.norms[ids]) / (1.0 + d2))
        check(bool(np.all(rel_err <= tol)),
              f"_score off by {rel_err.max():.3g} relative "
              f"(ids {ids.tolist()}, tolerance {tol.min():.3g})")
        tenth = 1.0 / (1.0 + ref_d2[-1])
        for doc, s, t in zip(ids.tolist(), want.tolist(), tol.tolist()):
            check(doc in ref_ids or s >= tenth * (1.0 - t),
                  f"id {doc} (reference score {s:.9g}) is not in the "
                  f"reference top-{K} (10th scores {tenth:.9g})")
    return {"recall": recall, "score_err": float(rel_err.max())}


def family_launches(client: Client) -> dict:
    report = client.call("GET", "/_roofline")
    return {row["family"]: row["launches"] for row in report["families"]}


def knn_batch_stat(client: Client, key: str):
    stats = client.call("GET", "/_nodes/stats/knn_batch")
    return next(iter(stats["nodes"].values()))["knn_batch"][key]


class Phase:
    """One index: load it, then drive searches whose every answer is held
    to the brute-force reference over the same rows."""

    def __init__(self, name: str, client: Client, child: Child, index: str,
                 n_shards: int, corpus: np.ndarray, exact: bool):
        self.name, self.client, self.child = name, client, child
        self.index, self.n_shards, self.exact = index, n_shards, exact
        self.corpus = corpus
        self.ref = Reference(corpus)
        self.families_before = family_launches(client)

    def log(self, msg: str) -> None:
        log(f"phase {self.name}: {msg}")

    def load(self, mapping: dict) -> dict:
        n = self.corpus.shape[0]
        self.log(f"{n} x {DIMS} into [{self.index}], "
                 f"{self.n_shards} shard(s)")
        self.client.call("PUT", f"/{self.index}", {
            "settings": {"number_of_shards": self.n_shards,
                         "number_of_replicas": 0},
            "mappings": {"properties": {"v": mapping}},
        })
        ingest_s = ingest(self.client, self.child, self.index, self.corpus)
        self.log(f"ingest {ingest_s:.1f}s "
                 f"({n / ingest_s:.0f} docs/s over HTTP)")
        t0 = time.monotonic()
        refreshed = self.client.call("POST", f"/{self.index}/_refresh")
        refresh_s = time.monotonic() - t0
        check(refreshed["_shards"]["failed"] == 0, "_refresh reported failures")
        count = self.client.call("GET", f"/{self.index}/_count")
        check(count["count"] == n,
              f"_count = {count['count']} after {n} acknowledged writes")
        check(count["_shards"]["failed"] == 0, "_count reported shard failures")
        self.log(f"refresh {refresh_s:.1f}s, _count ok")
        return {"index": self.index, "docs": n, "dims": DIMS,
                "shards": self.n_shards, "ingest_s": round(ingest_s, 2),
                "refresh_s": round(refresh_s, 2)}

    def search(self, c: Client, q: np.ndarray, params: dict,
               verdicts: list) -> float:
        t = time.monotonic()
        resp = c.call("POST", f"/{self.index}/_search", knn_body(q, params))
        wall = time.monotonic() - t
        verdicts.append(judge(
            resp, q, self.ref, self.corpus.shape[0], self.n_shards,
            params.get("k", K), self.exact))
        return wall

    def sequential(self, queries: np.ndarray, params: dict,
                   verdicts: list) -> dict:
        """The first search is timed apart: it compiles."""
        first_s = self.search(self.client, queries[0], params, verdicts)
        self.log(f"first search {first_s:.2f}s (compile) with "
                 f"{params or 'default parameters'}")
        warm = [self.search(self.client, q, params, verdicts) * 1e3
                for q in queries[1:]]
        return {"first_search_s": round(first_s, 3),
                "warm_search_ms": [round(w, 2) for w in warm]}

    def burst(self, queries: np.ndarray, params: dict,
              verdicts: list) -> dict:
        n = len(queries)
        barrier = threading.Barrier(n)
        walls: list = [None] * n
        errors: list = []

        def one(i: int) -> None:
            c = Client(self.child.port)
            try:
                barrier.wait(timeout=60)
                walls[i] = self.search(c, queries[i], params, verdicts) * 1e3
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append(e)
            finally:
                c.close()

        launches_before = knn_batch_stat(self.client, "dispatches")
        t0 = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall_ms = (time.monotonic() - t0) * 1e3
        if errors:
            raise errors[0]
        check(all(w is not None for w in walls),
              "a burst search never returned")
        launches = knn_batch_stat(self.client, "dispatches") - launches_before
        return {"clients": n, "wall_ms": round(wall_ms, 2),
                "search_ms": [round(w, 2) for w in walls],
                "launches": launches}

    def batched_burst(self, queries: np.ndarray, params: dict,
                      verdicts: list) -> dict:
        """A burst the batcher coalesces (see HOLD_ARRIVALS): the phase's
        program at B > 1, through the same route, every answer judged."""
        self.client.call("PUT", "/_cluster/settings",
                         {"transient": HOLD_ARRIVALS})
        coalesced_before = knn_batch_stat(self.client, "coalesced_batches")
        out = self.burst(queries, params, verdicts)
        out["coalesced_launches"] = (
            knn_batch_stat(self.client, "coalesced_batches")
            - coalesced_before)
        self.client.call("PUT", "/_cluster/settings",
                         {"transient": dict.fromkeys(HOLD_ARRIVALS)})
        check(out["coalesced_launches"] >= 1,
              f"no launch of the held burst served more than one query "
              f"({out['launches']} launches for {out['clients']} searches)")
        return out

    def families(self) -> dict:
        after = family_launches(self.client)
        launched = {f: after[f] - self.families_before.get(f, 0)
                    for f in after
                    if after[f] - self.families_before.get(f, 0) > 0}
        check(bool(launched),
              "no kernel family recorded a launch in this phase")
        return launched


def summary(verdicts: list, params: dict) -> dict:
    return {"answers_checked": len(verdicts),
            "recall_at_10": round(
                float(np.mean([v["recall"] for v in verdicts])), 4),
            "max_score_rel_err": max(v["score_err"] for v in verdicts),
            "search_parameters": params}


def run_phase(name: str, client: Client, child: Child, index: str,
              mapping: dict, n_shards: int, corpus: np.ndarray,
              queries: np.ndarray, held_queries: np.ndarray, exact: bool,
              params: dict, default_queries: np.ndarray | None = None,
              enforce_floor: bool = True) -> dict:
    phase = Phase(name, client, child, index, n_shards, corpus, exact)
    out = phase.load(mapping)
    verdicts: list = []
    out.update(phase.sequential(queries[:1 + N_SEQUENTIAL], params, verdicts))
    out["burst"] = phase.burst(queries[1 + N_SEQUENTIAL:], params, verdicts)
    out.update(summary(verdicts, params))
    held: list = []
    out["batched_burst"] = {
        **phase.batched_burst(held_queries, params, held),
        **summary(held, params)}
    if not exact:
        # the held burst's answers come from launches wider than one
        # query, so they meet the floor on their own, not diluted among
        # the rest. Only a dry run at a toy size goes without a floor:
        # IVF over 1,024 clusters says nothing about a few thousand rows.
        out["recall_floor_enforced"] = enforce_floor
        for what, got in (("", out), ("held burst: ", out["batched_burst"])):
            check(got["recall_at_10"] >= RECALL_FLOOR or not enforce_floor,
                  f"{what}recall@{K} {got['recall_at_10']:.4f} < "
                  f"{RECALL_FLOOR} over {got['answers_checked']} queries "
                  f"with {params}")
        # the index defaults, on fresh queries: answered and checked like
        # the rest, reported without the floor (see the module docstring)
        default_verdicts: list = []
        out["index_defaults"] = {
            **phase.sequential(default_queries, {}, default_verdicts),
            **summary(default_verdicts, {})}
    out["families"] = phase.families()
    phase.log(f"{len(verdicts) + len(held)} answers checked, recall@{K} "
              f"{out['recall_at_10']:.4f} (held burst "
              f"{out['batched_burst']['recall_at_10']:.4f} in "
              f"{out['batched_burst']['launches']} launches), "
              f"families {out['families']}")
    return out


def residency(client: Client) -> dict:
    stats = client.call("GET", "/_nodes/stats")
    node = next(iter(stats["nodes"].values()))
    device = node["device"]
    return {
        "resident_bytes": device["resident_bytes"],
        "by_device": device["by_device"],
        "structures": [
            {k: s[k] for k in ("index", "field", "kind", "device", "bytes",
                               "by_device") if k in s}
            for s in device["structures"]],
        "backend_memory": device["backend_memory"],
        "identity_ok": device["identity_ok"],
        "knn_batch": {k: node["knn_batch"][k] for k in (
            "dispatches", "merged_queries", "mean_merged_batch", "max_batch")},
        "ann": node["knn_batch"]["ann"],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs", type=int, default=1_000_000,
                    help="phase A rows (a cut is printed as such)")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="develop the script against a CPU child; the "
                         "result can never count as a pass on the chip")
    args = ap.parse_args(argv)

    if not (HERE / "opensearch_tpu" / "cli.py").is_file():
        print(f"chip_smoke: {HERE} holds no opensearch_tpu/ — this script "
              f"drives the repository it ships with", file=sys.stderr)
        return 2

    platform = "cpu" if args.cpu_dry_run else "tpu"
    part_docs = min(FULL_PART_DOCS, args.docs)
    n_queries = 1 + N_SEQUENTIAL + N_BURST
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(HERE / ".jax_cache")
    cache_before = cache_entries(cache_dir)

    data_dir = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    child = Child(platform, data_dir, free_port())
    # one clock for the whole run: a hang anywhere ends as a failure inside
    # the contract's time limit instead of outliving it
    def give_up() -> None:
        print(f"chip_smoke: not done after {DEADLINE_S}s — giving up",
              file=sys.stderr, flush=True)
        child.stop()
        os._exit(3)

    watchdog = threading.Timer(DEADLINE_S, give_up)
    watchdog.daemon = True
    watchdog.start()

    result: dict = {"ok": False, "dry_run": args.cpu_dry_run,
                    "seed": args.seed}
    failure = None
    try:
        child.wait_healthy()
        started = child.started()
        check(started is not None, "node printed no started= line")
        dev = started["device"]
        log(f"node up on {dev}")
        check(dev["platform"] == platform,
              f"node came up on [{dev['platform']}], wanted [{platform}]")
        result.update({
            "device": dev, "platform": dev["platform"],
            "device_kind": dev["kind"], "device_count": dev["count"],
            "native_available": started["native_available"],
            "compile_cache_dir": started["compile_cache_dir"],
        })
        check(started["compile_cache_dir"] == cache_dir,
              f"node keeps its compile cache in "
              f"[{started['compile_cache_dir']}], expected [{cache_dir}]")

        client = Client(child.port)
        peaks = client.call("GET", "/_roofline")["peaks"]
        check(peaks["platform"] == platform and peaks["source"] == "measured",
              f"/_roofline peaks are {peaks['platform']}/{peaks['source']}, "
              f"wanted {platform}/measured")
        result["roofline_peaks"] = peaks

        mix = Mixture(args.seed)
        corpus = mix.corpus(args.docs)
        result["data"] = {
            "mixture": f"{N_CENTERS} gamma(2,18) centres + {LATENT}-d latent "
                       f"(sigma {LATENT_SIGMA:g}) through a shared basis + N(0,3), "
                       f"clipped to [0,255]; corpus rounded to integers, "
                       f"queries not",
            "docs_cut": None if args.docs >= 1_000_000 else
            f"phase A holds {args.docs} rows (not 1,000,000), phases B and "
            f"C {part_docs} (not {FULL_PART_DOCS}; B a 32nd less)",
        }
        plain = {"type": "knn_vector", "dimension": DIMS, "space_type": "l2"}
        phases = result.setdefault("phases", {})
        resident = result.setdefault("resident", {})

        phases["A"] = run_phase(
            "A", client, child, "smoke-exact", plain, 1, corpus,
            mix.queries(0, n_queries), mix.queries(4, N_BURST), True, {})
        resident["A"] = residency(client)

        phases["B"] = run_phase(
            "B", client, child, "smoke-mesh", plain, 4,
            corpus[:int(part_docs * MESH_PART)],
            mix.queries(1, n_queries), mix.queries(5, N_BURST), True, {})
        resident["B"] = residency(client)
        mesh_width = max(w for w in (1, 2, 4) if w <= dev["count"])
        bundles = [s for s in resident["B"]["structures"]
                   if s["index"] == "smoke-mesh" and s["kind"] == "mesh_bundle"]
        check(len(bundles) == 1
              and bundles[0]["device"] == f"mesh[{mesh_width}]",
              f"phase B bundle is {bundles}, expected one on "
              f"mesh[{mesh_width}]")
        # placement, from the node's own ledger: every chip of the mesh holds
        # its own shard of smoke-mesh (segment columns and bundle slice) and
        # no other, so no chip holds more than 1.1 x the mean
        chips: dict = {}
        for row in resident["B"]["structures"]:
            if row["index"] == "smoke-mesh":
                for name, held in row.get(
                        "by_device", {row["device"]: row["bytes"]}).items():
                    chips[name] = chips.get(name, 0) + held
        phases["B"]["resident_by_chip"] = chips
        mean = sum(chips.values()) / mesh_width
        # a dry run at a toy size goes without the bound: a few thousand
        # rows a shard straddle a padding bucket whatever the size
        check(len(chips) == mesh_width and (
            max(chips.values()) <= 1.1 * mean
            or (platform != "tpu" and part_docs < FULL_PART_DOCS)),
              f"phase B is not placed shard by chip over {mesh_width} "
              f"chip(s): resident bytes {chips}")
        if mesh_width > 1 and platform == "tpu":
            # the CPU backend of a dry run reports no memory statistics
            share = bundles[0]["bytes"] // mesh_width
            held = [m.get("bytes_in_use") for m in
                    resident["B"]["backend_memory"]]
            check(all(h is not None and h >= share
                      for h in held[:mesh_width]),
                  f"not every mesh device holds a {share}-byte shard of "
                  f"the bundle: bytes_in_use = {held}")

        phases["C"] = run_phase(
            "C", client, child, "smoke-ann",
            {**plain, "method": {"name": "ivf_pq"}}, 1, corpus[:part_docs],
            mix.queries(2, n_queries), mix.queries(6, N_BURST), False,
            ANN_REQUEST, default_queries=mix.queries(3, N_BURST),
            enforce_floor=platform == "tpu" or part_docs >= FULL_PART_DOCS)
        resident["C"] = residency(client)
        builds = resident["C"]["ann"]["index_builds"]
        check(builds["builds"] >= 1, "phase C built no IVF-PQ index")
        phases["C"]["index_build_s"] = round(builds["build_wall_ns"] / 1e9, 2)

        # what the policy says ran is what ran
        resolved = resident["C"]["ann"]["resolved"]
        launched = {f for p in phases.values() for f in p["families"]}
        exact_family = "mesh_knn_fused[fp32]"   # either lowering of the scan
        ann_family = ("ivfpq_adc_pallas[fp32]"
                      if resolved["kernel"] == "pallas" else "ivfpq_search[fp32]")
        check(exact_family in launched and ann_family in launched,
              f"policy resolves to {resolved} but the launched families "
              f"are {sorted(launched)}")
        check(all(r["identity_ok"] for r in resident.values()),
              "device residency ledger identity broken")
        check(child.alive(), "node died before the end of the run")
        result["kernels"] = resolved
        result["compile_cache_entries"] = {
            "before": cache_before, "after": cache_entries(cache_dir)}
        result["wall_s"] = round(time.monotonic() - T0, 1)
        passed = True
    except (SmokeFailure, OSError, http.client.HTTPException, KeyError) as e:
        failure = f"{type(e).__name__}: {e}"
        passed = False
    finally:
        child.stop()
        watchdog.cancel()
        shutil.rmtree(data_dir, ignore_errors=True)

    if failure is not None:
        print(f"chip_smoke FAILED: {failure}", file=sys.stderr, flush=True)
        if "device" not in result:
            return 1  # never reached a device: no result line at all
        result["failure"] = failure
    if args.cpu_dry_run:
        result["dry_run_checks_passed"] = passed
    else:
        result["ok"] = passed
    result["claim"] = None  # a smoke claims no performance
    print(json.dumps(result), flush=True)
    # the verdict, last and alone: exactly what the chip check parses
    dev = result["device"]
    print(json.dumps({"ok": result["ok"], "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
