#!/usr/bin/env python3
"""tpu-search's benchmark: one run of one cell.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of `BENCHMARK.json`'s `workloads`: a configuration
(`perf/configs/<config>.json`) under a traffic mix
(`perf/mixes/<traffic>.json`). This parent (numpy + stdlib, never JAX)
starts the program's normal single-node entry as its one child with
JAX_PLATFORMS=tpu, fills or recovers the configuration's data directory
(one fixed corpus; the queries are drawn from --seed), warms the cell's own
shapes, drives
`POST /{index}/_search` over HTTP for `--seconds`, and then holds every
reply of the window to a numpy brute-force reference over the same rows
(worked out beside the node's boot and, what is left of it, after the
window: never while the timed path runs, and not inside `setup_s`).

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (with `--trace 1` also `breakdown`), then the
benchmark's own keys, `checks` last. A run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no such line.
`--cpu-dry-run` (never automatic) drives the same code against a CPU child
at a small `--docs`; its line says "dry_run": true and a cpu device, and
cannot be taken for a result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

T0 = time.monotonic()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
MANIFEST = ROOT / "BENCHMARK.json"
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from perf import judge, trace, work  # noqa: E402
from perf.data import Mixture, Reference, bulk_bodies  # noqa: E402
from perf.node import Child, Client, RunFailure  # noqa: E402
from perf.stats import percentile  # noqa: E402
from perf.traffic import burst, drive  # noqa: E402

TRACE_AFTER_S = 2.0     # into the window before the profiler starts
TRACE_SECONDS = 3.0
TRACE_EDGE_S = 0.25    # left out at each end: the profiler's own stall
COUNTER_KEYS = ("dispatches", "merged_queries")


def log(msg: str) -> None:
    print(f"[perf +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_reader(kind: str, name: str):
    """`perf/<kind>/<name>.py`'s `read(run)`; a metric is its own file."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(manifest: dict, workload: str) -> SimpleNamespace:
    """The cell, its files and the metrics it reports, from the manifest."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perf: no workload [{workload}] in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]

    def mine(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return SimpleNamespace(
        cell=cell,
        config=json.loads((ROOT / conf["file"]).read_text()),
        mix=json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text()),
        end_to_end=[m for m in manifest["end_to_end"] if mine(m)],
        per_layer=[m for m in manifest["per_layer"] if mine(m)])


def knn_stats(client: Client) -> dict:
    stats = client.call("GET", "/_nodes/stats/knn_batch")
    got = next(iter(stats["nodes"].values()))["knn_batch"]
    return {"t": time.perf_counter(), **{k: got[k] for k in COUNTER_KEYS}}


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for e in os.scandir(path) if e.is_file())


class Run:
    """What the metric readers see of a run."""

    def __init__(self, spec: SimpleNamespace, docs: int, device: dict):
        self.cell, self.config, self.mix = spec.cell, spec.config, spec.mix
        self.docs, self.device = docs, device
        self.peaks = None
        self.window = None
        self.judged = None
        self.numbers: dict = {}
        self.setup_s = 0.0
        self.counters: dict = {}
        self.trace = None

    def counter_delta(self, span: str) -> dict | None:
        pair = self.counters.get(span)
        if not pair:
            return None
        before, after = pair
        out = {k: after[k] - before[k] for k in COUNTER_KEYS}
        out["seconds"] = after["t"] - before["t"]
        return out


def fill(client: Client, child: Child, conf: dict, corpus: np.ndarray) -> int:
    """First run of this configuration in this checkout: the program's own
    write path, every acknowledgement checked. Returns documents
    acknowledged."""
    index = conf["index"]
    client.call("PUT", f"/{index}", conf["index_body"])
    acked, t0 = 0, time.monotonic()
    for lo, n, body in bulk_bodies(corpus, conf["field"],
                                   conf["bulk_docs_per_request"]):
        if not child.alive():
            raise RunFailure("node died during ingest")
        resp = client.call("POST", f"/{index}/_bulk", body, ndjson=True)
        if resp.get("errors") is not False or len(resp["items"]) != n:
            raise RunFailure(f"_bulk at doc {lo}: errors={resp.get('errors')}"
                             f", {len(resp['items'])} items for {n} docs")
        acked += n
    t1 = time.monotonic()
    refreshed = client.call("POST", f"/{index}/_refresh")
    if refreshed["_shards"]["failed"] != 0:
        raise RunFailure("_refresh reported shard failures")
    t2 = time.monotonic()
    client.call("POST", f"/{index}/_flush")
    log(f"filled [{index}]: ingest {t1 - t0:.1f}s "
        f"({acked / (t1 - t0):.0f} docs/s), refresh {t2 - t1:.1f}s, "
        f"flush {time.monotonic() - t2:.1f}s")
    return acked


def trace_part(child: Child, run: Run, trace_dir: Path, t_open: float,
               errors: list) -> None:
    """A few seconds of the window under `jax.profiler`, with the batcher's
    counters read just inside the traced span."""
    try:
        time.sleep(max(0.0, t_open + TRACE_AFTER_S - time.perf_counter()))
        c = Client(child.port, timeout=60.0)
        child.command(f"trace_start {trace_dir}", "trace_started", 60.0)
        before = knn_stats(c)
        time.sleep(TRACE_SECONDS)
        after = knn_stats(c)
        child.command("trace_stop", "trace_stopped", 120.0)
        c.close()
        run.counters["trace"] = (before, after)
    except Exception as e:  # noqa: BLE001 - re-raised by the caller
        errors.append(e)


def reduce_trace_dir(trace_dir: Path) -> dict | None:
    """The xplane needs JAX to be read: a CPU process of its own, once the
    node is gone."""
    out = trace_dir / "reduced.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(HERE / "trace.py"), str(trace_dir), str(out)],
        env=env, capture_output=True, text=True, timeout=240)
    if proc.returncode != 0:
        raise RunFailure(f"trace reduction failed: {proc.stderr[-2000:]}")
    return trace.reduce_trace(json.loads(out.read_text()), TRACE_EDGE_S)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="develop against a CPU child; never a result")
    ap.add_argument("--docs", type=int, help="dry run only: a smaller corpus")
    ap.add_argument("--cache-dir", default=str(HERE / ".cache"),
                    help="data directories and traces (git-ignored)")
    ap.add_argument("--control", action="store_true",
                    help="judge the lower-precision reference in the "
                         "program's place: correct must come out false")
    args = ap.parse_args(argv)
    if args.docs and not args.cpu_dry_run:
        ap.error("--docs belongs to --cpu-dry-run")

    if not (ROOT / "opensearch_tpu" / "cli.py").is_file():
        print(f"perf: {ROOT} holds no opensearch_tpu/ — the benchmark "
              f"drives the repository it ships with", file=sys.stderr)
        return 2
    manifest = json.loads(MANIFEST.read_text())
    spec = resolve(manifest, args.workload)
    conf, mix = spec.config, spec.mix
    readers = {m["name"]: load_reader(kind, m["name"])
               for kind, ms in (("end_to_end", spec.end_to_end),
                                ("layers", spec.per_layer)) for m in ms}
    platform = "cpu" if args.cpu_dry_run else "tpu"
    docs = args.docs or conf["docs"]
    size = conf["request"]["size"]
    shards = conf["index_body"]["settings"]["number_of_shards"]

    home = (Path(args.cache_dir) / conf["name"]
            / f"corpus-{conf['corpus_seed']}-docs-{docs}")
    ready = home / "READY"
    trace_dir = Path(args.cache_dir) / "trace" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    cold = not ready.is_file()
    if cold:
        shutil.rmtree(home, ignore_errors=True)
    (home / "node").mkdir(parents=True, exist_ok=True)

    # the corpus (from the configuration's corpus_seed: one fixed data set,
    # kept beside the data directory it filled), the queries (from --seed)
    # and the reference's answers to them are made on the host's spare
    # cores while the node boots and (first run in this checkout) ingests.
    # The answers are not waited for before the window: `spare` is cleared
    # from the first search to the window's close, so the reference rests
    # while the timed path runs, and what is left of it is worked out
    # afterwards, outside `setup_s`
    made: dict = {}
    corpus_ready = threading.Event()
    queries_ready = threading.Event()
    spare = threading.Event()
    spare.set()
    kept = home / "corpus.npy"

    def make_reference() -> None:
        try:
            mixture = Mixture(conf["corpus_seed"], conf["dims"])
            if not cold and kept.is_file():
                made["corpus"] = np.load(kept)
            else:
                made["corpus"] = mixture.corpus(docs)
                np.save(kept, made["corpus"])
            corpus_ready.set()
            made["queries"] = mixture.queries(args.seed, 0, mix["queries"])
            made["warm_queries"] = mixture.queries(args.seed, 1,
                                                   mix["queries"])
            queries_ready.set()
            made["ref"] = Reference(made["corpus"])
            made["ref_ids"], made["ref_d2"] = made["ref"].topk(
                made["queries"], size, gate=spare)
        except Exception as e:  # noqa: BLE001 - re-raised after join
            made["error"] = e
            corpus_ready.set()
            queries_ready.set()

    maker = threading.Thread(target=make_reference, daemon=True)
    maker.start()

    child = Child(platform, home / "node")
    tracer = None
    try:
        started = child.wait_healthy()
        dev = started["device"]
        log(f"node up on {dev} ({'first fill' if cold else 'recovered'})")
        if dev["platform"] != platform or dev["count"] < spec.cell["chips"]:
            raise RunFailure(f"node came up on {dev}; the cell wants "
                             f"{spec.cell['chips']} {platform} chip(s)")
        run = Run(spec, docs, dev)
        if not args.cpu_dry_run:
            run.peaks = work.peaks_for(dev["kind"])
        client = Client(child.port)
        index = conf["index"]
        if cold:
            corpus_ready.wait()
            if "error" in made:
                raise made["error"]
            acked = fill(client, child, conf, made["corpus"])
            ready.write_text(json.dumps({"acknowledged": acked}))
        acked = json.loads(ready.read_text())["acknowledged"]
        count = client.call("GET", f"/{index}/_count")
        if count["_shards"]["failed"] != 0:
            raise RunFailure("_count reported shard failures")
        count_gap = abs(count["count"] - acked) + abs(acked - docs)
        queries_ready.wait()
        if "error" in made:
            raise made["error"]
        queries = made["queries"]

        def bodies(qs: np.ndarray) -> list:
            return [json.dumps({"size": size, "query": {"knn": {conf["field"]: {
                "vector": [float(x) for x in q], **conf["request"]["knn"]}}}}
            ).encode() for q in qs]

        path = f"/{index}/_search"
        # warm-up: the first search builds and uploads what the index needs
        # on the device; then the mix itself, unmeasured, on other queries,
        # so that the widths its traffic reaches are compiled and the
        # batcher's tuner has seen this load
        warm = bodies(made["warm_queries"])
        timed = bodies(queries)
        spare.clear()
        status, payload = client.raw("POST", path, warm[0])
        if status != 200:
            raise RunFailure(f"first search -> HTTP {status}: {payload[:300]!r}")
        if mix.get("warmup_bursts"):
            held = mix["warmup_settings"]
            client.call("PUT", "/_cluster/settings", {"transient": held})
            at = 1
            for n in mix["warmup_bursts"]:
                got = burst(child.port, path, warm[at:at + n])
                if set(got) != {200}:
                    raise RunFailure(f"warm-up burst of {n} -> {got}")
                at += n
            client.call("PUT", "/_cluster/settings",
                        {"transient": dict.fromkeys(held)})
        warmed = drive(child.port, path, warm, mix, args.seed,
                       float(mix["warmup_seconds"]))
        bad = sum(1 for s in warmed.status if s != 200)
        if bad:
            raise RunFailure(f"{bad} of {len(warmed)} warm-up searches failed")
        cache_dir = started["compile_cache_dir"]
        compiled_before = cache_entries(cache_dir)
        errors: list = []
        run.setup_s = time.monotonic() - T0
        log(f"set-up {run.setup_s:.1f}s; window of {args.seconds:g}s opens "
            f"({len(warmed)} warm-up searches)")

        before = knn_stats(client)
        if args.trace:
            tracer = threading.Thread(
                target=trace_part, daemon=True,
                args=(child, run, trace_dir, time.perf_counter() + 0.05,
                      errors))
            tracer.start()
        run.window = drive(child.port, path, timed, mix, args.seed,
                           args.seconds)
        if tracer is not None:
            tracer.join(timeout=300)
            if errors:
                raise errors[0]
        run.counters["window"] = (before, knn_stats(client))
        spare.set()
        t_closed = time.monotonic()
        compiled = cache_entries(cache_dir) - compiled_before
        if compiled:
            log(f"WARNING: {compiled} program(s) compiled inside the window")
        memory = next(iter(client.call("GET", "/_nodes/stats")["nodes"]
                           .values()))["device"]["backend_memory"]
        peak = max((m.get("peak_bytes_in_use") or 0 for m in memory),
                   default=0)
        if not child.alive():
            raise RunFailure("node died before the end of the run")
        client.close()
    except (RunFailure, OSError, KeyError) as e:
        print(f"perf FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        child.stop()

    try:
        if args.trace:
            run.trace = reduce_trace_dir(trace_dir)
            shutil.rmtree(trace_dir / "plugins", ignore_errors=True)
    except (RunFailure, OSError, subprocess.TimeoutExpired) as e:
        print(f"perf FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1

    # the comparison, once the window has closed and the node is gone
    maker.join()
    if "error" in made:
        print(f"perf FAILED: reference: {made['error']!r}", file=sys.stderr,
              flush=True)
        return 1
    log(f"reference's answers ready {time.monotonic() - t_closed:.1f}s "
        f"after the window closed")
    ref = made["ref"]
    window = run.window
    if args.control:
        ids, scores = ref.topk_lower_precision(queries, size)
        window.payload = [judge.reply_bytes(ids[q], scores[q], shards)
                          for q in window.query]
        window.status = [200] * len(window)
    run.judged = judge.judge_window(window, queries, ref, made["ref_ids"],
                                    made["ref_d2"], size, shards)
    run.numbers = {**run.judged["numbers"], "count_gap": count_gap}
    correct, checks = judge.compare(run.numbers, conf["limits"])

    wanted = spec.per_layer if args.trace else spec.end_to_end
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(window),
              "failed": run.numbers["failed"] + run.numbers["malformed"],
              "metrics": metrics, "device": device}
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    delta = run.counter_delta("window")
    walls = window.wall_ms()
    result.update({
        "workload": args.workload, "seed": args.seed, "docs": docs,
        "first_fill": cold, "compiled_in_window": compiled,
        "launches": delta["dispatches"],
        "queries_per_launch": (delta["merged_queries"] / delta["dispatches"]
                               if delta["dispatches"] else None),
        "late_ms_max": max(window.late_s, default=0.0) * 1e3,
        "wall_ms": {f"p{p:g}": percentile(walls, p)
                    for p in (50, 90, 95, 99, 99.9, 100)},
    })
    if args.cpu_dry_run:
        result["dry_run"] = True
    if args.control:
        result["control"] = "reference with the query in bfloat16"
    result["checks"] = checks
    for name, c in checks.items():
        print(f"perf check {name} = {c['value']:.6g} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
