"""The files the benchmark holds for `knn-nested-chunks` (MS MARCO
documents split into 384-token chunks, each chunk's 768-d vector a nested
object, searched by a `nested` `knn` with score mode max): the kind
(`perf/kinds/knn-nested-chunks.py`: chunk counts from a length law, chunks
around a document's centre, queries from a target document's chunk, a
reference that takes a document's nearest chunk), the work function
(`perf/nested_work.py`), the three readers under `perf/layers/` and the
configuration's file. Arithmetic on small seeded data against a slow loop
in plain Python, on a hand-written capture and on hand-written device
planes; a planted fault (a document scored by its FIRST chunk) held to the
judge; then the cell itself through `perf/run.py --cpu-dry-run`, once."""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import threading
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _perf_dry import DOCS as DRY_DOCS, REPO, dry_run  # noqa: E402

sys.path.insert(0, str(REPO))

from perf import judge, trace, work  # noqa: E402
from perf.nested_work import nested_scan_work  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads((REPO / "perf/configs/knn-nested-chunks.json").read_text())
CELL = "knn-nested-chunks.c32"
ADDED = ["nested_scan_roofline", "nested.reduce_device_share",
         "nested.knn_ms"]
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
SIZE = CONFIG["request"]["size"]
DOCS = 1500             # the data set the slow loop is run over


def module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, REPO / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer(name: str):
    return module(f"perf/layers/{name}.py", "perf_layer_under_test")


KIND = module("perf/kinds/knn-nested-chunks.py", "perf_kind_nested_chunks")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """The data set at DOCS documents, as a first run makes and keeps it."""
    home = tmp_path_factory.mktemp("nested_docs")
    return KIND.dataset(CONFIG, DOCS, home, True), home


@pytest.fixture(scope="module")
def drawn(data):
    return KIND.queries(CONFIG, data[0], 2**31 + 41, 0, 128)


# -- the configuration's file and its manifest entries -------------------------


def test_the_configuration_keeps_every_width_of_the_source():
    shapes = CONFIG["source_shapes"]
    assert CONFIG["kind"] == "knn-nested-chunks"
    assert CONFIG["dims"] == 768 == shapes["dims"]
    assert CONFIG["request"] == {"size": 10, "score_mode": "max",
                                 "knn": {"k": 10}}
    assert shapes["size"] == shapes["k"] == 10
    assert shapes["score_mode"] == "max"
    assert (KIND.CHUNK_TOKENS, KIND.MAX_CHUNKS) == (
        shapes["chunk_tokens"], shapes["max_chunks"]) == (384, 100)
    body = CONFIG["index_body"]
    assert body["settings"] == {"number_of_shards": 1,
                                "number_of_replicas": 0}
    assert body["mappings"]["properties"] == {"passage_chunk_embedding": {
        "type": "nested", "properties": {"knn": {
            "type": "knn_vector", "dimension": 768, "space_type": "l2"}}}}
    assert CONFIG["field"] == "passage_chunk_embedding.knn"
    # the one cut, with its reason in the file
    assert CONFIG["reduced"] == ["docs"] == list(CONFIG["cuts"])
    assert CONFIG["docs"] < shapes["docs"] == 3_213_835
    assert CONFIG["work"] == {"kind": "nested_scan", "stored_bytes": 4}
    assert set(CONFIG["limits"]) == {"failed", "malformed", "count_gap",
                                     "score_gap", "rank_gap", "recall_at_10"}
    assert len(CONFIG["assumed"]) >= 5 and CONFIG["limits_why"]


def test_the_manifest_gains_one_configuration_one_cell_three_metrics():
    (conf,) = [c for c in MANIFEST["configs"]
               if c["name"] == "knn-nested-chunks"]
    assert conf["reduced"] == ["docs"] and len(conf["source"]) <= 200
    assert conf["source"] == CONFIG["source"]
    assert "MS MARCO Document Ranking" in conf["source"]
    assert "text_chunking" in conf["source"]
    assert conf["file"] == "perf/configs/knn-nested-chunks.json"
    (cell,) = [w for w in MANIFEST["workloads"]
               if w["config"] == "knn-nested-chunks"]
    assert cell == {"name": CELL, "config": "knn-nested-chunks",
                    "traffic": "c32", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    added = {m["name"]: m for m in MANIFEST["per_layer"]
             if m["name"] in ADDED}
    assert sorted(added) == sorted(ADDED)
    for m in added.values():
        assert m["workloads"] == [CELL] and m["moves"] == "qps"
        assert (REPO / "perf/layers" / f"{m['name']}.py").is_file()
    assert added["nested_scan_roofline"]["layer"] == "kernels"
    assert added["nested_scan_roofline"]["unit"] == "%"
    for name in ("nested.reduce_device_share", "nested.knn_ms"):
        assert added[name]["layer"] == "mesh program / per-shard ANN"
    assert added["nested.knn_ms"]["source"] == "program_span"
    # every other metric keeps its workloads: none names the new cell
    for m in MANIFEST["per_layer"]:
        if m["name"] not in ADDED:
            assert CELL not in m.get("workloads", ())


def test_the_new_files_import_nothing_of_the_program():
    for path in ("perf/kinds/knn-nested-chunks.py", "perf/nested_work.py",
                 *(f"perf/layers/{name}.py" for name in ADDED)):
        text = (REPO / path).read_text()
        assert "opensearch_tpu" not in text and "tests" not in text, path


# -- the data set, the documents, the queries ----------------------------------


def test_document_i_is_the_same_at_any_docs(data, tmp_path):
    full, _home = data
    fewer = KIND.dataset(CONFIG, 1100, tmp_path, True)
    assert np.array_equal(fewer["counts"], full["counts"][:1100])
    rows = fewer["ptr"][-1]
    assert np.array_equal(fewer["chunks"], full["chunks"][:rows])


def test_a_later_run_loads_what_the_first_kept(data):
    full, home = data
    again = KIND.dataset(CONFIG, DOCS, home, False)
    assert np.array_equal(again["chunks"], full["chunks"])
    assert np.array_equal(again["ptr"], full["ptr"])


def test_chunk_counts_follow_the_stated_law():
    counts = KIND._counts(CONFIG["corpus_seed"], 65_536)
    assert counts.min() == 1 and counts.max() <= KIND.MAX_CHUNKS
    assert counts.mean() == pytest.approx(3.84, abs=0.05)
    assert np.median(counts) == 3
    assert (counts == 1).mean() == pytest.approx(0.24, abs=0.02)
    assert (counts == KIND.MAX_CHUNKS).sum() >= 1      # the cut bites
    # the configuration's rows fit the program's power-of-two padding
    rows = KIND._counts(CONFIG["corpus_seed"], CONFIG["docs"]).sum()
    assert 0.9 * 2**19 < rows < 2**19


def test_a_documents_chunks_lie_near_each_other(data):
    full, _home = data
    chunks, ptr = full["chunks"].astype(np.float64), full["ptr"]
    many = [d for d in range(DOCS) if ptr[d + 1] - ptr[d] >= 3][:40]
    within = np.mean([np.sum((chunks[ptr[d]] - chunks[ptr[d] + 1]) ** 2)
                      for d in many])
    across = np.mean([np.sum((chunks[ptr[d]] - chunks[ptr[e]]) ** 2)
                      for d, e in zip(many, many[1:])])
    assert within < across / 3
    assert np.array_equal(full["chunks"], np.rint(full["chunks"]))
    assert full["chunks"].min() >= 0 and full["chunks"].max() <= 255


def test_bulk_bodies_carry_every_documents_chunks(data):
    full, _home = data
    conf = {**CONFIG, "bulk_docs_per_request": 700}
    seen = 0
    for lo, n, body in KIND.bulk_bodies(conf, full):
        lines = [json.loads(ln) for ln in body.split(b"\n") if ln]
        assert len(lines) == 2 * n and lo == seen
        for i in range(n):
            doc = lo + i
            assert lines[2 * i] == {"index": {"_id": str(doc)}}
            (objects,) = lines[2 * i + 1].values()
            assert np.array_equal(
                np.asarray([o["knn"] for o in objects], np.float32),
                full["chunks"][full["ptr"][doc]:full["ptr"][doc + 1]])
        seen += n
    assert seen == DOCS


def test_a_request_is_one_nested_knn(drawn):
    body = json.loads(KIND.request(CONFIG, drawn[3]))
    assert body["size"] == SIZE
    assert body["_source"] == {"excludes": ["passage_chunk_embedding"]}
    nested = body["query"]["nested"]
    assert nested["path"] == "passage_chunk_embedding"
    assert nested["score_mode"] == "max"
    knn = nested["query"]["knn"]["passage_chunk_embedding.knn"]
    assert knn["k"] == 10
    assert knn["vector"] == pytest.approx(drawn.vectors[3].tolist())


def test_a_query_is_a_chunk_of_its_target_plus_noise(data, drawn):
    full, _home = data
    ptr, chunks = full["ptr"], full["chunks"]
    for q in range(16):
        vector, _ = drawn[q]
        target = drawn.targets[q]
        mine = chunks[ptr[target]:ptr[target + 1]]
        nearest = np.min(np.sum((mine - vector) ** 2, axis=1))
        assert nearest / len(vector) == pytest.approx(9.0, rel=0.25)


# -- the reference against a slow loop ----------------------------------------


def slow_topk(full, vector, size):
    """Plain Python over every chunk: a document's least squared distance,
    then the documents by it, ties by id."""
    best = {}
    q = [float(x) for x in vector]
    for doc in range(len(full["ptr"]) - 1):
        for row in range(full["ptr"][doc], full["ptr"][doc + 1]):
            d2 = math.fsum((float(c) - x) ** 2
                           for c, x in zip(full["chunks"][row], q))
            best[doc] = min(best.get(doc, math.inf), d2)
    order = sorted(best, key=lambda doc: (best[doc], doc))[:size]
    return order, [1.0 / (1.0 + best[doc]) for doc in order]


@pytest.fixture(scope="module")
def answered(data, drawn):
    full, _home = data
    ref = KIND.reference(CONFIG, full)
    ids, scores = ref.topk(drawn, SIZE)
    return ref, ids, scores


@pytest.mark.parametrize("q", [0, 1, 7, 42])
def test_the_reference_equals_a_slow_loop(data, drawn, answered, q):
    full, _home = data
    ref, ids, scores = answered
    want_ids, want_scores = slow_topk(full, drawn.vectors[q], SIZE)
    assert ids[q].tolist() == want_ids
    assert scores[q] == pytest.approx(want_scores, rel=1e-12)
    assert ref.scores(drawn[q], ids[q]) == pytest.approx(want_scores,
                                                         rel=1e-12)
    assert ids[q][0] == drawn.targets[q]


def test_the_reference_waits_on_its_gate_before_each_piece(data, drawn):
    full, _home = data
    gate = threading.Event()
    out = {}
    t = threading.Thread(target=lambda: out.update(
        got=KIND.reference(CONFIG, full).topk(drawn, SIZE, gate=gate)))
    t.start()
    t.join(timeout=1.0)
    assert t.is_alive() and not out       # held while the gate is clear
    gate.set()
    t.join(timeout=60)
    assert "got" in out


class _Window:
    """A window of sound 200 replies, one a query in order."""

    def __init__(self, replies):
        self.status = [200] * len(replies)
        self.query = list(range(len(replies)))
        self.payload = [judge.reply_bytes(i, s, 1) for i, s in replies]

    def __len__(self):
        return len(self.status)


def judged(drawn, answered, replies):
    ref, ids, scores = answered
    got = judge.judge_window(_Window(replies), drawn, ref, ids, scores,
                             SIZE, 1)
    return judge.compare({**got["numbers"], "count_gap": 0},
                         CONFIG["limits"], must_have=got["further"])


def test_the_reference_in_the_programs_place_is_correct(drawn, answered):
    _ref, ids, scores = answered
    correct, checks = judged(drawn, answered,
                             list(zip(ids.tolist(), scores.tolist())))
    assert correct, checks
    assert checks["recall_at_10"]["value"] == 1.0


def test_the_control_is_one_precision_down_and_fails_the_limits(
        drawn, answered):
    ref, _ids, _scores = answered
    c_ids, c_scores = ref.control(drawn, SIZE)
    correct, checks = judged(drawn, answered,
                             list(zip(c_ids.tolist(), c_scores.tolist())))
    assert correct is False
    assert checks["score_gap"]["ok"] is False
    assert KIND.CONTROL


def test_a_document_scored_by_its_first_chunk_is_not_correct(
        data, drawn, answered):
    """The planted fault: each document's FIRST chunk stands for it, as a
    flattened field (one vector a document) would answer."""
    full, _home = data
    ptr, chunks = full["ptr"], full["chunks"].astype(np.float64)
    first = chunks[ptr[:-1]]
    replies = []
    for q in range(len(drawn)):
        d2 = np.sum((first - drawn.vectors[q]) ** 2, axis=1)
        order = np.lexsort((np.arange(len(d2)), d2))[:SIZE]
        replies.append((order.tolist(), (1.0 / (1.0 + d2[order])).tolist()))
    correct, checks = judged(drawn, answered, replies)
    assert correct is False
    assert checks["score_gap"]["ok"] is False
    assert checks["recall_at_10"]["ok"] is False


# -- the work function and the three readers -----------------------------------


def test_the_least_work_of_nested_launches_by_hand():
    ops, moved = nested_scan_work(4000, 768, 10, launches=3, requests=7)
    assert ops == 2 * 7 * 4000 * 768
    assert moved == 3 * 4000 * 768 * 4 + 7 * (768 * 4 + 10 * 8)
    # the same floor as an exact scan's over the rows: one read a launch
    assert (ops, moved) == work.exact_scan_work(4000, 768, 10, 3, 7)


def planes(scan_ms=2.7, reduce_ms=0.3, launches=200) -> dict:
    """One chip, `launches` launches one after the other (0.8 s: longer
    than the reader's edges): the nested scan kernel, then the cut after
    it."""
    events, t = [], 0.0
    for _ in range(launches):
        for name, ms in (("pallas_knn_fused_nested.1", scan_ms),
                         ("top_k.3 = f32[8,10]", reduce_ms)):
            if ms:
                events.append([name, t, ms * 1e6])
                t += ms * 1e6
        t += 1e6
    return {"devices": {"/device:TPU:0": events}}


MS = 1_000_000


def capture_with(knn_ms: list, rows: float = 503_000.0) -> dict:
    """One whole `_search` request a `knn.nested` span, well inside the
    capture's steady span; the last one is cut by the closing edge. The
    counters: a nested request each, `rows` rows each."""
    records, t = [], 1_000 * MS
    for i, took in enumerate(knn_ms):
        trace_id, root = f"trace-{i}", f"root-{i}"
        start = t
        records += [
            ["http_request", trace_id, root, None, 1, start,
             start + (took + 4) * MS, {"path": "/knn-nested-chunks/_search"}],
            ["knn.nested", trace_id, f"nested-{i}", f"phase-{i}", 2,
             start + MS, start + (took + 1) * MS,
             {"children": int(rows), "parents": 131072, "road": "mesh",
              "launches": 1}],
            ["http.respond", trace_id, f"resp-{i}", root, 1,
             start + (took + 4) * MS, start + (took + 5) * MS, None]]
        t = start + (took + 6) * MS
    fields = ["name", "trace_id", "span_id", "parent_id", "thread",
              "start_ns", "end_ns", "attributes"]
    n = len(knn_ms)
    return {"opened": {"perf_counter_ns": 500 * MS},
            "closed": {"perf_counter_ns": t + 200 * MS},
            "counters": {
                "open": {"knn_nested": {"requests": 40.0,
                                        "children": 40 * rows}},
                "close": {"knn_nested": {"requests": 40.0 + n,
                                         "children": (40 + n) * rows}}},
            "spans": [dict(zip(fields, r)) for r in records]}


def run_of(reduced: dict, capture: dict | None = None,
           launches: int = 12, served: int = 48, seconds: float = 3.0):
    counters = {"trace": ({"t": 1.0, "dispatches": 100,
                           "merged_queries": 400},
                          {"t": 1.0 + seconds, "dispatches": 100 + launches,
                           "merged_queries": 400 + served})}
    run = SimpleNamespace(
        config=CONFIG, docs=CONFIG["docs"], peaks=PEAKS,
        cell={"name": CELL, "chips": 1}, numbers={},
        trace=trace.reduce_trace(reduced), counters=counters)

    def counter_delta(span):
        before, after = counters[span]
        out = {k: after[k] - before[k]
               for k in ("dispatches", "merged_queries")}
        out["seconds"] = after["t"] - before["t"]
        return out

    run.counter_delta = counter_delta
    run._host_capture = capture
    return run


def test_nested_scan_roofline_by_hand_and_never_above_100():
    read = layer("nested_scan_roofline").read
    run = run_of(planes(), capture_with([3.0] * 12))
    _ops, moved = nested_scan_work(503_000, 768, 10, 12, 48)
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    assert read(run) == pytest.approx(
        100.0 * (moved / PEAKS["bytes_per_s"] / 3.0) / busy_rate, rel=1e-9)
    assert 0 < read(run) < 100
    # nothing but the least traffic at the memory's speed, back to back:
    # 100%, however many requests a launch serves
    ideal = run_of(planes(), capture_with([3.0] * 12), served=12 * 32)
    ideal.trace["busy_s"] = (12 * 503_000 * 768 * 4
                             + 12 * 32 * (768 * 4 + 80)) / 819e9
    ideal.trace["window_s"] = 3.0
    assert read(ideal) == pytest.approx(100.0, rel=1e-9)
    # nothing to read: the parent's capture (no such counters), no
    # capture, no trace, no peaks, another kind of work, no launch
    parent = {**capture_with([3.0]), "counters": {"open": {}, "close": {}}}
    assert read(run_of(planes(), parent)) is None
    assert read(run_of(planes(), {**capture_with([3.0]), "counters": {
        "open": None, "close": None}})) is None
    assert read(run_of(planes(), capture_with([3.0]), launches=0)) is None
    for broken in (dict(_host_capture=None), dict(peaks=None), dict(
            config={**CONFIG, "work": {"kind": "exact_scan"}})):
        run = run_of(planes(), capture_with([3.0]))
        vars(run).update(broken)
        assert read(run) is None
    run = run_of(planes(), capture_with([3.0]))
    run.trace = None
    assert read(run) is None


def test_reduce_device_share_is_what_is_not_the_scan(tmp_path, monkeypatch):
    read = layer("nested.reduce_device_share").read
    cell_dir = tmp_path / "trace" / CELL
    cell_dir.mkdir(parents=True)
    (cell_dir / "reduced.json").write_text(json.dumps(planes()))
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir", str(tmp_path)])
    assert read(run_of(planes())) == pytest.approx(
        100.0 * 0.3 / 3.0, rel=2e-2)
    # no scan kernel in the trace (another lowering), no trace: nothing
    (cell_dir / "reduced.json").write_text(json.dumps(planes(scan_ms=0)))
    assert read(run_of(planes(scan_ms=0))) is None
    run = run_of(planes())
    run.trace = None
    assert read(run) is None


def test_the_span_reader_takes_the_mean_over_whole_requests():
    read = layer("nested.knn_ms").read
    run = run_of(planes(), capture_with([3.0, 4.0, 5.0, 300.0]))
    assert read(run) == pytest.approx(4.0)          # the cut one left out
    # a program without the span (the parent commit): nothing, no error
    spans = [s for s in run._host_capture["spans"]
             if s["name"] != "knn.nested"]
    run._host_capture = {**run._host_capture, "spans": spans}
    assert read(run) is None
    run = run_of(planes(), capture_with([3.0]))
    run.trace = None                                # an untraced run
    assert read(run) is None


# -- the cell through `perf/run.py`: one dry run --------------------------------


def test_the_cell_runs_correct_on_the_cpu_through_the_normal_path(
        tmp_path_factory):
    tmp = tmp_path_factory.mktemp("perf_nested")
    proc, last = dry_run(tmp, CELL, "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is True and last["dry_run"] is True
    assert last["attempted"] > 20 and last["first_fill"] is True
    assert last["docs"] == DRY_DOCS
    assert last["metrics"]["recall_at_10"]["value"] >= 0.99
    assert set(last["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert list(last["checks"]) == list(CONFIG["limits"])
    assert last["checks"]["count_gap"]["value"] == 0
    assert last["compiled_in_window"] == 0
    assert last["launches"] > 0
