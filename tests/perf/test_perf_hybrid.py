"""The files PR 37 adds to the benchmark for `hybrid-bm25-knn` (BEIR NQ's
shapes served as OpenSearch's hybrid search): the kind
(`perf/kinds/hybrid-bm25-knn.py`: passages from a word law, questions made
from a target passage, a reference that fuses a BM25 pool and an exact l2
pool), the work function of a hybrid request (`perf/hybrid_work.py`), the
four readers under `perf/layers/`, and the configuration's file. Arithmetic
on small seeded data against a fusion computed by hand in plain Python, on
a hand-written capture and on hand-written device planes; then the cell
itself through `perf/run.py --cpu-dry-run`, twice and one after the other
(PERF.md sec. 7 row 19d): sound, and with the fusion dropped where the
answer is produced."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _perf_dry import DOCS, HERE, REPO, SEED, dry_run  # noqa: E402

sys.path.insert(0, str(REPO))

from perf import trace, work  # noqa: E402
from perf.hybrid_work import BM25_OPS_PER_POSTING, hybrid_scan_work  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads((REPO / "perf/configs/hybrid-bm25-knn.json").read_text())
CELL = "hybrid-bm25-knn.c32"
ADDED = ["bm25.score_ms", "hybrid.fuse_ms", "hybrid.lexical_device_share",
         "hybrid_scan_roofline"]
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
SIZE = CONFIG["request"]["size"]
ROWS = 1024             # the data set the fusion is computed by hand over


def module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, REPO / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer(name: str):
    return module(f"perf/layers/{name}.py", "perf_layer_under_test")


KIND = module("perf/kinds/hybrid-bm25-knn.py", "perf_kind_hybrid_bm25_knn")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """The data set at ROWS rows, as a first run makes and keeps it."""
    home = tmp_path_factory.mktemp("hybrid_rows")
    return KIND.dataset(CONFIG, ROWS, home, True), home


@pytest.fixture(scope="module")
def drawn(data):
    return KIND.queries(CONFIG, data[0], 2**31 + 37, 0, 256)


# -- the configuration's file and its manifest entries -------------------------


def test_the_configuration_keeps_every_width_of_the_source():
    shapes = CONFIG["source_shapes"]
    assert CONFIG["kind"] == "hybrid-bm25-knn"
    assert CONFIG["dims"] == 768 == shapes["dims"]
    assert CONFIG["request"]["size"] == 10 == shapes["size"]
    assert CONFIG["request"]["knn"] == {"k": shapes["k"]} == {"k": 10}
    assert CONFIG["request"]["match"] == {"operator": "or"}
    (processor,) = CONFIG["request"]["search_pipeline"][
        "phase_results_processors"]
    assert processor == {"normalization-processor": {
        "normalization": {"technique": "min_max"},
        "combination": {"technique": "arithmetic_mean",
                        "parameters": {"weights": [0.3, 0.7]}}}}
    body = CONFIG["index_body"]
    assert body["settings"] == {"number_of_shards": 1,
                                "number_of_replicas": 0}
    assert body["mappings"]["properties"] == {
        "text": {"type": "text"},
        "v": {"type": "knn_vector", "dimension": 768, "space_type": "l2"}}
    assert (KIND.K1, KIND.B, KIND.FLOOR) == (1.2, 0.75, 0.001)
    assert KIND.LEN_MEAN == shapes["passage_words_mean"] == 78.9
    assert (KIND.QUESTION_MIN + KIND.QUESTION_MEAN_MORE
            == pytest.approx(shapes["question_words_mean"]))
    # the one cut, with its reason in the file
    assert CONFIG["reduced"] == ["docs"] == list(CONFIG["cuts"])
    assert CONFIG["docs"] == 262_144 < shapes["docs"] == 2_681_468
    # every number the kind compares has its limit, and its reason
    assert {"fused_abs_gap", "pool_violations"} < set(CONFIG["limits"])
    assert CONFIG["limits"]["pool_violations"] == {"max": 0}
    assert len(CONFIG["assumed"]) >= 6 and CONFIG["limits_why"]


def test_the_manifest_gains_one_configuration_one_cell_four_metrics():
    (conf,) = [c for c in MANIFEST["configs"]
               if c["name"] == "hybrid-bm25-knn"]
    assert conf["reduced"] == ["docs"] and len(conf["source"]) <= 200
    assert "BEIR NQ" in conf["source"]
    assert "normalization-processor" in conf["source"]
    assert conf["source"] == CONFIG["source"]
    (cell,) = [w for w in MANIFEST["workloads"]
               if w["config"] == "hybrid-bm25-knn"]
    assert cell == {"name": CELL, "config": "hybrid-bm25-knn",
                    "traffic": "c32", "chips": 1, "why": cell["why"]}
    added = [m for m in MANIFEST["per_layer"] if m["name"] in ADDED]
    assert [m["name"] for m in added] == ADDED
    assert all(m["workloads"] == [CELL] and m["moves"] == "qps"
               for m in added)
    assert [m["source"] for m in added] == [
        "program_span", "program_span", "device_trace", "device_trace"]
    assert [m["layer"] for m in added] == [
        "mesh program / per-shard ANN", "search service",
        "mesh program / per-shard ANN", "kernels"]
    # nothing else names the cell; the metrics without a list follow it
    assert sum(CELL in m.get("workloads", ()) for m in
               MANIFEST["end_to_end"] + MANIFEST["per_layer"]) == 4
    reported = [m["name"] for m in MANIFEST["per_layer"]
                if CELL in m.get("workloads", (CELL,))]
    assert reported == ["device.idle_share", "device.resident_bytes", *ADDED]
    assert [m["name"] for m in MANIFEST["end_to_end"]
            if CELL in m.get("workloads", (CELL,))] == [
                "qps", "recall_at_10", "setup_s"]


def test_nothing_under_perf_imports_the_program_or_the_tests():
    for path in ("perf/kinds/hybrid-bm25-knn.py", "perf/hybrid_work.py",
                 *(f"perf/layers/{name}.py" for name in ADDED)):
        text = (REPO / path).read_text()
        assert "opensearch_tpu" not in text
        assert "import tests" not in text and "from tests" not in text


# -- the kind: data set, questions, request, bodies ----------------------------


def test_row_i_is_the_same_at_any_docs(data, tmp_path):
    small, _home = data
    big = KIND.dataset(CONFIG, 3000, tmp_path, True)
    assert np.array_equal(small["corpus"], big["corpus"][:ROWS])
    assert np.array_equal(small["ptr"], big["ptr"][:ROWS + 1])
    assert np.array_equal(small["tokens"], big["tokens"][:small["ptr"][-1]])
    # integers 0-255 in 768 dimensions, float32
    assert big["corpus"].shape == (3000, 768)
    assert big["corpus"].dtype == np.float32
    assert np.array_equal(big["corpus"], np.rint(big["corpus"]))
    assert 0 <= big["corpus"].min() and big["corpus"].max() <= 255


def test_a_later_run_loads_what_the_first_kept(data):
    made, home = data
    assert np.load(home / "corpus.npy").dtype == np.uint8
    again = KIND.dataset(CONFIG, ROWS, home, False)
    for name in ("corpus", "ptr", "tokens"):
        assert np.array_equal(again[name], made[name])
    assert np.array_equal(again["postings"].rows, made["postings"].rows)


def test_passages_follow_the_stated_laws(tmp_path):
    ptr, tokens = KIND._passages(CONFIG["corpus_seed"], 20_000)
    lengths = np.diff(ptr)
    assert lengths.min() >= KIND.LEN_MIN
    assert lengths.mean() == pytest.approx(78.9, rel=0.02)
    assert 0 <= tokens.min() and tokens.max() < KIND.VOCAB
    # the commonest word sits in most passages, as "the" does; every word
    # of the law's first TOP in more than half (a question's longest list)
    rows = np.repeat(np.arange(20_000), lengths)
    for rank in range(KIND.TOP):
        share = len(np.unique(rows[tokens == rank])) / 20_000
        assert share > (0.85 if rank == 0 else 0.5)
    # ~68 distinct words a passage, the postings' size
    distinct = len(np.unique(tokens.astype(np.int64) * 20_000 + rows))
    assert 60 < distinct / 20_000 < 75
    assert KIND.law().sum() == pytest.approx(1.0)
    assert np.all(np.diff(KIND.law()) < 0)


@pytest.mark.parametrize("seed", [1, 2**31 + 37, 2_137_000_111])
def test_every_seed_draws_the_same_law_of_questions(data, seed):
    made = data[0]
    questions = KIND.queries(CONFIG, made, seed, 0, 2048)
    lengths = np.asarray([len(w) for w in questions.words])
    assert lengths.min() >= 3 and lengths.max() <= 20
    assert lengths.mean() == pytest.approx(9.2, abs=0.25)
    assert questions.vectors.shape == (2048, 768)
    assert questions.vectors.dtype == np.float32
    # not rounded: a matmul that drops to bfloat16 shows
    assert not np.array_equal(questions.vectors, np.rint(questions.vectors))
    for i in range(0, 2048, 97):
        vector, words, number = questions[i]
        assert number == i
        target = questions.targets[i]
        passage = set(made["tokens"][made["ptr"][target]:
                                     made["ptr"][target + 1]].tolist())
        content = [w for w in words.tolist() if w >= KIND.HEAD]
        assert content and set(content) <= passage
        assert len(set(content)) == len(content)
        assert any(w < KIND.TOP for w in words.tolist())
        # near the target's vector: the mixture's own noise a coordinate
        d2 = float(((vector - made["corpus"][target]) ** 2).sum())
        assert d2 < 768 * 4 * KIND.QUERY_SIGMA ** 2
    # another stream, another seed: other questions
    assert not np.array_equal(
        questions.targets, KIND.queries(CONFIG, made, seed, 1, 2048).targets)
    assert not np.array_equal(
        questions.targets,
        KIND.queries(CONFIG, made, seed + 1, 0, 2048).targets)


def test_the_warm_up_serves_every_term_count_first(data):
    warm = KIND.queries(CONFIG, data[0], 2**31 + 37, 1, 64)
    first = [len(w) for w in warm.words[:18]]
    assert first == list(KIND.WARM_FIRST) and sorted(first) == list(
        range(3, 21))
    # the harness sends them as 1, then bursts of 2, 3, 4, 8 at once: no
    # burst meets more than two shapes nobody has compiled, by term count
    # or by its multiple of four, and the burst of 8 meets none of the
    # latter
    seen: set = set()
    for lo, hi in ((0, 1), (1, 3), (3, 6), (6, 10), (10, 18)):
        padded = {-(-n // 4) for n in first[lo:hi]}
        assert len(padded - seen) <= 2
        seen |= padded
        if hi == 10:
            assert seen == {1, 2, 3, 4, 5}
    timed = KIND.queries(CONFIG, data[0], 2**31 + 37, 0, 64)
    assert [len(w) for w in timed.words[:18]] != first


def test_bulk_bodies_carry_every_rows_vector_and_passage(data):
    made = data[0]
    conf = {**CONFIG, "bulk_docs_per_request": 400}
    seen = 0
    for lo, n, body in KIND.bulk_bodies(conf, made):
        lines = body.split(b"\n")
        assert lo == seen and len([ln for ln in lines if ln]) == 2 * n
        for i in (0, n - 1):
            assert json.loads(lines[2 * i]) == {"index": {"_id": str(lo + i)}}
            doc = json.loads(lines[2 * i + 1])
            assert set(doc) == {"v", "text"}
            assert doc["v"] == made["corpus"][lo + i].tolist()
            passage = made["tokens"][made["ptr"][lo + i]:
                                     made["ptr"][lo + i + 1]]
            assert doc["text"] == " ".join(f"w{w}" for w in passage)
        seen += n
    assert seen == ROWS


def test_a_request_is_one_hybrid_query_with_its_pipeline_inline(drawn):
    vector, words, _number = drawn[5]
    body = json.loads(KIND.request(CONFIG, drawn[5]))
    assert body == {
        "size": 10, "_source": {"excludes": ["v"]},
        "query": {"hybrid": {"queries": [
            {"match": {"text": {"query": " ".join(f"w{w}" for w in words),
                                "operator": "or"}}},
            {"knn": {"v": {"vector": [float(x) for x in vector],
                           "k": 10}}}]}},
        "search_pipeline": CONFIG["request"]["search_pipeline"]}


# -- the reference against a fusion computed by hand ---------------------------


@pytest.fixture(scope="module")
def by_hand(data):
    """BM25 (Lucene's idf, tf norm with exact lengths), l2 and the
    normalization-processor's arithmetic in plain Python over the rows."""
    made = data[0]
    passages = [made["tokens"][made["ptr"][i]:made["ptr"][i + 1]].tolist()
                for i in range(ROWS)]
    counts = [Counter(p) for p in passages]
    avgdl = sum(len(p) for p in passages) / ROWS

    def pool(scores: list) -> list:
        ranked = sorted((r for r in range(ROWS) if scores[r] > 0),
                        key=lambda r: (-scores[r], r))[:SIZE]
        return [(r, scores[r]) for r in ranked]

    def fused(question) -> list:
        vector, words, _number = question
        lexical = [0.0] * ROWS
        for w in words.tolist():
            df = sum(1 for c in counts if w in c)
            idf = math.log(1.0 + (ROWS - df + 0.5) / (df + 0.5))
            for r, c in enumerate(counts):
                tf = c.get(w, 0)
                if tf:
                    lexical[r] += idf * tf / (tf + 1.2 * (
                        1.0 - 0.75 + 0.75 * len(passages[r]) / avgdl))
        diff = made["corpus"].astype(np.float64) - vector.astype(np.float64)
        knn = (1.0 / (1.0 + (diff * diff).sum(axis=1))).tolist()
        total: dict = {}
        for weight, hits in ((0.3, pool(lexical)), (0.7, pool(knn))):
            hi, lo = hits[0][1], hits[-1][1]
            for r, s in hits:
                normed = 1.0 if hi <= lo else max((s - lo) / (hi - lo), 0.001)
                total[r] = total.get(r, 0.0) + weight * normed
        ranked = sorted(total, key=lambda r: (-total[r], r))[:SIZE]
        return [(r, total[r] / 1.0) for r in ranked]

    return fused


@pytest.mark.parametrize("i", range(12))
def test_the_reference_equals_a_fusion_computed_by_hand(
        data, drawn, by_hand, i):
    ref = KIND.reference(CONFIG, data[0])
    ids, scores = ref.topk(drawn, SIZE)
    want = by_hand(drawn[i])
    assert ids[i].tolist() == [r for r, _s in want]
    assert scores[i].tolist() == pytest.approx([s for _r, s in want],
                                               rel=1e-9)
    # `scores` gives served ids what `topk` gave them, and a document of
    # neither pool nothing
    assert ref.scores(drawn[i], ids[i]).tolist() == pytest.approx(
        scores[i].tolist(), rel=1e-12)
    lexical, knn = ref._parts(drawn[i], np.arange(ROWS))
    outside = np.flatnonzero((lexical == 0) & (knn == 0))
    assert len(outside) >= ROWS - 2 * SIZE
    assert ref.scores(drawn[i], outside[:3]).tolist() == [0.0, 0.0, 0.0]


def test_min_max_keeps_upstreams_rules():
    assert KIND.min_max(5.0, 1.0, [5.0, 3.0, 1.0]).tolist() == [
        1.0, 0.5, 0.001]
    assert KIND.min_max(2.0, 2.0, [2.0]).tolist() == [1.0]   # one-point range
    assert KIND.min_max(5.0, 1.0, [0.5]).tolist() == [0.001]


def test_a_repeated_word_is_a_clause_of_its_own(data):
    ref = KIND.reference(CONFIG, data[0])
    once = ref.bm25(np.asarray([3, 500]))
    twice = ref.bm25(np.asarray([3, 500, 3]))
    only = ref.bm25(np.asarray([3]))
    assert np.allclose(twice, once + only, rtol=1e-12)
    assert ref.bm25_of([3, 500, 3], np.arange(ROWS)) == pytest.approx(twice)


def test_the_reference_waits_on_its_gate_before_each_piece(data, drawn):
    class Gate:
        waits = 0

        def wait(self):
            self.waits += 1

    gate = Gate()
    KIND.reference(CONFIG, data[0]).topk(drawn, SIZE, gate=gate)
    assert gate.waits >= len(drawn)


def test_further_compares_absolute_gaps_and_counts_outsiders(data, drawn):
    ref = KIND.reference(CONFIG, data[0])
    ids, scores = ref.topk(drawn, SIZE)
    served = [(q, ids[q], scores[q]) for q in range(64)]
    sound = ref.further(drawn, served)
    assert sound["pool_violations"] == 0
    assert sound["fused_abs_gap"] < 1e-12
    assert set(sound) <= set(CONFIG["limits"])
    # a served document of neither pool counts once; a score off by 0.01
    # shows as 0.01 however small the score it belongs to
    lexical, knn = ref._parts(drawn[0], np.arange(ROWS))
    planted_ids, planted_scores = ids[0].copy(), scores[0].copy()
    planted_ids[3] = np.flatnonzero((lexical == 0) & (knn == 0))[0]
    planted_scores[9] += 0.01
    bad = ref.further(drawn, [(0, planted_ids, planted_scores), *served[1:]])
    assert bad["pool_violations"] == 1
    assert bad["fused_abs_gap"] == pytest.approx(scores[0][3], abs=0.011)
    assert bad["fused_abs_gap"] >= 0.01
    # a document a hair under a pool's lowest score is no outsider: the l2
    # pool's last document, with the pool's edge moved just above it
    lex_hi, lex_lo, knn_hi, knn_lo = ref.stats[0]
    pool = np.flatnonzero(knn > 0)
    edge = pool[[ref.exact.d2(drawn[0][0], pool).argmax()]]
    ref.stats[0] = (lex_hi, lex_lo, knn_hi,
                    knn_lo * (1 + KIND.TIE["knn"] / 2))
    try:
        assert ref._parts(drawn[0], edge)[1].tolist() == [0.0]
        assert ref._parts(drawn[0], edge, KIND.TIE)[1].tolist() == [0.001]
    finally:
        ref.stats[0] = (lex_hi, lex_lo, knn_hi, knn_lo)
    assert ref.further(drawn, []) == {"fused_abs_gap": 0.0,
                                      "pool_violations": 0}


def test_the_control_is_one_precision_down_and_its_numbers_show_it(
        data, drawn):
    ref = KIND.reference(CONFIG, data[0])
    ref.topk(drawn, SIZE)
    ids, scores = ref.control(drawn, SIZE)
    assert "bfloat16" in KIND.CONTROL
    served = [(q, ids[q], scores[q]) for q in range(len(drawn))]
    assert ref.further(drawn, served)["fused_abs_gap"] > 2 * CONFIG[
        "limits"]["fused_abs_gap"]["max"]
    # both halves are down: the BM25 pool's scores and the l2 pool's
    words = drawn.words[0]
    assert not np.array_equal(ref.bm25(words), ref.bm25(words, bf16=True))
    assert np.allclose(ref.bm25(words), ref.bm25(words, bf16=True), rtol=0.05)


# -- the work function and the four readers ------------------------------------


def test_the_least_work_of_a_hybrid_request_by_hand():
    ops, moved = hybrid_scan_work(1000, 768, 10, 7, 5000, 4, 8)
    assert ops == 2 * 7 * 1000 * 768 + BM25_OPS_PER_POSTING * 5000
    assert moved == 7 * (1000 * 768 * 4 + 768 * 4 + 2 * 10 * 8) + 5000 * 8
    # the vectors' part is an exact scan's, one launch a request
    scan_ops, scan_moved = work.exact_scan_work(1000, 768, 10, 7, 7)
    assert ops - scan_ops == BM25_OPS_PER_POSTING * 5000
    assert moved - scan_moved == 7 * 10 * 8 + 5000 * 8


def planes(scan_ms=1.4, scatter_ms=40.0, gather_ms=50.0,
           requests=12) -> dict:
    """One chip, `requests` hybrid requests one after the other: BM25's
    gathers and scatter-adds, then the vector scan."""
    events, t = [], 0.0
    for _ in range(requests):
        for name, ms in (("gather.3 = f32[9,262144]", gather_ms),
                         ("scatter-add.1 = f32[262144]", scatter_ms),
                         ("pallas_knn_fused.1", scan_ms)):
            if ms:
                events.append([name, t, ms * 1e6])
                t += ms * 1e6
        t += 1e6        # a millisecond of idle between requests
    return {"devices": {"/device:TPU:0": events}}


MS = 1_000_000


def capture_with(score_ms: list, fuse_ms: float = 0.2,
                 postings: float = 1_400_000.0) -> dict:
    """One whole `_search` request a `bm25.score` and a `hybrid.fuse`, well
    inside the capture's steady span; the last one is cut by the closing
    edge. The counters: a hybrid request each, `postings` entries each."""
    records, t = [], 1_000 * MS
    for i, took in enumerate(score_ms):
        trace_id, root = f"trace-{i}", f"root-{i}"
        start = t
        fuse_at = start + (took + 2) * MS
        records += [
            ["http_request", trace_id, root, None, 1, start,
             start + (took + 4) * MS, {"path": "/hybrid-bm25-knn/_search"}],
            ["bm25.score", trace_id, f"bm25-{i}", f"phase-{i}", 2,
             start + MS, start + (took + 1) * MS,
             {"terms": 9, "postings": int(postings), "window": 262144,
              "rows": 262144}],
            ["hybrid.fuse", trace_id, f"fuse-{i}", f"phase-{i}", 2,
             fuse_at, fuse_at + fuse_ms * MS,
             {"sub_queries": 2, "pooled": 20, "shards": 1}],
            ["http.respond", trace_id, f"resp-{i}", root, 1,
             start + (took + 4) * MS, start + (took + 5) * MS, None]]
        t = start + (took + 6) * MS
    fields = ["name", "trace_id", "span_id", "parent_id", "thread",
              "start_ns", "end_ns", "attributes"]
    n = len(score_ms)
    return {"opened": {"perf_counter_ns": 500 * MS},
            "closed": {"perf_counter_ns": t + 200 * MS},
            "counters": {
                "open": {"lexical": {"hybrid_requests": 40.0,
                                     "bm25_launches": 40.0,
                                     "bm25_postings": 40 * postings}},
                "close": {"lexical": {"hybrid_requests": 40.0 + n,
                                      "bm25_launches": 40.0 + n,
                                      "bm25_postings": (40 + n) * postings}}},
            "spans": [dict(zip(fields, r)) for r in records]}


def run_of(reduced: dict, capture: dict | None = None):
    run = SimpleNamespace(
        config=CONFIG, docs=CONFIG["docs"], peaks=PEAKS,
        cell={"name": CELL, "chips": 1}, numbers={},
        trace=trace.reduce_trace(reduced),
        counters={"trace": ({"t": 1.0}, {"t": 3.7})},
        counter_delta=lambda span: None)
    run._host_capture = capture
    return run


def test_lexical_device_share_is_what_is_not_the_scan(tmp_path, monkeypatch):
    read = layer("hybrid.lexical_device_share").read
    cell_dir = tmp_path / "trace" / CELL
    cell_dir.mkdir(parents=True)
    (cell_dir / "reduced.json").write_text(json.dumps(planes()))
    monkeypatch.setattr(sys, "argv", ["run.py", "--cache-dir", str(tmp_path)])
    assert read(run_of(planes())) == pytest.approx(
        100.0 * (1 - 1.4 / 91.4), rel=2e-2)
    # no scan kernel in the trace (another lowering), no trace: nothing
    (cell_dir / "reduced.json").write_text(json.dumps(planes(scan_ms=0)))
    assert read(run_of(planes(scan_ms=0))) is None
    run = run_of(planes())
    run.trace = None
    assert read(run) is None


def test_hybrid_scan_roofline_by_hand_and_never_above_100():
    read = layer("hybrid_scan_roofline").read
    capture = capture_with([95.0] * 12)
    run = run_of(planes(), capture)
    seconds = (capture["closed"]["perf_counter_ns"]
               - capture["opened"]["perf_counter_ns"]) / 1e9
    _ops, moved = hybrid_scan_work(262_144, 768, 10, 12, 12 * 1.4e6, 4, 8)
    busy_rate = run.trace["busy_s"] / run.trace["window_s"]
    assert read(run) == pytest.approx(
        100.0 * (moved / PEAKS["bytes_per_s"] / seconds) / busy_rate,
        rel=1e-9)
    assert 0 < read(run) < 5.0
    # nothing but the least traffic at the memory's speed, back to back
    per_request_s = (262_144 * 768 * 4 + 768 * 4 + 160 + 1.4e6 * 8) / 819e9
    ideal = run_of(planes(), capture)
    ideal.trace["busy_s"] = 12 * per_request_s
    ideal.trace["window_s"] = seconds
    assert read(ideal) == pytest.approx(100.0, rel=1e-9)
    # nothing to read: the parent's capture (no such counters), no
    # capture, no trace, no peaks, another kind of work
    parent = {**capture, "counters": {"open": {}, "close": {}}}
    assert read(run_of(planes(), parent)) is None
    assert read(run_of(planes(), {**capture, "counters": {
        "open": None, "close": None}})) is None
    for broken in (dict(_host_capture=None), dict(peaks=None),
                   dict(config={**CONFIG, "work": {"kind": "exact_scan"}})):
        run = run_of(planes(), capture)
        vars(run).update(broken)
        assert read(run) is None
    run = run_of(planes(), capture)
    run.trace = None
    assert read(run) is None


@pytest.mark.parametrize("name, want", [("bm25.score_ms", 110.0),
                                        ("hybrid.fuse_ms", 0.2)])
def test_the_span_readers_take_the_mean_over_whole_requests(name, want):
    read = layer(name).read
    run = run_of(planes(), capture_with([100.0, 110.0, 120.0, 300.0]))
    assert read(run) == pytest.approx(want)         # the cut one left out
    # a program without the span (the parent commit): nothing, no error
    spans = [s for s in run._host_capture["spans"]
             if s["name"] not in ("bm25.score", "hybrid.fuse")]
    run._host_capture = {**run._host_capture, "spans": spans}
    assert read(run) is None
    run = run_of(planes(), capture_with([100.0]))
    run.trace = None                                # an untraced run
    assert read(run) is None


# -- the cell through `perf/run.py`: two dry runs, one after the other ---------


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return tmp_path_factory.mktemp("perf_hybrid")


def test_the_cell_runs_correct_on_the_cpu_through_the_normal_path(tmp):
    proc, last = dry_run(tmp, CELL, "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is True and last["dry_run"] is True
    assert last["attempted"] > 20 and last["first_fill"] is True
    assert last["metrics"]["recall_at_10"]["value"] >= 0.99
    assert set(last["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert list(last["checks"]) == list(CONFIG["limits"])
    assert last["checks"]["pool_violations"]["value"] == 0
    assert last["checks"]["fused_abs_gap"]["value"] < CONFIG["limits"][
        "fused_abs_gap"]["max"]
    # the knn sub-query is a dispatch of one query in the batcher's books
    assert last["launches"] >= last["attempted"] - 32
    assert last["compiled_in_window"] == 0


def test_the_fusion_dropped_where_the_answer_is_made_is_not_correct(tmp):
    """Every seventh search served as its `knn` sub-query alone
    (`_hybrid_faulty_launcher.py`): rightly ranked neighbours under raw l2
    scores, where the reference has the fused ones."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           "JAX_COMPILATION_CACHE_DIR": str(tmp / "jax_cache")}
    proc = subprocess.run(
        [sys.executable, str(HERE / "_perf_hybrid_child.py"), "--workload",
         CELL, "--seed", str(SEED), "--seconds", "2", "--cache-dir",
         str(tmp / "cache"), "--cpu-dry-run", "--docs", str(DOCS),
         "--trace", "0"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["first_fill"] is False
    assert last["checks"]["fused_abs_gap"]["ok"] is False
    assert last["checks"]["fused_abs_gap"]["value"] > 0.5
    assert last["checks"]["failed"]["ok"] and last["checks"]["malformed"]["ok"]
    # the neighbours served alone are the l2 pool's own: no outsider
    assert last["checks"]["pool_violations"]["ok"]
