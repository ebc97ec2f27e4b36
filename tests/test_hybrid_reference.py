"""A hybrid search (a `match` and a `knn` sub-query fused by the
normalization-processor) through the served path against the plain
reference of the benchmark's kind `hybrid-bm25-knn` (PR 37), at the
configuration's widths (768-d, its word laws, k = 10, min_max,
arithmetic_mean [0.3, 0.7]) and a tests' size, two segments. Documents and
requests are the kind's own `bulk_bodies` / `request`, handed to the REST
handlers in process.

Also here: the temporary search pipeline (`"search_pipeline": {...}` in the
request body: resolved where an id is, validated, stored nowhere), and what
makes the path visible: the detail spans `bm25.score` and `hybrid.fuse`,
`search.query_phase`'s `sub_queries`, and the counters
`search.hybrid.requests`, `search.bm25.launches`, `search.bm25.postings`
(registered with the node, so a 0 shows).

Every profiler session here starts and stops inside a test of this file's
own process; nothing touches the profiler at import time."""

from __future__ import annotations

import glob
import importlib.util
import json
import time
from pathlib import Path

import numpy as np
import pytest

from opensearch_tpu.common.errors import IllegalArgumentException
from opensearch_tpu.node import TpuNode
from opensearch_tpu.rest import handlers
from opensearch_tpu.telemetry import spans as span_names

REPO = Path(__file__).resolve().parents[1]
DOCS = 1200
SEGMENT_DOCS = 700      # two `_bulk` requests, a refresh after each
SEED = 2**31 + 37       # more than 32 signed bits hold
QUESTIONS = 16
COUNTERS = ("search.hybrid.requests", "search.bm25.launches",
            "search.bm25.postings")


def _kind():
    spec = importlib.util.spec_from_file_location(
        "perf_kind_hybrid_bm25_knn", REPO / "perf/kinds/hybrid-bm25-knn.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KIND = _kind()
CONF = {**json.loads((REPO / "perf/configs/hybrid-bm25-knn.json").read_text()),
        "docs": DOCS, "bulk_docs_per_request": SEGMENT_DOCS}
INDEX = CONF["index"]
SIZE = CONF["request"]["size"]


def _counters(node) -> tuple:
    shown = node.telemetry.metrics.stats()["counters"]
    return tuple(shown[name] for name in COUNTERS)


def _search(node, body: dict, **query) -> dict:
    status, resp = handlers.search(node, {"index": INDEX}, query, body)
    assert status == 200 and resp["_shards"]["failed"] == 0
    return resp


def _hits(resp: dict):
    hits = resp["hits"]["hits"]
    return [int(h["_id"]) for h in hits], [h["_score"] for h in hits]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """(node, data, questions, reference, its answers): the kind's data set
    at DOCS rows ingested through `_bulk` as the harness's fill does."""
    home = tmp_path_factory.mktemp("hybrid")
    data = KIND.dataset(CONF, DOCS, home, True)
    node = TpuNode(home / "node")
    # registered with the node: a 0 is shown as a 0, before any request
    assert _counters(node) == (0, 0, 0)
    node.create_index(INDEX, CONF["index_body"])
    acked = 0
    for lo, n, body in KIND.bulk_bodies(CONF, data):
        lines = [json.loads(ln) for ln in body.split(b"\n") if ln]
        status, resp = handlers.bulk(node, {"index": INDEX}, {}, lines)
        assert status == 200 and resp["errors"] is False
        assert [int(i["index"]["_id"]) for i in resp["items"]] == list(
            range(lo, lo + n))
        node.refresh(INDEX)
        acked += n
    assert acked == DOCS == node.count(INDEX)["count"]
    questions = KIND.queries(CONF, data, SEED, 0, QUESTIONS)
    ref = KIND.reference(CONF, data)
    ids, scores = ref.topk(questions, SIZE)
    yield node, data, questions, ref, ids, scores
    node.close()


def _body(questions, i: int) -> dict:
    return json.loads(KIND.request(CONF, questions[i]))


@pytest.mark.parametrize("i", range(QUESTIONS))
def test_the_served_fused_top_10_is_the_references(world, i):
    node, _data, questions, ref, ids, scores = world
    body = _body(questions, i)
    assert set(body) == {"size", "_source", "query", "search_pipeline"}
    resp = _search(node, body)
    got_ids, got_scores = _hits(resp)
    assert got_ids == ids[i].tolist()
    # min-max divides by a pool's range: a sub-score's float32 rounding
    # arrives multiplied by score / range, so absolute, not relative
    assert got_scores == pytest.approx(scores[i].tolist(), abs=2e-5)
    assert got_scores == pytest.approx(
        ref.scores(questions[i], np.asarray(got_ids)).tolist(), abs=2e-5)
    # a hit carries its passage and not its vector
    first = resp["hits"]["hits"][0]["_source"]
    assert set(first) == {CONF["text_field"]}


def test_term_rows_are_launched_in_multiples_of_four(world, monkeypatch):
    """Questions of 3 to 20 words meet five launch shapes, not eighteen:
    `_bm25` fills the term rows up with rows of length 0, which score
    nothing (the sixteen answers above are served through it)."""
    from opensearch_tpu.search import executor

    node, _data, questions, _ref, _ids, _scores = world
    rows = []
    launch = executor.bm25.bm25_term_scores

    def recorded(docs, tfs, doc_len, offsets, lengths, idfs, *args, **kw):
        rows.append((len(offsets), lengths.tolist(), idfs.tolist()))
        return launch(docs, tfs, doc_len, offsets, lengths, idfs, *args, **kw)

    monkeypatch.setattr(executor.bm25, "bm25_term_scores", recorded)
    segments = -(-DOCS // SEGMENT_DOCS)
    for i in range(QUESTIONS):
        _search(node, _body(questions, i))
    assert len(rows) == QUESTIONS * segments
    assert {n for n, _lengths, _idfs in rows} <= {4, 8, 12, 16, 20}
    assert len({n for n, _lengths, _idfs in rows}) >= 2
    for at, (n, lengths, idfs) in enumerate(rows):
        words = len(questions.words[at // segments])
        assert n == -(-words // executor.BM25_TERM_ROWS) * 4
        assert lengths[words:] == idfs[words:] == [0] * (n - words)


def test_the_sixteen_questions_compile_five_programs_a_segment_at_most(world):
    """A `_bm25` call is ONE compiled program, keyed by its term rows, its
    window and the segment's shapes: the sixteen questions compile at most
    five a segment, and served again they compile nothing."""
    from opensearch_tpu.search import executor

    node, _data, questions, _ref, _ids, _scores = world
    program = executor.bm25.bm25_term_scores.__wrapped__
    program.clear_cache()
    for i in range(QUESTIONS):
        _search(node, _body(questions, i))
    compiled = program._cache_size()
    segments = -(-DOCS // SEGMENT_DOCS)
    assert 2 <= compiled <= 5 * segments
    for i in range(QUESTIONS):
        _search(node, _body(questions, i))
    assert program._cache_size() == compiled


def test_the_target_passage_leads_and_both_sub_queries_weigh_in(world):
    """A question is made from a passage's words and vector: that passage
    is the best of both pools (fused 1.0), and the rest of the top 10 comes
    from both sub-queries."""
    node, _data, questions, ref, ids, scores = world
    assert (ids[:, 0] == questions.targets).all()
    assert scores[:, 0] == pytest.approx(1.0)
    lexical, knn = zip(*(ref._parts(questions[i], ids[i])
                         for i in range(QUESTIONS)))
    assert (np.asarray(lexical)[:, 1:] > 0).any()
    assert (np.asarray(knn)[:, 1:] > 0).any()


def test_a_stored_pipeline_and_the_same_pipeline_inline_answer_alike(world):
    node, _data, questions, _ref, _ids, _scores = world
    body = _body(questions, 0)
    inline = body.pop("search_pipeline")
    # served inline, the pipeline is stored nowhere
    _search(node, {**body, "search_pipeline": inline})
    assert handlers.get_search_pipelines(node, {}, {}, None) == (200, {})
    assert not (node.data_path / "search_pipelines.json").exists()
    handlers.put_search_pipeline(node, {"id": "nq"}, {}, inline)
    try:
        by_inline = _hits(_search(node, {**body, "search_pipeline": inline}))
        by_body_id = _hits(_search(node, {**body, "search_pipeline": "nq"}))
        by_param = _hits(_search(node, body, search_pipeline="nq"))
        assert by_inline == by_body_id == by_param
        # the query parameter wins over the body's key, object or id
        other = json.loads(json.dumps(inline))
        other["phase_results_processors"][0]["normalization-processor"][
            "combination"]["parameters"]["weights"] = [0.9, 0.1]
        swapped = _hits(_search(node, {**body, "search_pipeline": other}))
        assert swapped != by_inline
        assert _hits(_search(node, {**body, "search_pipeline": other},
                             search_pipeline="nq")) == by_inline
        assert list(handlers.get_search_pipelines(node, {}, {}, None)[1]) \
            == ["nq"]
    finally:
        handlers.delete_search_pipeline(node, {"id": "nq"}, {}, None)


@pytest.mark.parametrize("pipeline", [
    {"phase_results_processors": [{"no-such-processor": {}}]},
    {"request_processors": [{"filter_query": {}, "oversample": {}}]},
    ["normalization-processor"], 7])
def test_a_bad_inline_pipeline_is_a_400_and_not_a_500(world, pipeline):
    node, _data, questions, _ref, _ids, _scores = world
    with pytest.raises(IllegalArgumentException) as refused:
        _search(node, {**_body(questions, 0), "search_pipeline": pipeline})
    assert refused.value.status == 400
    assert handlers.get_search_pipelines(node, {}, {}, None) == (200, {})


def test_the_three_counters_move_by_the_requests_sent(world):
    node, data, questions, _ref, _ids, _scores = world
    hybrid0, launches0, postings0 = _counters(node)
    sent = range(5)
    for i in sent:
        _search(node, _body(questions, i))
    hybrid, launches, postings = _counters(node)
    assert hybrid == hybrid0 + len(sent)
    # one BM25 launch a text sub-query and segment
    segments = -(-DOCS // SEGMENT_DOCS)
    assert launches == launches0 + len(sent) * segments
    # the posting entries of the questions' words, each occurrence a term
    df = np.diff(data["postings"].ptr)
    assert postings == postings0 + sum(
        int(df[w]) for i in sent for w in questions.words[i])
    # a plain match counts its BM25 work and is no hybrid request; a plain
    # knn moves none of the three
    match, knn = _body(questions, 0)["query"]["hybrid"]["queries"]
    _search(node, {"size": SIZE, "query": match})
    assert _counters(node)[0] == hybrid
    assert _counters(node)[1] == launches + segments
    after = _counters(node)
    _search(node, {"size": SIZE, "query": knn})
    assert _counters(node) == after
    # `_nodes/stats` and Prometheus show all three
    _status, stats = handlers.nodes_stats(node, {}, {}, None)
    shown = next(iter(stats["nodes"].values()))["telemetry"]["counters"]
    assert tuple(shown[name] for name in COUNTERS) == after
    _status, text = handlers.prometheus_metrics(node, {}, {}, None)
    for name in COUNTERS:
        assert "opensearch_tpu_" + name.replace(".", "_") in text


def _traced(node, tmp_path, work) -> dict:
    """Run `work()` under a profiler session with the benchmark launcher's
    options; the capture the session left on disk, spans as dicts."""
    import jax

    before = set(glob.glob(str(node.data_path / "telemetry" / "*.json")))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    node.search(INDEX, {"size": 1, "query": {"match_all": {}}})
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        new = set(glob.glob(
            str(node.data_path / "telemetry" / "*.json"))) - before
        if new:
            doc = json.loads(open(new.pop()).read())
            doc["spans"] = [dict(zip(doc["fields"], r))
                            for r in doc["records"]]
            return doc
        time.sleep(0.05)
    raise AssertionError("no capture file after the session ended")


def test_a_capture_holds_bm25_score_and_hybrid_fuse_in_every_request(
        world, tmp_path):
    node, data, questions, _ref, _ids, _scores = world
    sent = range(6)
    knn = {"size": SIZE,
           "query": _body(questions, 0)["query"]["hybrid"]["queries"][1]}
    for i in sent:      # warm: every program compiled
        _search(node, _body(questions, i))
    _search(node, knn)

    def work():
        for i in sent:
            _search(node, _body(questions, i))
        _search(node, knn)

    doc = _traced(node, tmp_path, work)
    roots = sorted((s for s in doc["spans"] if s["name"] == "search"
                    and s["parent_id"] is None),
                   key=lambda s: s["start_ns"])
    assert len(roots) == len(sent) + 1
    df = np.diff(data["postings"].ptr)
    segments = -(-DOCS // SEGMENT_DOCS)
    for root, i in zip(roots, sent):
        tree = [s for s in doc["spans"] if s["trace_id"] == root["trace_id"]]
        by_name = {}
        for s in tree:
            by_name.setdefault(s["name"], []).append(s)
        (phase,) = by_name[span_names.SEARCH_QUERY_PHASE]
        assert phase["attributes"]["sub_queries"] == 2
        (scored,) = by_name[span_names.BM25_SCORE]      # one shard
        words = questions.words[i]
        assert scored["attributes"]["terms"] == len(words) * segments
        assert scored["attributes"]["postings"] == sum(
            int(df[w]) for w in words)
        # a launch gathers terms x window elements whatever the lists hold
        assert scored["attributes"]["window"] >= max(
            int(df[w]) for w in words) / segments
        assert scored["attributes"]["rows"] >= DOCS
        (fused,) = by_name[span_names.HYBRID_FUSE]
        assert fused["attributes"] == {
            "sub_queries": 2, "pooled": 2 * SIZE, "shards": 1}
        # the sub-queries run one after the other inside the query phase,
        # the fusion after both and before the fetch
        assert phase["start_ns"] <= scored["start_ns"]
        assert scored["end_ns"] <= fused["start_ns"]
        assert fused["end_ns"] <= phase["end_ns"]
        assert fused["end_ns"] <= by_name[span_names.SEARCH_FETCH][0][
            "start_ns"]
        # the knn sub-query's launches come after the text sub-query's span
        assert all(s["start_ns"] >= scored["end_ns"]
                   for s in by_name[span_names.LAUNCH])
    plain = [s for s in doc["spans"] if s["trace_id"] == roots[-1]["trace_id"]]
    assert not [s for s in plain if s["name"] in (
        span_names.BM25_SCORE, span_names.HYBRID_FUSE)]
    assert [s["attributes"]["sub_queries"] for s in plain
            if s["name"] == span_names.SEARCH_QUERY_PHASE] == [0]
    # the capture's counter snapshots carry the three counters
    opened, closed = (doc["counters"][k]["lexical"]
                      for k in ("open", "close"))
    assert closed["hybrid_requests"] == opened["hybrid_requests"] + len(sent)
    assert closed["bm25_launches"] == (
        opened["bm25_launches"] + len(sent) * segments)
    assert closed["bm25_postings"] - opened["bm25_postings"] == sum(
        int(df[w]) for i in sent for w in questions.words[i])


def test_the_new_spans_are_detail_spans_kept_beside_all():
    assert span_names.BM25_SCORE == "bm25.score"
    assert span_names.HYBRID_FUSE == "hybrid.fuse"
    assert not {span_names.BM25_SCORE, span_names.HYBRID_FUSE} & set(
        span_names.ALL)
