"""Full-text queries through `SegmentExecutor` where the BM25 launch scatters
a match-count column only for queries that read one: a `match` with
`operator: and` or `minimum_should_match`, a `match_phrase` and the body of
a `match_phrase_prefix` count; an OR `match` reads `scores > 0` and
launches without the column (`search.bm25.uncounted_launches`), unless a
term's idf is not positive. Every query answers as a numpy reference over
the documents' words says: the same hits, the same scores."""

from __future__ import annotations

import numpy as np
import pytest

from opensearch_tpu.index.device import to_device
from opensearch_tpu.index.engine import SearcherSnapshot
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder
from opensearch_tpu.ops import bm25
from opensearch_tpu.search import batcher, query_dsl
from opensearch_tpu.search.executor import SegmentExecutor, ShardContext
from opensearch_tpu.telemetry.tracing import MetricsRegistry

DOCS = 600
VOCAB = [f"w{i:02d}" for i in range(60)]
FIELD = "body"


def _texts() -> list[list[str]]:
    """A seeded corpus: 3 to 30 words a document from a skewed law, so the
    common words sit next to each other often enough for phrases."""
    rng = np.random.default_rng(42)
    law = 1.0 / np.arange(1, len(VOCAB) + 1)
    law /= law.sum()
    return [[VOCAB[w] for w in rng.choice(len(VOCAB), int(rng.integers(3, 31)),
                                          p=law)] for _ in range(DOCS)]


TEXTS = _texts()

# (query body, the query's words, which documents it holds: f(counts, doc))
CASES = {
    "or": ({"match": {FIELD: "w01 w07 w33 nowhere"}},
           ["w01", "w07", "w33", "nowhere"], lambda c, d: c >= 1),
    "and": ({"match": {FIELD: {"query": "w01 w07", "operator": "and"}}},
            ["w01", "w07"], lambda c, d: c >= 2),
    "minimum_should_match": (
        {"match": {FIELD: {"query": "w01 w07 w33", "minimum_should_match": 2}}},
        ["w01", "w07", "w33"], lambda c, d: c >= 2),
    "match_phrase": ({"match_phrase": {FIELD: "w00 w01"}}, ["w00", "w01"],
                     lambda c, d: any(TEXTS[d][i:i + 2] == ["w00", "w01"]
                                      for i in range(len(TEXTS[d])))),
    # the body's words all held, and a word that starts with the prefix
    # (10 expansions, under `max_expansions`)
    "match_phrase_prefix": (
        {"match_phrase_prefix": {FIELD: "w00 w02 w1"}}, ["w00", "w02"],
        lambda c, d: c >= 2 and any(w.startswith("w1") for w in TEXTS[d])),
}


@pytest.fixture(scope="module")
def segment():
    ms = MapperService({"properties": {FIELD: {"type": "text"}}})
    builder = SegmentBuilder(ms, "_0")
    for i, words in enumerate(TEXTS):
        builder.add(ms.parse_document(str(i), {FIELD: " ".join(words)}),
                    seq_no=i)
    host = builder.build()
    return ms, host, to_device(host)


@pytest.fixture
def metrics(monkeypatch):
    """The registry `count_metric` records into outside a node's request."""
    registry = MetricsRegistry()
    monkeypatch.setattr(batcher.default_batcher, "metrics", registry)
    return registry


def _executor(segment) -> SegmentExecutor:
    ms, host, dev = segment
    ctx = ShardContext(SearcherSnapshot(segments=[(host, dev)], generation=0), ms)
    return SegmentExecutor(ctx, host, dev)


def _reference(words: list[str], holds, df_of=None):
    """(hits bool [DOCS], scores [DOCS]) in float64: BM25 with k1 1.2, b 0.75
    summed over the query's words (a repeated word a clause of its own),
    over the documents `holds` keeps, given how many words each holds."""
    dl = np.asarray([len(t) for t in TEXTS], np.float64)
    avgdl = dl.mean()
    tf = {w: np.asarray([t.count(w) for t in TEXTS], np.float64) for w in words}
    scores = np.zeros(DOCS)
    counts = np.zeros(DOCS, np.int64)
    for w in words:
        df = int((tf[w] > 0).sum()) if df_of is None else df_of(w)
        weight = bm25.idf(df, DOCS) * tf[w] / (
            tf[w] + 1.2 * (1 - 0.75 + 0.75 * dl / avgdl))
        scores += np.where(tf[w] > 0, weight, 0.0)
        counts += tf[w] > 0
    hits = np.asarray([bool(holds(counts[d], d)) for d in range(DOCS)])
    return hits, scores


def _launches(metrics) -> tuple[int, int]:
    return (metrics.counter("search.bm25.launches").value,
            metrics.counter("search.bm25.uncounted_launches").value)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_full_text_query_answers_as_the_reference_and_counts_where_it_must(
        segment, metrics, case):
    body, words, holds = CASES[case]
    ex = _executor(segment)
    before = _launches(metrics)
    result = ex.execute(query_dsl.parse_query(body))
    launches, uncounted = np.subtract(_launches(metrics), before)
    mask = np.asarray(result.mask)
    scores = np.asarray(result.scores)
    want_hits, want_scores = _reference(words, holds)
    assert 20 < want_hits.sum() < DOCS
    assert np.array_equal(mask[:DOCS], want_hits) and not mask[DOCS:].any()
    np.testing.assert_allclose(scores[:DOCS][want_hits],
                               want_scores[want_hits], rtol=1e-5)
    assert launches == 1
    assert uncounted == (case == "or")


def test_an_or_match_uncounted_scores_and_matches_as_the_counted_launch(
        segment, metrics):
    """The same OR terms through `_bm25` with and without the count column:
    the scores bit for bit, and `scores > 0` the old `counts > 0`."""
    ex = _executor(segment)
    terms = ["w01", "w07", "w33", "nowhere", "w07"]
    plain, none = ex._bm25(FIELD, terms, 2.0)
    counted, counts = ex._bm25(FIELD, terms, 2.0, counts=True)
    assert none is None and counts is not None
    assert np.array_equal(np.asarray(plain.scores).view(np.uint32),
                          np.asarray(counted.scores).view(np.uint32))
    assert np.array_equal(np.asarray(plain.mask), np.asarray(counts) > 0)
    assert np.array_equal(np.asarray(plain.mask), np.asarray(counted.mask))
    assert _launches(metrics) == (2, 1)


def test_a_term_whose_idf_is_not_positive_falls_back_to_counts(
        segment, metrics, monkeypatch):
    """Statistics that give a term more documents than the field holds make
    its idf negative: its documents score below 0 and still match, so the
    launch counts, though the query is an OR match."""
    stats = ShardContext.text_df
    inflated = lambda w: 10 * DOCS if w == "w01" else None
    monkeypatch.setattr(ShardContext, "text_df", lambda self, field, term: (
        inflated(term) or stats(self, field, term)))
    assert bm25.idf(10 * DOCS, DOCS) < 0
    body, words, holds = CASES["or"]
    ex = _executor(segment)
    result = ex.execute(query_dsl.parse_query(body))
    mask = np.asarray(result.mask)[:DOCS]
    scores = np.asarray(result.scores)[:DOCS]
    want_hits, want_scores = _reference(
        words, holds, lambda w: inflated(w) or sum(w in t for t in TEXTS))
    assert np.array_equal(mask, want_hits)
    np.testing.assert_allclose(scores[want_hits], want_scores[want_hits],
                               rtol=1e-5, atol=1e-6)
    # documents that only the negative term holds: matched, below 0
    assert (mask & (scores < 0)).any()
    assert _launches(metrics) == (1, 0)
