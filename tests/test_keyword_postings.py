"""A keyword field's ordinal-major postings view and the masks made from it
(PR 36): `HostKeywordField.ord_offsets` / `ord_docs` hold, ordinal by
ordinal, the documents that `mv_ords` / `mv_docs` hold document by document;
the view is built once a segment and field, whoever comes first; and every
keyword ordinal mask of the filter executor (`term`, `terms`, range on
ordinals, prefix, case-insensitive `term`) equals the all-pairs answer,
`np.isin` over `mv_ords`, that the device programs it replaces computed.
Counts only: nothing here asserts a time."""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from opensearch_tpu.index.device import to_device
from opensearch_tpu.index.engine import SearcherSnapshot
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder
from opensearch_tpu.node import TpuNode
from opensearch_tpu.ops import filters
from opensearch_tpu.search import query_dsl
from opensearch_tpu.search.executor import SegmentExecutor, ShardContext

DOCS = 400
VOCAB = [f"{p}{i:02d}" for p in ("Ab", "ab", "ba", "Zz") for i in range(12)]
DELETED = (0, 5, 17, 123, 399)


def _bags() -> list[list[str]]:
    """A seeded multi-valued field: 0 to 6 words a document from a skewed
    law, every ninth document without the field."""
    rng = np.random.default_rng(36)
    law = 1.0 / np.arange(1, len(VOCAB) + 1)
    law /= law.sum()
    return [[] if i % 9 == 4 else sorted(
        {VOCAB[w] for w in rng.choice(len(VOCAB), int(rng.integers(0, 7)),
                                      p=law)}) for i in range(DOCS)]


BAGS = _bags()


@pytest.fixture(scope="module")
def shard():
    """(mapper service, host segment, device segment) of the seeded field,
    with deletions."""
    ms = MapperService({"properties": {"tags": {"type": "keyword"}}})
    builder = SegmentBuilder(ms, "_0")
    for i, bag in enumerate(BAGS):
        builder.add(ms.parse_document(str(i), {"tags": bag} if bag else {}),
                    seq_no=i)
    host = builder.build()
    for i in DELETED:
        assert host.delete_doc(str(i))
    return ms, host, to_device(host)


def test_the_view_holds_every_ordinal_s_documents_ascending(shard):
    _ms, host, _dev = shard
    kf = host.keyword_fields["tags"]
    kf.build_postings()
    assert kf.ord_offsets.dtype == np.int64 and kf.ord_docs.dtype == np.int32
    assert len(kf.ord_offsets) == len(kf.ord_values) + 1
    assert kf.ord_offsets[0] == 0 and kf.ord_offsets[-1] == len(kf.mv_docs)
    for o, word in enumerate(kf.ord_values):
        docs = kf.ord_docs[kf.ord_offsets[o]:kf.ord_offsets[o + 1]]
        assert np.array_equal(docs, kf.mv_docs[kf.mv_ords == o])
        assert list(docs) == [i for i, bag in enumerate(BAGS) if word in bag]


def _all_pairs(kf, ords, dev) -> np.ndarray:
    """What the deleted device programs computed: a pass over every (ord,
    doc) pair of the field, cut to the live documents."""
    mask = np.zeros(dev.n_pad, bool)
    mask[kf.mv_docs[np.isin(kf.mv_ords, np.asarray(list(ords), np.int32))]] = True
    return mask & np.asarray(dev.live)


def _ords(kf, keep) -> list[int]:
    return [o for o, v in enumerate(kf.ord_values) if keep(v)]


# (query body, which of the field's values it names)
MASK_CASES = {
    "term": ({"term": {"tags": "ab00"}}, lambda v: v == "ab00"),
    "term-unknown": ({"term": {"tags": "nowhere"}}, lambda v: False),
    "term-case-insensitive": (
        {"term": {"tags": {"value": "AB01", "case_insensitive": True}}},
        lambda v: v.lower() == "ab01"),
    "terms": ({"terms": {"tags": ["Ab03", "ba00", "ba01", "nowhere", "Zz11"]}},
              lambda v: v in ("Ab03", "ba00", "ba01", "Zz11")),
    "range": ({"range": {"tags": {"gte": "ab03", "lt": "ba02"}}},
              lambda v: "ab03" <= v < "ba02"),
    "range-open": ({"range": {"tags": {"gt": "ba05"}}}, lambda v: v > "ba05"),
    "range-empty": ({"range": {"tags": {"gte": "ab03", "lte": "ab02"}}},
                    lambda v: False),
    "prefix": ({"prefix": {"tags": "ab0"}}, lambda v: v.startswith("ab0")),
    "prefix-case-insensitive": (
        {"prefix": {"tags": {"value": "aB", "case_insensitive": True}}},
        lambda v: v.lower().startswith("ab")),
    "wildcard": ({"wildcard": {"tags": "?b*1"}},
                 lambda v: v[1] == "b" and v.endswith("1")),
}


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_a_keyword_mask_equals_the_all_pairs_answer(shard, case):
    ms, host, dev = shard
    body, keep = MASK_CASES[case]
    kf = host.keyword_fields["tags"]
    ords = _ords(kf, keep)
    ex = SegmentExecutor(
        ShardContext(SearcherSnapshot(segments=[(host, dev)], generation=0),
                     ms), host, dev)
    got = np.asarray(ex.execute(query_dsl.parse_query(body)).mask)
    want = _all_pairs(kf, ords, dev)
    assert np.array_equal(got, want)
    assert not got[list(DELETED)].any() and not got[host.n_docs:].any()
    assert want.any() == (case not in (
        "term-unknown", "range-empty"))
    # the executor's tally: the posting entries of the ordinals named, and
    # no more
    assert ex.postings == int(np.isin(kf.mv_ords, ords).sum())
    assert ex.postings < len(kf.mv_ords)


def test_runs_of_consecutive_ordinals_are_one_slice_each(shard):
    _ms, host, dev = shard
    kf = host.keyword_fields["tags"]
    kf.build_postings()
    n = len(kf.ord_values)
    for ords in ([], [-3], range(0), range(3, 9), [8, 3, 4, -3, 5, 20, 21, 4],
                 range(n), list(range(n))):
        mask, postings = filters.keyword_mask_from_postings(
            kf, ords, dev.n_pad)
        held = sorted({o for o in ords if o >= 0})
        want = np.zeros(dev.n_pad, bool)
        want[kf.mv_docs[np.isin(kf.mv_ords, held)]] = True
        assert np.array_equal(mask, want)
        assert postings == int(np.isin(kf.mv_ords, held).sum())


def _builds(node) -> float:
    return node.telemetry.metrics.stats()["counters"][
        "knn.filter.postings_builds"]


def test_eight_cold_callers_build_a_field_s_view_once(tmp_path):
    node = TpuNode(tmp_path / "node")
    try:
        # registered with the node: a 0 is shown as a 0
        assert _builds(node) == 0
        node.create_index("p", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {"tags": {"type": "keyword"},
                                        "kind": {"type": "keyword"}}}})
        segments = ((0, 250), (250, DOCS))
        for lo, hi in segments:
            node.bulk([("index", {"_index": "p", "_id": str(i)},
                        {"tags": BAGS[i], "kind": f"k{i % 3}"})
                       for i in range(lo, hi)], refresh=True)
        assert _builds(node) == 0
        want = sum("ab00" in bag for bag in BAGS)
        body = {"size": 0, "track_total_hits": True, "query": {"bool": {
            "filter": [{"term": {"tags": "ab00"}}]}}}
        gate = threading.Barrier(8)
        totals, errors = [], []

        def search():
            try:
                gate.wait(timeout=30)
                totals.append(
                    node.search("p", body)["hits"]["total"]["value"])
            except Exception as e:      # noqa: BLE001 - shown below
                errors.append(e)

        threads = [threading.Thread(target=search) for _ in range(8)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)     # hand the lock over inside the check
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not errors and totals == [want] * 8
        # once a segment and field, whoever came first
        assert _builds(node) == len(segments)
        node.search("p", body)
        assert _builds(node) == len(segments)
        # another field of the same segments is another view
        node.search("p", {"size": 0, "query": {"terms": {"kind": ["k1"]}}})
        assert _builds(node) == 2 * len(segments)
    finally:
        node.close()
