"""Core pipeline: parse docs -> build segment -> device arrays -> score ops."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.index.device import to_device
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.segment import SegmentBuilder, i64_query_words
from opensearch_tpu.ops import bm25, filters, knn, topk

MAPPINGS = {
    "properties": {
        "title": {"type": "text"},
        "tag": {"type": "keyword"},
        "price": {"type": "long"},
        "rating": {"type": "float"},
        "vec": {"type": "dense_vector", "dims": 4, "similarity": "l2_norm"},
    }
}

DOCS = [
    {"title": "the quick brown fox", "tag": "animal", "price": 10, "rating": 4.5,
     "vec": [1.0, 0.0, 0.0, 0.0]},
    {"title": "the lazy brown dog", "tag": "animal", "price": 20, "rating": 3.0,
     "vec": [0.0, 1.0, 0.0, 0.0]},
    {"title": "quick quick quick fox", "tag": "speed", "price": 30, "rating": 5.0,
     "vec": [0.9, 0.1, 0.0, 0.0]},
    {"title": "an unrelated document", "tag": "other", "price": 7_000_000_000,
     "rating": 1.0, "vec": [0.0, 0.0, 1.0, 0.0]},
]


@pytest.fixture
def segment():
    ms = MapperService(MAPPINGS)
    b = SegmentBuilder(ms, "_0")
    for i, d in enumerate(DOCS):
        b.add(ms.parse_document(str(i), d), seq_no=i)
    return b.build()


def test_segment_build_postings(segment):
    tf = segment.text_fields["title"]
    assert tf.doc_freq("quick") == 2
    assert tf.doc_freq("brown") == 2
    assert tf.doc_freq("missing") == 0
    # postings for "quick": docs 0 and 2, tf 1 and 3
    tid = tf.term_dict["quick"]
    start, end = tf.term_offsets[tid], tf.term_offsets[tid + 1]
    assert list(tf.postings_docs[start:end]) == [0, 2]
    assert list(tf.postings_tfs[start:end]) == [1.0, 3.0]
    assert tf.doc_len[0] == 4.0


def test_keyword_ordinals(segment):
    kf = segment.keyword_fields["tag"]
    assert kf.ord_values == ["animal", "other", "speed"]
    assert list(kf.first_ord) == [0, 0, 2, 1]


def test_bm25_scoring_matches_formula(segment):
    dev = to_device(segment)
    tf = segment.text_fields["title"]
    tfd = dev.text_fields["title"]
    n_pad = dev.n_pad
    # query: "quick fox"
    terms = ["quick", "fox"]
    n_docs = segment.n_docs
    avgdl = tf.total_terms / tf.docs_with_field
    offs, lens, idfs = [], [], []
    for t in terms:
        tid = tf.term_dict[t]
        offs.append(int(tf.term_offsets[tid]))
        lens.append(int(tf.term_offsets[tid + 1] - tf.term_offsets[tid]))
        idfs.append(bm25.idf(tf.doc_freq(t), n_docs))
    scores, counts = bm25.bm25_term_scores(
        tfd.postings_docs, tfd.postings_tfs, tfd.doc_len,
        jnp.asarray(offs, jnp.int32), jnp.asarray(lens, jnp.int32),
        jnp.asarray(idfs, jnp.float32), jnp.float32(avgdl),
        n_pad=n_pad, window=8,
    )
    scores = np.asarray(scores)
    counts = np.asarray(counts)
    # reference formula by hand for doc 0 ("the quick brown fox", len 4)
    def bm25_one(tf_, df):
        idf_ = math.log(1 + (n_docs - df + 0.5) / (df + 0.5))
        return idf_ * tf_ / (tf_ + 1.2 * (1 - 0.75 + 0.75 * 4.0 / avgdl))

    expected0 = bm25_one(1, 2) + bm25_one(1, 2)
    assert scores[0] == pytest.approx(expected0, rel=1e-5)
    assert counts[0] == 2          # matched both terms
    assert counts[1] == 0          # "the lazy brown dog" matches neither
    assert counts[2] == 2
    assert counts[3] == 0
    assert scores[1] == 0.0
    # doc 2 has tf=3 for quick and shorter... same len 4; should outscore doc 0
    assert scores[2] > scores[0]
    # padding region untouched
    assert scores[n_docs:].sum() == 0.0


def test_topk_tiebreak_prefers_lower_docid():
    scores = jnp.asarray([1.0, 3.0, 3.0, 2.0, 3.0] + [-np.inf] * 3)
    vals, ids = topk.segment_top_k(scores, 4)
    assert list(np.asarray(ids)) == [1, 2, 4, 3]
    assert list(np.asarray(vals)) == [3.0, 3.0, 3.0, 2.0]


def test_range_filter_i64_beyond_int32(segment):
    dev = to_device(segment)
    nf = dev.numeric_fields["price"]
    gte_hi, gte_lo = i64_query_words(15)
    lte_hi, lte_lo = i64_query_words(8_000_000_000)
    mask = filters.range_mask_i64(
        nf.hi, nf.lo, nf.present,
        jnp.int32(gte_hi), jnp.int32(gte_lo), jnp.int32(lte_hi), jnp.int32(lte_lo),
    )
    assert list(np.asarray(mask)[: segment.n_docs]) == [False, True, True, True]
    # exclusive of values below 15; doc 3 at 7e9 (beyond int32) included
    gte_hi, gte_lo = i64_query_words(6_999_999_999)
    mask = filters.range_mask_i64(
        nf.hi, nf.lo, nf.present,
        jnp.int32(gte_hi), jnp.int32(gte_lo), jnp.int32(lte_hi), jnp.int32(lte_lo),
    )
    assert list(np.asarray(mask)[: segment.n_docs]) == [False, False, False, True]


def test_keyword_term_filter(segment):
    dev = to_device(segment)
    kf = segment.keyword_fields["tag"]
    assert kf.build_postings() and not kf.build_postings()
    mask, postings = filters.keyword_mask_from_postings(
        kf, (kf.ord_dict["animal"],), dev.n_pad)
    assert mask.shape == (dev.n_pad,) and mask.dtype == bool
    assert list(mask[: segment.n_docs]) == [True, True, False, False]
    assert postings == 2
    # unknown term ordinal matches nothing
    mask, postings = filters.keyword_mask_from_postings(kf, (-3,), dev.n_pad)
    assert not mask.any() and postings == 0


def test_exact_knn_l2(segment):
    dev = to_device(segment)
    vf = dev.vector_fields["vec"]
    q = jnp.asarray([[1.0, 0.0, 0.0, 0.0]], jnp.float32)
    valid = vf.present & dev.live
    scores = knn.exact_knn_scores(q, vf.vectors, vf.norms_sq, valid, "l2_norm")
    s = np.asarray(scores)[0]
    # doc 0 is the query itself: d^2=0 -> score 1.0
    assert s[0] == pytest.approx(1.0)
    # doc 2 at [0.9, 0.1]: d^2 = 0.01 + 0.01 = 0.02 -> 1/1.02
    assert s[2] == pytest.approx(1 / 1.02, rel=1e-5)
    vals, ids = topk.segment_top_k(scores[0], 2)
    assert list(np.asarray(ids)) == [0, 2]
    # padding is -inf
    assert not np.isfinite(s[segment.n_docs:]).any()


def test_knn_cosine_and_dot():
    vecs = jnp.asarray([[1.0, 0.0], [0.5, 0.5], [-1.0, 0.0]], jnp.float32)
    norms = jnp.sum(vecs * vecs, axis=1)
    valid = jnp.asarray([True, True, True])
    q = jnp.asarray([[1.0, 0.0]], jnp.float32)
    cos = np.asarray(knn.exact_knn_scores(q, vecs, norms, valid, "cosine"))[0]
    assert cos[0] == pytest.approx(1.0)
    assert cos[1] == pytest.approx((1 + math.cos(math.pi / 4)) / 2, rel=1e-5)
    assert cos[2] == pytest.approx(0.0)
    dot = np.asarray(knn.exact_knn_scores(q, vecs, norms, valid, "dot_product"))[0]
    assert dot[0] == pytest.approx(2.0)     # 1 + 1
    assert dot[2] == pytest.approx(0.5)     # 1/(1-(-1))
