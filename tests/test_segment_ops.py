"""Core pipeline: parse docs -> build segment -> device arrays -> score ops."""

import functools
import math

import jax
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
        tfd.postings_docs, tfd.postings_tfs, tfd.postings_dl,
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


# -- bm25_term_scores: one compiled program, a term row read as a slice -----

def _columns(p_pad, n_pad, lists, rng):
    """Flat CSR posting columns holding `lists` = [(offset, docs)], the rest
    filled with entries of OTHER lists' kind (in-range doc ids, tf 7): what a
    row's slice reads beside its own list must not score."""
    docs = rng.integers(0, n_pad, p_pad).astype(np.int32)
    tfs = np.full(p_pad, 7.0, np.float32)
    for off, ids in lists:
        docs[off:off + len(ids)] = ids
        tfs[off:off + len(ids)] = rng.integers(1, 6, len(ids))
    return docs, tfs


def _bm25_loop(docs, tfs, doc_len, offsets, lengths, idfs, avgdl, n_pad,
               k1=bm25.K1_DEFAULT, b=bm25.B_DEFAULT):
    """The plain reference: a loop over rows and postings, in float32."""
    f = np.float32
    scores = np.zeros(n_pad, np.float32)
    counts = np.zeros(n_pad, np.int32)
    for off, n, idf in zip(offsets, lengths, idfs):
        for p in range(int(off), int(off) + int(n)):
            d, tf = docs[p], tfs[p]
            denom = tf + f(k1) * (f(1.0) - f(b) + f(b) * doc_len[d] / f(avgdl))
            scores[d] += f(idf) * tf / denom
            counts[d] += 1
    return scores, counts


def _split(offset, ids, width):
    """One term's list as the caller launches it: rows of at most `width`
    entries, in order (search/executor.py `_chunk_rows`)."""
    return [(offset + at, ids[at:at + width]) for at in range(0, len(ids), width)]


def _case(name):
    """(p_pad, n_pad, window, terms): a term is its rows [(offset, doc ids)],
    all with the term's idf."""
    rng = np.random.default_rng(38)
    pick = lambda n_pad, n: np.sort(rng.choice(n_pad, n, replace=False)).astype(np.int32)
    if name == "rows_of_length_0_between_real_ones":   # a rung's padding
        rows = [(3, pick(32, 5)), (0, []), (20, pick(32, 16)), (0, []),
                (40, pick(32, 9)), (50, pick(32, 3)), (0, []), (0, [])]
        return 64, 32, 16, [[row] for row in rows]
    if name == "a_list_ends_at_the_columns_last_entry":  # offset + window > P_pad
        rows = [(64 - 10, pick(32, 10)), (5, pick(32, 16)), (0, []), (0, [])]
        return 64, 32, 16, [[row] for row in rows]
    if name == "window_equals_p_pad":
        rows = [(0, pick(8, 3)), (3, pick(8, 5)), (0, []), (0, [])]
        return 8, 8, 8, [[row] for row in rows]
    if name == "window_over_p_pad":
        rows = [(0, pick(16, 16)), (0, []), (0, []), (0, [])]
        return 16, 16, 32, [[row] for row in rows]
    if name == "two_terms_share_documents":
        shared = pick(64, 12)
        rows = [(7, shared), (100, shared[::2].copy()), (30, pick(64, 20)),
                (0, [])]
        return 128, 64, 32, [[row] for row in rows]
    if name == "a_list_longer_than_the_window_split_into_rows":
        shared = pick(128, 45)
        return 256, 128, 16, [_split(30, shared, 16),
                              [(120, shared[::5].copy())],
                              [(0, [])] * 4]
    if name == "a_split_list_ends_at_the_columns_last_entry":
        return 64, 64, 16, [_split(24, pick(64, 40), 16), [(3, pick(64, 9))],
                            [(0, [])] * 4]
    if name == "ladder_padding_between_a_split_terms_rows":
        (a, b, c), ids = _split(8, pick(64, 40), 16), pick(64, 11)
        return 128, 64, 16, [[a, (0, []), b, (0, []), (0, []), c], [(0, [])],
                             [(90, ids), (0, []), (0, [])], [(0, [])] * 2]
    raise AssertionError(name)


BM25_CASES = ("rows_of_length_0_between_real_ones",
              "a_list_ends_at_the_columns_last_entry", "window_equals_p_pad",
              "window_over_p_pad", "two_terms_share_documents",
              "a_list_longer_than_the_window_split_into_rows",
              "a_split_list_ends_at_the_columns_last_entry",
              "ladder_padding_between_a_split_terms_rows")


def _launch_args(name, metadata):
    p_pad, n_pad, window, terms = _case(name)
    rng = np.random.default_rng(len(name))
    rows = [row for term in terms for row in term]
    docs, tfs = _columns(p_pad, n_pad, [(o, ids) for o, ids in rows if len(ids)], rng)
    doc_len = rng.integers(1, 60, n_pad).astype(np.float32)
    offsets = np.asarray([o for o, _ids in rows], np.int32)
    lengths = np.asarray([len(ids) for _o, ids in rows], np.int32)
    idfs = np.asarray([idf if len(ids) else 0.0
                       for term, idf in zip(terms, rng.random(len(terms)) + 0.1)
                       for _o, ids in term], np.float32)
    avgdl = np.float32(doc_len.mean())
    host = (docs, tfs, doc_len, offsets, lengths, idfs, avgdl)
    as_meta = jnp.asarray if metadata == "device" else np.asarray
    # the launch reads each posting's length from a posting-aligned column
    # (index/device.py `postings_dl`); the loop reads doc_len by doc
    device = (jnp.asarray(docs), jnp.asarray(tfs), jnp.asarray(doc_len[docs]),
              as_meta(offsets), as_meta(lengths), as_meta(idfs), avgdl)
    return host, device, n_pad, window


def _terms_holding(name, n_pad):
    """counts[d] as the terms see it: how many terms' lists hold d, however
    each list was cut into rows."""
    _p_pad, _n_pad, _window, terms = _case(name)
    held = np.zeros(n_pad, np.int32)
    for term in terms:
        ids = [d for _o, row in term for d in row]
        assert len(set(ids)) == len(ids)
        held[np.asarray(ids, np.int64)] += 1
    return held


@pytest.mark.parametrize("metadata", ["host", "device"])
@pytest.mark.parametrize("name", BM25_CASES)
def test_bm25_term_scores_is_the_loop_over_rows_and_postings(name, metadata):
    host, device, n_pad, window = _launch_args(name, metadata)
    want_scores, want_counts = _bm25_loop(*host, n_pad)
    scores, counts = bm25.bm25_term_scores(*device, n_pad=n_pad, window=window)
    assert scores.dtype == jnp.float32 and counts.dtype == jnp.int32
    assert np.asarray(counts).tolist() == want_counts.tolist()
    assert want_counts.sum() == host[4].sum() > 0
    # a term cut into several rows still counts once a document
    assert want_counts.tolist() == _terms_holding(name, n_pad).tolist()
    np.testing.assert_allclose(np.asarray(scores), want_scores, rtol=1e-6, atol=0)


@pytest.mark.parametrize("name", BM25_CASES)
def test_constant_term_scores_reads_its_rows_the_same_way(name):
    host, device, n_pad, window = _launch_args(name, "host")
    docs, _tfs, _dl, offsets, lengths, weights, _avgdl = host
    scores, counts = bm25.constant_term_scores(
        device[0], offsets, lengths, weights, n_pad=n_pad, window=window)
    want = np.zeros(n_pad, np.float32)
    want_counts = np.zeros(n_pad, np.int32)
    for off, n, w in zip(offsets, lengths, weights):
        np.add.at(want, docs[off:off + n], w)
        np.add.at(want_counts, docs[off:off + n], 1)
    assert np.asarray(counts).tolist() == want_counts.tolist()
    np.testing.assert_allclose(np.asarray(scores), want, rtol=1e-6, atol=0)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("rows", [4, 8, 20])
def test_a_term_row_is_read_as_a_slice_and_not_gathered(rows):
    """The traced program builds no [rows, window] index array into the
    posting columns: each row is `window` contiguous entries of each column
    (the doc ids, the tfs and the docs' lengths), and no element gather is
    left, counted or not."""
    p_pad, n_pad, window = 4096, 256, 128        # p_pad != n_pad: told apart
    meta = (np.zeros(rows, np.int32), np.zeros(rows, np.int32),
            np.zeros(rows, np.float32))
    columns = (jnp.zeros(p_pad, jnp.int32), jnp.zeros(p_pad, jnp.float32),
               jnp.ones(p_pad, jnp.float32))

    def gathers(fn, *args, **static):
        """(operand shape, index array shape, slice_sizes) of every gather."""
        eqns = _equations(jax.make_jaxpr(
            lambda *a: fn(*a, n_pad=n_pad, window=window, **static))(
                *args).jaxpr)
        return sorted((eqn.invars[0].aval.shape, eqn.invars[1].aval.shape,
                       tuple(eqn.params["slice_sizes"]))
                      for eqn in eqns if eqn.primitive.name == "gather")

    a_column = ((p_pad,), (rows, 1), (window,))     # `rows` starts, whole slices
    for counts in (True, False):
        assert gathers(bm25.bm25_term_scores, *columns, *meta,
                       np.float32(3.0), counts=counts) == [a_column] * 3
    assert gathers(bm25.constant_term_scores, columns[0], *meta) == [a_column]


def test_a_second_launch_of_the_same_shapes_traces_nothing():
    program = bm25.bm25_term_scores.__wrapped__       # inside the profiler's wrapper
    host, device, n_pad, window = _launch_args("two_terms_share_documents", "host")
    bm25.bm25_term_scores(*device, n_pad=n_pad, window=window)
    compiled = program._cache_size()
    # other offsets, lengths and idfs, the same (rows, window, n_pad, P_pad)
    docs, tfs, doc_len, offsets, lengths, idfs, avgdl = device
    again = (docs, tfs, doc_len, offsets[::-1].copy(), lengths[::-1].copy(),
             idfs[::-1].copy(), np.float32(avgdl * 2))
    first = bm25.bm25_term_scores(*device, n_pad=n_pad, window=window)
    second = bm25.bm25_term_scores(*again, n_pad=n_pad, window=window)
    assert program._cache_size() == compiled
    assert np.asarray(first[1]).tolist() == np.asarray(second[1]).tolist()
    # another number of term rows is another program, once
    wider = tuple(np.concatenate([a, a]) for a in (offsets, lengths, idfs))
    bm25.bm25_term_scores(docs, tfs, doc_len, *wider, avgdl, n_pad=n_pad, window=window)
    bm25.bm25_term_scores(docs, tfs, doc_len, *wider, avgdl, n_pad=n_pad, window=window)
    assert program._cache_size() == compiled + 1


# -- the posting-length column and the count column, against the program
# that gathered doc_len[docs] and always counted ------------------------------

@functools.partial(jax.jit, static_argnames=("n_pad", "window"))
def _gathering_program(postings_docs, postings_tfs, doc_len, offsets, lengths,
                       idfs, avgdl, n_pad, window,
                       k1=bm25.K1_DEFAULT, b=bm25.B_DEFAULT):
    """The launch as it was before the length travelled with the posting:
    `doc_len[docs]` gathered from the [n_pad] column, two scatter-adds."""
    valid, docs, tfs = bm25._term_rows(offsets, lengths, window,
                                       postings_docs, postings_tfs)
    docs = jnp.where(valid, docs, 0)
    dl = doc_len[docs]
    denom = tfs + k1 * (1.0 - b + b * dl / avgdl)
    contrib = idfs[:, None] * tfs / jnp.maximum(denom, 1e-9)
    contrib = jnp.where(valid, contrib, 0.0)
    flat_docs = docs.reshape(-1)
    scores = jnp.zeros(n_pad, jnp.float32).at[flat_docs].add(contrib.reshape(-1))
    counts = jnp.zeros(n_pad, jnp.int32).at[flat_docs].add(
        valid.reshape(-1).astype(jnp.int32))
    return scores, counts


def _corpus_launch(seed):
    """A seeded random corpus's CSR columns and one OR match over it, laid
    out as `_bm25` lays it out: lists of up to 40,000 documents cut into
    rows of `BM25_CHUNK` = 16,384 entries, an absent term (a row of length
    0), and the rows padded up the ladder with more rows of length 0."""
    from opensearch_tpu.search.executor import (BM25_CHUNK, BM25_ROW_RUNGS,
                                                _chunk_rows, _padded)

    rng = np.random.default_rng(seed)
    n_docs = 50_000 + int(rng.integers(0, 10_000))
    n_pad = 1 << 16
    dfs = [40_000, 17_000, int(rng.integers(1, 300)), 0, 16_384, 5]
    lists = [np.sort(rng.choice(n_docs, df, replace=False)) for df in dfs]
    offsets = np.cumsum([0] + dfs[:-1])
    docs = np.concatenate(lists).astype(np.int32)
    tfs = rng.integers(1, 9, len(docs)).astype(np.float32)
    doc_len = np.zeros(n_pad, np.float32)
    doc_len[:n_docs] = rng.integers(1, 400, n_docs)
    p_pad = 1 << int(len(docs) - 1).bit_length()
    docs = np.concatenate([docs, np.zeros(p_pad - len(docs), np.int32)])
    tfs = np.concatenate([tfs, np.zeros(p_pad - len(tfs), np.float32)])
    idfs = np.asarray([bm25.idf(df, n_docs) for df in dfs], np.float32)
    offs, lens, row_idfs = _chunk_rows(offsets.astype(np.int64),
                                       np.asarray(dfs, np.int64), idfs,
                                       BM25_CHUNK)
    rung = next(r for r in BM25_ROW_RUNGS if r >= len(offs))
    assert rung > len(offs) and (lens == 0).any() and (lens == BM25_CHUNK).any()
    rows = (_padded(offs, rung, np.int32), _padded(lens, rung, np.int32),
            _padded(row_idfs, rung, np.float32), np.float32(doc_len[:n_docs].mean()))
    return docs, tfs, doc_len, rows, n_pad, BM25_CHUNK


@pytest.mark.parametrize("seed", [42, 2**31 + 42, 3142000042])
@pytest.mark.parametrize("check", ["scores_bitwise", "counts", "or_mask"])
def test_the_posting_length_column_and_the_count_column_change_no_answer(
        check, seed):
    """scores_bitwise: the length read from the posting column scores every
    document bit for bit as `doc_len[docs]` did, counted or not; counts:
    the counted launch counts as before; or_mask: for an OR match (every
    idf > 0) a document scores above 0 exactly where a row holds it, so
    the uncounted launch's `scores > 0` is the old `counts > 0`."""
    docs, tfs, doc_len, rows, n_pad, window = _corpus_launch(seed)
    static = {"n_pad": n_pad, "window": window}
    old_scores, old_counts = _gathering_program(
        jnp.asarray(docs), jnp.asarray(tfs), jnp.asarray(doc_len), *rows,
        **static)
    columns = (jnp.asarray(docs), jnp.asarray(tfs),
               jnp.asarray(doc_len[docs]))
    scores, counts = bm25.bm25_term_scores(*columns, *rows, **static)
    uncounted = bm25.bm25_term_scores(*columns, *rows, counts=False, **static)
    assert isinstance(uncounted, jax.Array)
    old_scores, old_counts = np.asarray(old_scores), np.asarray(old_counts)
    if check == "scores_bitwise":
        for got in (scores, uncounted):
            assert np.array_equal(np.asarray(got).view(np.uint32),
                                  old_scores.view(np.uint32))
    elif check == "counts":
        assert np.array_equal(np.asarray(counts), old_counts)
        assert old_counts.max() >= 4 and old_counts.sum() == rows[1].sum()
    else:
        assert (rows[2][rows[1] > 0] > 0).all()
        assert np.array_equal(np.asarray(uncounted) > 0, old_counts > 0)
        assert 0 < (old_counts > 0).sum() < n_pad


def test_topk_tiebreak_prefers_lower_docid():
    scores = jnp.asarray([1.0, 3.0, 3.0, 2.0, 3.0] + [-np.inf] * 3)
    vals, ids = topk.segment_top_k(scores, 4)
    assert list(np.asarray(ids)) == [1, 2, 4, 3]
    assert list(np.asarray(vals)) == [3.0, 3.0, 3.0, 2.0]


def test_segment_top_k_is_one_program_and_traces_once():
    """The query phase's cut after `bm25_term_scores`: a second call at the
    same (shape, k) traces nothing, on the blockwise route (two `fori_loop`s)
    as on the sort's."""
    rng = np.random.default_rng(3)
    for n in (64, 65_536):                       # the sort; block-max pruning
        scores = rng.random(n).astype(np.float32)
        vals, ids = topk.segment_top_k(jnp.asarray(scores), 10)
        compiled = topk.segment_top_k._cache_size()
        again, _ = topk.segment_top_k(jnp.asarray(scores[::-1].copy()), 10)
        assert topk.segment_top_k._cache_size() == compiled
        order = np.lexsort((np.arange(n), -scores))[:10]
        assert np.asarray(ids).tolist() == order.tolist()
        assert np.asarray(vals).tolist() == scores[order].tolist()
        assert sorted(np.asarray(again).tolist()) == sorted(np.asarray(vals).tolist())


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
