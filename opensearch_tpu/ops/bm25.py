"""BM25 lexical scoring: one compiled program a launch; a term row is a slice.

Replaces the reference's per-doc Lucene collector loop (the ★★ hot loop in
SURVEY.md §3.2: search/internal/ContextIndexSearcher.java:242 driving
BM25Similarity) with a vectorized formulation, traced once a
(term rows, window, n_pad, P_pad) and launched as ONE XLA program:

for each term row (a query term's postings, or one chunk of them: the
caller, search/executor.py `_bm25`, cuts a list longer than `window` into
rows of at most `window` entries and pads the rows to a few fixed counts):
    read its postings (docs, tfs) as the contiguous slice of `window`
    entries they are in the flat CSR columns (`lax.dynamic_slice` at the
    row's offset: no [rows, window] index array, no general gather),
    and each posting's document length beside them (a posting-aligned
    column the upload derives, `index/device.py`: no [n_pad] column is
    gathered),
    compute idf * tf / (tf + k1*(1 - b + b*dl/avgdl)) on the VPU,
    scatter-add contributions into a dense [n_pad] score column, and, only
    where the caller counts matched terms, ones into a count column.

A launch works over rows x window entries whatever its lists hold, so the
caller keeps both near the lists: `window` is the longest list's padded
length or the chunk width, whichever is less. Only (offset, length, idf) a
row crosses host→device at query time; postings stay resident in HBM. A
launch is one scatter-add per entry uncounted, two counted. Scoring ends in
jax.lax.top_k downstream.

Scoring math matches Lucene's BM25Similarity (idf = ln(1 + (N-df+0.5)/(df+0.5)))
with exact doc lengths instead of Lucene's lossy SmallFloat norm encoding —
scores are therefore slightly *more* accurate than the reference's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from opensearch_tpu.search.profile import profiled_kernel

K1_DEFAULT = 1.2
B_DEFAULT = 0.75


def idf(doc_freq: int, doc_count: int) -> float:
    """Lucene BM25Similarity.idfExplain."""
    return math.log(1.0 + (doc_count - doc_freq + 0.5) / (doc_freq + 0.5))


def _term_rows(
    offsets: jnp.ndarray,         # int32 [Q]
    lengths: jnp.ndarray,         # int32 [Q]
    window: int,
    *columns: jnp.ndarray,        # each [P_pad], flat CSR posting columns
) -> tuple[jnp.ndarray, ...]:
    """The one way this file reads a term row: (valid [Q, w] bool, then each
    column's [Q, w] entries), w = min(window, P_pad).

    Row i is the contiguous slice of `w` entries that holds term i's
    postings. `dynamic_slice` clamps a start that would run past the
    column's end, so the start is clamped HERE and `valid` is made from the
    positions actually read: a list that ends at the column's last entry
    is read from an earlier start and masked at its head instead of its
    tail. Entries outside a row's own list belong to its neighbours; the
    caller masks them with `valid`.
    """
    p_pad = columns[0].shape[0]
    assert all(c.shape == (p_pad,) for c in columns), "columns of one CSR"
    w = min(window, p_pad)
    starts = jnp.clip(offsets, 0, p_pad - w)                      # [Q]
    pos = starts[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    valid = (pos >= offsets[:, None]) & (pos < (offsets + lengths)[:, None])

    def rows(column):
        # traced as a gather of whole `w`-entry slices ([Q, 1] starts, no
        # [Q, w] index array); the TPU compiler runs it as a loop of Q
        # dynamic-slices
        return jax.vmap(lambda start: lax.dynamic_slice(column, (start,), (w,)))(starts)

    return (valid, *map(rows, columns))


@profiled_kernel("bm25_term_scores")
@functools.partial(jax.jit, static_argnames=("n_pad", "window", "k1", "b",
                                             "counts"))
def bm25_term_scores(
    postings_docs: jnp.ndarray,   # int32 [P_pad] flat CSR postings
    postings_tfs: jnp.ndarray,    # float32 [P_pad]
    postings_dl: jnp.ndarray,     # float32 [P_pad] each posting's doc length
    offsets: jnp.ndarray,         # int32 [Q] per-row start into postings
    lengths: jnp.ndarray,         # int32 [Q] per-row postings count (<= window)
    idfs: jnp.ndarray,            # float32 [Q] the row's term's idf weight
    avgdl: jnp.ndarray,           # float32 scalar (shard-level average doc len)
    n_pad: int,                   # static: padded doc-column size
    window: int,                  # static: entries a row reads
    k1: float = K1_DEFAULT,
    b: float = B_DEFAULT,
    counts: bool = True,          # static: also count each doc's rows
) -> tuple[jnp.ndarray, jnp.ndarray] | jnp.ndarray:
    """Returns (scores [n_pad] f32, match_counts [n_pad] i32), or the scores
    alone where `counts` is False.

    match_counts[d] = number of rows holding doc d: the number of query
    terms matching it, for the rows of one term are disjoint — the bool-query
    building block (must => count == n_required, should => count >= minimum).
    A caller that only asks whether a doc matched at all, with every row's
    idf > 0, reads `scores > 0` instead and launches without the column.
    A row is one term's postings or a chunk of them: the caller
    (search/executor.py `_bm25`) cuts a list longer than `window` into rows
    of at most `window` entries, each with the term's idf, and fills the
    launch up with rows of length 0, which score and count nothing.
    """
    valid, docs, tfs, dl = _term_rows(offsets, lengths, window, postings_docs,
                                      postings_tfs, postings_dl)
    docs = jnp.where(valid, docs, 0)                              # 0-contrib dump slot
    denom = tfs + k1 * (1.0 - b + b * dl / avgdl)
    contrib = idfs[:, None] * tfs / jnp.maximum(denom, 1e-9)
    contrib = jnp.where(valid, contrib, 0.0)
    flat_docs = docs.reshape(-1)
    scores = jnp.zeros(n_pad, jnp.float32).at[flat_docs].add(contrib.reshape(-1))
    if not counts:
        return scores
    matched = jnp.zeros(n_pad, jnp.int32).at[flat_docs].add(
        valid.reshape(-1).astype(jnp.int32)
    )
    return scores, matched


@profiled_kernel("constant_term_scores")
@functools.partial(jax.jit, static_argnames=("n_pad", "window"))
def constant_term_scores(
    postings_docs: jnp.ndarray,
    offsets: jnp.ndarray,
    lengths: jnp.ndarray,
    weights: jnp.ndarray,
    n_pad: int,
    window: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Constant-score variant (filter/term-in-constant-score context):
    each matching doc gets `weight` per term, no tf/norm math."""
    valid, docs = _term_rows(offsets, lengths, window, postings_docs)
    docs = jnp.where(valid, docs, 0)
    contrib = jnp.where(valid, weights[:, None], 0.0)
    flat = docs.reshape(-1)
    scores = jnp.zeros(n_pad, jnp.float32).at[flat].add(contrib.reshape(-1))
    counts = jnp.zeros(n_pad, jnp.int32).at[flat].add(
        valid.reshape(-1).astype(jnp.int32)
    )
    return scores, counts
