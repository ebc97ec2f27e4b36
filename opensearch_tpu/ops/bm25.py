"""BM25 lexical scoring: one compiled program a launch; a term row is a slice.

Replaces the reference's per-doc Lucene collector loop (the ★★ hot loop in
SURVEY.md §3.2: search/internal/ContextIndexSearcher.java:242 driving
BM25Similarity) with a vectorized formulation, traced once a
(term rows, window, n_pad, P_pad) and launched as ONE XLA program:

for each query term q (padded to a static Q):
    read its postings (docs, tfs) as the contiguous slice of `window`
    entries they are in the flat CSR columns (`lax.dynamic_slice` at the
    row's offset: no [Q, window] index array, no general gather),
    compute idf * tf / (tf + k1*(1 - b + b*dl/avgdl)) on the VPU,
    scatter-add contributions into a dense [n_pad] score column.

Only (offset, length, idf) per query term crosses host→device at query time;
postings stay resident in HBM. Scoring ends in jax.lax.top_k downstream.

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
@functools.partial(jax.jit, static_argnames=("n_pad", "window", "k1", "b"))
def bm25_term_scores(
    postings_docs: jnp.ndarray,   # int32 [P_pad] flat CSR postings
    postings_tfs: jnp.ndarray,    # float32 [P_pad]
    doc_len: jnp.ndarray,         # float32 [n_pad]
    offsets: jnp.ndarray,         # int32 [Q] per-query-term start into postings
    lengths: jnp.ndarray,         # int32 [Q] per-query-term postings count
    idfs: jnp.ndarray,            # float32 [Q] precomputed idf weights
    avgdl: jnp.ndarray,           # float32 scalar (shard-level average doc len)
    n_pad: int,                   # static: padded doc-column size
    window: int,                  # static: padded per-term postings window
    k1: float = K1_DEFAULT,
    b: float = B_DEFAULT,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (scores [n_pad] f32, match_counts [n_pad] i32).

    match_counts[d] = number of query terms matching doc d — the bool-query
    building block (must => count == n_required, should => count >= minimum).
    Terms whose postings exceed `window` must be split by the caller into
    multiple (offset, length) rows; idf weight rides along unchanged.
    """
    valid, docs, tfs = _term_rows(offsets, lengths, window,
                                  postings_docs, postings_tfs)
    docs = jnp.where(valid, docs, 0)                              # 0-contrib dump slot
    dl = doc_len[docs]
    denom = tfs + k1 * (1.0 - b + b * dl / avgdl)
    contrib = idfs[:, None] * tfs / jnp.maximum(denom, 1e-9)
    contrib = jnp.where(valid, contrib, 0.0)
    flat_docs = docs.reshape(-1)
    scores = jnp.zeros(n_pad, jnp.float32).at[flat_docs].add(contrib.reshape(-1))
    counts = jnp.zeros(n_pad, jnp.int32).at[flat_docs].add(
        valid.reshape(-1).astype(jnp.int32)
    )
    return scores, counts


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
