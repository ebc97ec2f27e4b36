"""What a served HYBRID request (a `match` and a `knn` sub-query over one
shard, fused) has to do at least, from shapes and the program's own count
of posting entries: the work function of `hybrid_scan_roofline`. A floor
that no implementation can beat, so that the share reads the same whatever
scores the words and scans the vectors: per request the vector column's
stored bytes once, its terms' posting entries once ((document, tf) pairs,
`posting_bytes` each), the query vector in, and k (score, id) pairs out of
each of the two sub-queries; one multiply-add per dimension of every row
and BM25's handful of operations per posting entry. Padding a gather
window to a power of two, a dense score column, the fusion on the host:
all counted as no work at all."""

from __future__ import annotations

BM25_OPS_PER_POSTING = 6.0   # b dl / avgdl, + tf, k1 x, divide, x idf, add


def hybrid_scan_work(n: int, d: int, k: int, requests: float, postings: float,
                     stored_bytes: int = 4, posting_bytes: int = 8
                     ) -> tuple[float, float]:
    """(operations, bytes) of `requests` hybrid requests over `n` rows of
    `d` dimensions whose terms' posting lists hold `postings` entries
    between them (sums will do: both are linear)."""
    ops = 2.0 * requests * n * d + BM25_OPS_PER_POSTING * postings
    moved = (requests * (n * d * stored_bytes + d * 4 + 2 * k * 8)
             + postings * posting_bytes)
    return ops, float(moved)
