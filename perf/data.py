"""Data from --seed and the plain reference: the yardstick's own copies.

`Mixture` and `Reference` are copies of `chip_smoke.py`'s (PR 21), kept
here so that a later PR that changes the smoke cannot move the benchmark.
numpy and the standard library only — nothing of the program is imported,
and nothing the program has made (no scores, norms or ids) is read.

Mixture (from a seed): 1,024 cluster centres with gamma(2, 18)
coordinates, each point its centre plus a 12-dimensional latent offset
(sigma 5) through one shared random basis plus N(0, 3) noise, clipped to
[0, 255]; corpus rows rounded to integers as SIFT descriptors are, queries
NOT rounded, so a matmul that drops to one bf16 pass shows as a score
error. Rows come in fixed blocks keyed by (seed, block): row i is the same
at any corpus size. The benchmark draws the corpus from the configuration's
`corpus_seed` (one fixed data set, as the source's is: an ingest of a new
one takes longer than a run may) and the queries, timed and warm-up, from
--seed.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 65_536
N_CENTERS = 1024
LATENT = 12
LATENT_SIGMA = 5.0
GROUP = 64          # rows per group of the reference's two-level ranking
CAND_GROUPS = 32    # groups kept per query (the top 10 lie in at most 10)


class Mixture:
    def __init__(self, seed: int, dims: int):
        self.seed, self.dims = seed, dims
        root = np.random.default_rng([seed, 0])
        self.centers = root.gamma(2.0, 18.0, (N_CENTERS, dims))
        self.basis = root.standard_normal((LATENT, dims))

    def _draw(self, rng: np.random.Generator, rows: int) -> np.ndarray:
        a = rng.integers(0, N_CENTERS, rows)
        z = rng.standard_normal((rows, LATENT)) * LATENT_SIGMA
        x = self.centers[a] + z @ self.basis \
            + rng.normal(0, 3.0, (rows, self.dims))
        return np.clip(x, 0.0, 255.0)

    def corpus(self, n: int) -> np.ndarray:
        """[n, dims] float32, integer-valued; row i is the same at any n."""
        out = np.empty((n, self.dims), np.float32)
        for block, lo in enumerate(range(0, n, BLOCK_ROWS)):
            rng = np.random.default_rng([self.seed, 1, block])
            rows = self._draw(rng, BLOCK_ROWS)
            hi = min(lo + BLOCK_ROWS, n)
            out[lo:hi] = np.rint(rows[: hi - lo])
        return out

    def queries(self, seed: int, stream: int, n: int) -> np.ndarray:
        """Real-valued draws from this mixture, fixed by (seed, stream)."""
        rng = np.random.default_rng([self.seed, 2, seed, stream])
        return self._draw(rng, n).astype(np.float32)


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


class Reference:
    """Brute-force kNN (l2) over the same vectors, independent of the code
    under test: one BLAS pass ranks, then the candidates' distances are
    recomputed in float64 so the comparison has no rounding of its own.
    OpenSearch's l2 score of a hit is 1 / (1 + d2)."""

    def __init__(self, corpus: np.ndarray):
        self.corpus = corpus
        self.norms = np.einsum("nd,nd->n", corpus, corpus, dtype=np.float64)

    def d2(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        diff = self.corpus[ids].astype(np.float64) - q.astype(np.float64)
        return np.einsum("nd,nd->n", diff, diff)

    def topk(self, queries: np.ndarray, k: int, block: int = 1024,
             chunk_groups: int = 128, gate=None):
        """(ids [Q, k] by ascending distance then id, exact d2 [Q, k]).

        `gate` (a `threading.Event`, optional) is waited on before every
        chunk and every query: the harness clears it while it warms up and
        measures, so that the reference takes no core from the timed path,
        and sets it again once the window has closed.

        Two levels, so that a batch costs one matmul and one min-reduction
        instead of a partition of n per query: the k nearest rows lie in at
        most k of the GROUP-row groups, and each of those groups has one of
        the k smallest group minima; CAND_GROUPS > k leaves room for the
        float32 rounding of the ranking pass. The ranking pass works on
        chunks of the corpus that stay in the cache, rows by queries, with
        the norms riding in the matmul as one more column (|c|^2 - 2 q.c
        ranks as the distance does), so a set of some thousand queries
        costs seconds: most of it fits beside the node's boot."""
        n, d = self.corpus.shape
        n_groups = -(-n // GROUP)
        keep = min(CAND_GROUPS, n_groups)
        chunk = chunk_groups * GROUP
        rows = np.empty((chunk, d + 1), np.float32)
        ids = np.empty((len(queries), k), np.int64)
        d2 = np.empty((len(queries), k), np.float64)
        for lo in range(0, len(queries), block):
            q = queries[lo:lo + block]
            cols = np.empty((d + 1, len(q)), np.float32)
            cols[:d], cols[d] = -2.0 * q.T, 1.0
            mins = np.empty((n_groups, len(q)), np.float32)
            for c0 in range(0, n, chunk):
                if gate is not None:
                    gate.wait()
                c1 = min(c0 + chunk, n)
                rows[:c1 - c0, :d] = self.corpus[c0:c1]
                rows[:c1 - c0, d] = self.norms[c0:c1]
                part = rows[:c1 - c0] @ cols
                if (c1 - c0) % GROUP:
                    part = np.pad(part, ((0, -(c1 - c0) % GROUP), (0, 0)),
                                  constant_values=np.inf)
                mins[c0 // GROUP:c0 // GROUP + len(part) // GROUP] = \
                    part.reshape(-1, GROUP, len(q)).min(axis=1)
            groups = np.argpartition(mins, keep - 1, axis=0)[:keep].T
            for i in range(len(q)):
                if gate is not None:
                    gate.wait()
                cand = (groups[i][:, None] * GROUP
                        + np.arange(GROUP)[None, :]).ravel()
                cand = cand[cand < n]
                exact = self.d2(q[i], cand)
                order = np.lexsort((cand, exact))[:k]
                ids[lo + i], d2[lo + i] = cand[order], exact[order]
        return ids, d2

    def topk_lower_precision(self, queries: np.ndarray, k: int,
                             block: int = 128):
        """The control: the same brute force in the nearest precision
        below the configuration's float32 — the query rounded to bfloat16
        (the corpus is integer-valued below 256, exact in bfloat16), one
        pass accumulated in float32, ranked and scored by that pass alone.
        Returns (ids [Q, k], scores [Q, k]) in score order."""
        ids = np.empty((len(queries), k), np.int64)
        scores = np.empty((len(queries), k), np.float64)
        for lo in range(0, len(queries), block):
            q = queries[lo:lo + block]
            approx = round_bf16(q) @ self.corpus.T
            approx *= -2.0
            approx += self.norms.astype(np.float32)[None, :]
            approx += np.einsum("bd,bd->b", q, q)[:, None]
            part = np.argpartition(approx, k - 1, axis=1)[:, :k]
            vals = np.take_along_axis(approx, part, axis=1)
            order = np.argsort(vals, axis=1, kind="stable")
            ids[lo:lo + len(q)] = np.take_along_axis(part, order, axis=1)
            scores[lo:lo + len(q)] = 1.0 / (1.0 + np.maximum(
                np.take_along_axis(vals, order, axis=1), 0.0))
        return ids, scores


def bulk_bodies(corpus: np.ndarray, field: str, docs_per_request: int):
    """Yield (first id, docs, NDJSON bytes) for `_bulk`, ids = row numbers.

    The numbers are laid out by numpy, four bytes each (digits right-
    aligned behind JSON white space, then the comma), because a Python
    loop over 128 million values would take longer than the node's own
    work and set-up is what every run of every later check pays."""
    n, dims = corpus.shape
    head = b'{"%s":[' % field.encode()
    for lo in range(0, n, docs_per_request):
        v = corpus[lo:lo + docs_per_request].astype(np.int64)
        if v.min() < 0 or v.max() > 999:
            raise ValueError("bulk_bodies lays out integers in [0, 999]")
        cell = np.empty(v.shape + (4,), np.uint8)
        cell[..., 0] = np.where(v >= 100, 48 + v // 100, 32)
        cell[..., 1] = np.where(v >= 10, 48 + (v // 10) % 10, 32)
        cell[..., 2] = 48 + v % 10
        cell[..., 3] = ord(",")
        cell[:, -1, 3] = ord("]")
        rows = cell.reshape(len(v), dims * 4)
        lines = []
        for i in range(len(v)):
            lines.append(b'{"index":{"_id":"%d"}}' % (lo + i))
            lines.append(head + rows[i].tobytes() + b"}")
        yield lo, len(v), b"\n".join(lines) + b"\n"
