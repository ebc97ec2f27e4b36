"""Filtered l2 kNN at the shapes of big-ann-benchmarks' NeurIPS'23 filtered
track (YFCC-10M): every document holds a 192-d vector of integers in 0-255
and a bag of tags from a 200,386-word vocabulary; every query carries a
vector and one or two tags that a result must ALL carry; the request is
OpenSearch's efficient k-NN filtering, `knn.<field>.filter` = a `bool`
whose `filter` is a list of `term` clauses on the keyword field of tags.
The six things of `perf/README.md` "A kind of deployment".

What is the source's and what is set here (no network: the data set is made
from `corpus_seed`): the shapes above are the source's; the vectors are
`perf/data.py`'s clustered integer mixture at 192 dimensions; the bags are
drawn from a Zipf-like law over the whole vocabulary, `ZIPF`, with
1 + NegativeBinomial(`BAG_SHAPE`, mean `BAG_MEAN_MORE`) words drawn a row
(a heavy-tailed size; about eleven distinct words a bag, which is what the
builder knows of the source's metadata matrix: on the order of 1e8 entries
for its 1e7 rows). Rows come in fixed blocks keyed by (`corpus_seed`,
block), so row i is the same at any `docs`.

At this size nothing is dense: the bags are a CSR pair (`indptr`, `tags`,
a row's words sorted), eligibility is the intersection of the tags' sorted
posting lists, and the reference ranks the eligible rows alone: float32
ranking (a gather of the eligible rows where they are few, one BLAS pass
over the column for a block of queries where they are many), float64
rescoring of the best candidates, ties by row. numpy and the standard
library only; nothing of the program is imported.

A query takes its tags from the bag of a row drawn from `--seed` (as the
track took its queries' tags from images'), one or two with equal odds, and
is drawn again until `request.size` rows or more are eligible: the harness
asks every reply for `size` hits, so the rarest pairs are missing.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from perf.data import Mixture, Reference, bulk_bodies as vector_bodies
from perf.data import round_bf16

CONTROL = "filtered reference with the query in bfloat16"
VOCAB = 200_386         # the source's vocabulary
ZIPF = 1.0              # set here: p(word w) ~ 1 / (w + 1) ** ZIPF
BAG_SHAPE = 3.0         # set here: words drawn a row = 1 + NB(shape, mean)
BAG_MEAN_MORE = 10.6    # ~11 distinct words a bag once duplicates are gone
BLOCK_ROWS = 65_536
GATHER_ROWS = 32_768    # up to here the eligible rows are gathered; above,
                        # a block of queries shares one pass over the column
QUERY_BLOCK = 64
SLACK = 22              # float32-ranked candidates kept beyond `size`


def _bags(seed: int, docs: int) -> tuple[np.ndarray, np.ndarray]:
    """(indptr int64[docs + 1], tags int32[nnz]): row i carries
    tags[indptr[i]:indptr[i + 1]], distinct and ascending."""
    law = 1.0 / np.arange(1, VOCAB + 1) ** ZIPF
    cdf = np.cumsum(law / law.sum())
    counts, words = [], []
    for block, lo in enumerate(range(0, docs, BLOCK_ROWS)):
        rng = np.random.default_rng([seed, 5, block])
        drawn = 1 + rng.negative_binomial(
            BAG_SHAPE, BAG_SHAPE / (BAG_SHAPE + BAG_MEAN_MORE), BLOCK_ROWS)
        word = np.minimum(np.searchsorted(cdf, rng.random(int(drawn.sum()))),
                          VOCAB - 1)
        # distinct words of each row, ascending: unique (row, word) keys
        keys = np.unique(np.repeat(np.arange(BLOCK_ROWS), drawn) * VOCAB + word)
        keys = keys[: np.searchsorted(keys, min(BLOCK_ROWS, docs - lo) * VOCAB)]
        counts.append(np.bincount(keys // VOCAB,
                                  minlength=min(BLOCK_ROWS, docs - lo)))
        words.append((keys % VOCAB).astype(np.int32))
    indptr = np.zeros(docs + 1, np.int64)
    np.cumsum(np.concatenate(counts), out=indptr[1:])
    return indptr, np.concatenate(words)


class Postings:
    """tag -> its rows, ascending (the bags turned over)."""

    def __init__(self, indptr: np.ndarray, tags: np.ndarray):
        order = np.argsort(tags, kind="stable")
        rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                         np.diff(indptr))
        self.rows = rows[order]
        self.ptr = np.zeros(VOCAB + 1, np.int64)
        np.cumsum(np.bincount(tags, minlength=VOCAB), out=self.ptr[1:])

    def of(self, tag: int) -> np.ndarray:
        return self.rows[self.ptr[tag]:self.ptr[tag + 1]]

    def carry(self, rows: np.ndarray, tag: int) -> np.ndarray:
        """bool[len(rows)]: which of `rows` carry `tag`."""
        mine = self.of(tag)
        if len(mine) == 0:
            return np.zeros(len(rows), bool)
        at = np.minimum(np.searchsorted(mine, rows), len(mine) - 1)
        return mine[at] == rows

    def eligible(self, tags: tuple) -> np.ndarray:
        """The rows that carry every tag, ascending: the shortest list cut
        down by a binary search in each other one."""
        shortest, *others = sorted(
            tags, key=lambda t: self.ptr[t + 1] - self.ptr[t])
        rows = self.of(shortest)
        for t in others:
            rows = rows[self.carry(rows, t)]
        return rows


def dataset(conf: dict, docs: int, home: Path, fresh: bool) -> dict:
    """The vectors (`corpus.npy`, uint8 as the source stores them, handed on
    as float32), the bags (`indptr.npy`, `tags.npy`) and their postings (made
    anew: two seconds), from `corpus_seed`; row i the same at any `docs`."""
    kept = {name: home / f"{name}.npy"
            for name in ("corpus", "indptr", "tags")}
    if not fresh and all(p.is_file() for p in kept.values()):
        data = {name: np.load(p) for name, p in kept.items()}
    else:
        indptr, tags = _bags(conf["corpus_seed"], docs)
        data = {"corpus": Mixture(conf["corpus_seed"], conf["dims"])
                .corpus(docs).astype(np.uint8),
                "indptr": indptr, "tags": tags}
        for name, p in kept.items():
            np.save(p, data[name])
    data["corpus"] = data["corpus"].astype(np.float32)
    data["postings"] = Postings(data["indptr"], data["tags"])
    return data


def bulk_bodies(conf: dict, data: dict):
    """`perf/data.py`'s vector lines, each with its row's tags added:
    `{"<field>":[...],"<tag_field>":["t3","t17"]}`."""
    indptr, tags = data["indptr"], data["tags"]
    tail = b',"%s":[' % conf["tag_field"].encode()
    for lo, n, body in vector_bodies(data["corpus"], conf["field"],
                                     conf["bulk_docs_per_request"]):
        lines = body.split(b"\n")
        for i in range(n):
            bag = tags[indptr[lo + i]:indptr[lo + i + 1]].tolist()
            lines[2 * i + 1] = (lines[2 * i + 1][:-1] + tail + b",".join(
                b'"t%d"' % t for t in bag) + b"]}")
        yield lo, n, b"\n".join(lines)


class Queries:
    """A sequence of (vector, tags), and how many rows each leaves eligible."""

    def __init__(self, vectors: np.ndarray, tags: list, eligible: np.ndarray):
        self.vectors, self.tags, self.eligible = vectors, tags, eligible

    def __len__(self) -> int:
        return len(self.tags)

    def __getitem__(self, i: int):
        return self.vectors[i], self.tags[i]


def queries(conf: dict, data: dict, seed: int, stream: int, n: int) -> Queries:
    vectors = Mixture(conf["corpus_seed"], conf["dims"]).queries(
        seed, stream, n)
    rng = np.random.default_rng([conf["corpus_seed"], 6, seed, stream])
    indptr, bags, postings = data["indptr"], data["tags"], data["postings"]
    docs, size = len(indptr) - 1, conf["request"]["size"]
    tags, eligible = [], []
    while len(tags) < n:
        # one tag or two, decided first, so that the rows drawn again for
        # want of eligible rows do not turn pairs into singles
        want = int(rng.integers(1, 3))
        count = 0
        while count < size:
            row = int(rng.integers(docs))
            bag = bags[indptr[row]:indptr[row + 1]]
            if len(bag) < want:
                continue
            mine = tuple(sorted(int(t) for t in rng.choice(
                bag, want, replace=False)))
            count = len(postings.eligible(mine))
        tags.append(mine)
        eligible.append(count)
    return Queries(vectors, tags, np.asarray(eligible, np.int64))


def request(conf: dict, query) -> bytes:
    vector, tags = query
    return json.dumps({"size": conf["request"]["size"], "query": {"knn": {
        conf["field"]: {
            "vector": [float(x) for x in vector], **conf["request"]["knn"],
            "filter": {"bool": {"filter": [
                {"term": {conf["tag_field"]: f"t{t}"}} for t in tags]}}}}}}
    ).encode()


class FilteredBruteForce:
    """l2 brute force over the rows that carry all of a query's tags;
    OpenSearch's l2 score of a hit is 1 / (1 + d2), in float64 from the
    float64 distance."""

    def __init__(self, data: dict):
        self.corpus = data["corpus"]
        self.postings = data["postings"]
        self.exact = Reference(self.corpus)     # .d2 (float64), .norms
        self.norms = self.exact.norms.astype(np.float32)
        self.docs = len(self.corpus)    # known ids are 0 .. docs - 1

    def _ranked(self, queries: Queries, vectors: np.ndarray, keep: int,
                gate=None):
        """Yields (query number, the `keep` eligible rows nearest to
        `vectors[i]` by one float32 pass, their |c|^2 - 2 q.c), in any
        order. Few eligible rows are gathered; many share one pass over the
        whole column with the other such queries of their block."""
        many = [i for i in range(len(queries))
                if queries.eligible[i] > GATHER_ROWS]
        few = [i for i in range(len(queries))
               if queries.eligible[i] <= GATHER_ROWS]

        def best(i, rows, part):
            part = part + self.norms[rows]
            if len(rows) > keep:
                cut = np.argpartition(part, keep - 1)[:keep]
                rows, part = rows[cut], part[cut]
            return i, rows, part

        for i in few:
            if gate is not None:
                gate.wait()
            rows = self.postings.eligible(queries.tags[i])
            yield best(i, rows, self.corpus[rows] @ (-2.0 * vectors[i]))
        for lo in range(0, len(many), QUERY_BLOCK):
            if gate is not None:
                gate.wait()
            block = many[lo:lo + QUERY_BLOCK]
            parts = (-2.0 * vectors[block]) @ self.corpus.T
            for j, i in enumerate(block):
                if gate is not None:
                    gate.wait()
                rows = self.postings.eligible(queries.tags[i])
                yield best(i, rows, parts[j, rows])

    def topk(self, queries: Queries, size: int, gate=None):
        """(ids [Q, size], reference scores [Q, size]), best first, ties by
        row; `gate` is waited on so that the timed path keeps the cores."""
        ids = np.empty((len(queries), size), np.int64)
        scores = np.empty((len(queries), size), np.float64)
        for i, rows, _part in self._ranked(queries, queries.vectors,
                                           size + SLACK, gate):
            d2 = self.exact.d2(queries.vectors[i], rows)
            order = np.lexsort((rows, d2))[:size]
            ids[i], scores[i] = rows[order], 1.0 / (1.0 + d2[order])
        return ids, scores

    def scores(self, query, ids: np.ndarray) -> np.ndarray:
        """The reference's scores of any served documents of one query."""
        return 1.0 / (1.0 + self.exact.d2(query[0], ids))

    def further(self, queries: Queries, served: list) -> dict:
        """`filter_violations`: served documents that lack one of their
        query's tags. `eligible_rows_mean`: the mean number of rows the
        served queries' filters leave eligible (what a filtered scan has to
        read at least: `perf/layers/filtered_scan_roofline.py`)."""
        violations = sum(
            int((~self.postings.carry(ids, t)).sum())
            for q, ids, _scores in served for t in queries.tags[q])
        return {"filter_violations": violations,
                "eligible_rows_mean": float(np.mean(
                    [queries.eligible[q] for q, _ids, _scores in served]))
                if served else 0.0}

    def control(self, queries: Queries, size: int):
        """(ids, scores): the eligible rows ranked and scored by one
        bfloat16-query pass accumulated in float32 (the rows are integers
        below 256: exact in bfloat16)."""
        ids = np.empty((len(queries), size), np.int64)
        scores = np.empty((len(queries), size), np.float64)
        for i, rows, part in self._ranked(queries, round_bf16(queries.vectors),
                                          size):
            approx = (part + queries.vectors[i] @ queries.vectors[i]
                      ).astype(np.float32)
            order = np.lexsort((rows, approx))[:size]
            ids[i] = rows[order]
            scores[i] = 1.0 / (1.0 + np.maximum(approx[order], 0.0))
        return ids, scores


def reference(conf: dict, data: dict) -> FilteredBruteForce:
    return FilteredBruteForce(data)
