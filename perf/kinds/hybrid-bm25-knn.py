"""Hybrid lexical + vector search at the shapes of BEIR's NQ served as
OpenSearch documents it: every document is a passage (a `text` field, 78.9
words on average) and its 768-d embedding (`knn_vector`, l2); every request
is one `hybrid` query of two sub-queries, a `match` on the passage text
(operator `or`, BM25 k1 = 1.2, b = 0.75) and a `knn` with k = 10 on the
vector, with a temporary search pipeline in the request body whose
`normalization-processor` normalises each sub-query's top 10 by `min_max`
and combines them by `arithmetic_mean` with weights [0.3, 0.7]. The six
things of `perf/README.md` "A kind of deployment".

What is the source's and what is set here (no network: NQ's passages and a
real encoder's embeddings are not on this machine): the shapes above are
the source's. Set here, each law under `assumed` in the configuration:
words are tokens `w<rank>` drawn from a Zipf-Mandelbrot law over `VOCAB`
ranks, p(rank r) ~ 1 / (r + 1 + `ZM_SHIFT`) ** `ZM_EXPONENT`; a passage
holds `LEN_MIN` + NegativeBinomial(`LEN_SHAPE`, mean 78.9 - `LEN_MIN`)
words; rows come in fixed blocks keyed by (`corpus_seed`, block), so row i
is the same at any `docs`; vectors are `perf/data.py`'s clustered mixture
at 768 dimensions, integers 0-255. A question takes a target passage from
`--seed`: 3 + Poisson(6.2) words (20 at most; mean 9.2), of which
1 + Binomial(words - 2, 0.4) are content words of the target (its distinct
words beyond the law's first `HEAD` ranks, drawn without replacement), one
is one of the `TOP` commonest words (nearly every English question holds
"the", "of", "in", "is" or a question word) and the rest are function words
from the law's first `HEAD` ranks; its vector is the target's plus N(0,
`QUERY_SIGMA`) a coordinate, not rounded: lexical and vector evidence agree,
as they do for a real question. The first 18 warm-up questions (stream 1)
are cut to `WARM_FIRST` words, every count from 3 to 20 once, so that every
term count the window can send has been served before it opens.

The reference is plain numpy and shares no code with the program: BM25 over
this module's own postings in float64 (Lucene's idf and tf norm with exact
document lengths, the program's stated departure from Lucene's one-byte
norms), exact l2 over all rows (float32 ranking, float64 rescoring),
upstream's min-max and arithmetic mean, ties by row.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from perf.data import Mixture, Reference, bulk_bodies as vector_bodies
from perf.data import round_bf16

CONTROL = ("hybrid reference with the query vector in bfloat16 and the BM25 "
           "contributions accumulated in bfloat16")
VOCAB = 100_000         # set here: ranks of the word law
ZM_EXPONENT = 1.07      # set here: Zipf-Mandelbrot, English-like
ZM_SHIFT = 2.7
LEN_MIN = 10            # set here: words a passage = LEN_MIN + NB(shape, mean)
LEN_SHAPE = 3.0
LEN_MEAN = 78.9         # the source's mean passage length
HEAD = 64               # ranks below this are function words
TOP = 8                 # every question holds one of these commonest words
QUESTION_MIN, QUESTION_MAX = 3, 20
QUESTION_MEAN_MORE = 6.2    # 3 + Poisson(6.2): the source's mean of 9.2
CONTENT_SHARE = 0.4
# the warm-up stream's first questions, by words: every count from 3 to 20
# once. The harness sends the first alone, then bursts of 2, 3, 4 and 8 at
# once: in this order a burst meets at most two launch shapes nobody has
# compiled, whether the program compiles one a term count or (PR 37) one a
# multiple of four, and the burst of 8 meets none
WARM_FIRST = (10, 7, 14, 4, 18, 3, 5, 6, 8, 9, 11, 12, 13, 15, 16, 17, 19, 20)
QUERY_SIGMA = 3.0       # the mixture's own noise a coordinate
BLOCK_ROWS = 65_536
K1, B = 1.2, 0.75       # OpenSearch's default BM25 similarity
FLOOR = 0.001           # upstream's MIN_SCORE of min-max normalisation
# `pool_violations` counts a served document as inside a pool when its
# sub-score reaches the pool's lowest within this relative tolerance: the
# program ranks in float32, and two documents closer than its rounding may
# change places at a pool's edge. BM25 sums some ten float32 products (~1e-6); the l2 score comes
# from |c|^2 - 2 q.c + |q|^2 in float32, a difference of numbers a
# hundred times its size (~3e-5 at 768 dimensions)
TIE = {"lexical": 1e-4, "knn": 2e-3}


def law() -> np.ndarray:
    """p(rank) of the word law, float64 [VOCAB]."""
    p = 1.0 / (np.arange(1, VOCAB + 1) + ZM_SHIFT) ** ZM_EXPONENT
    return p / p.sum()


def _passages(seed: int, docs: int) -> tuple[np.ndarray, np.ndarray]:
    """(ptr int64[docs + 1], tokens int32[words]): row i reads
    tokens[ptr[i]:ptr[i + 1]], in the order its words were drawn."""
    cdf = np.cumsum(law())
    lengths, words = [], []
    for block, lo in enumerate(range(0, docs, BLOCK_ROWS)):
        rng = np.random.default_rng([seed, 7, block])
        n = LEN_MIN + rng.negative_binomial(
            LEN_SHAPE, LEN_SHAPE / (LEN_SHAPE + LEN_MEAN - LEN_MIN),
            BLOCK_ROWS)
        drawn = np.minimum(np.searchsorted(cdf, rng.random(int(n.sum()))),
                           VOCAB - 1).astype(np.int32)
        rows = min(BLOCK_ROWS, docs - lo)
        lengths.append(n[:rows])
        words.append(drawn[: int(n[:rows].sum())])
    ptr = np.zeros(docs + 1, np.int64)
    np.cumsum(np.concatenate(lengths), out=ptr[1:])
    return ptr, np.concatenate(words)


class Postings:
    """word -> (its rows ascending, the word's count in each), and what BM25
    needs of a row: `weight[j]` = tf / (tf + k1 (1 - b + b dl / avgdl)) of
    posting j in float64, so that a term's contribution is idf x weight."""

    def __init__(self, ptr: np.ndarray, tokens: np.ndarray):
        docs = len(ptr) - 1
        self.docs = docs
        self.doc_len = np.diff(ptr).astype(np.float64)
        self.avgdl = float(self.doc_len.sum() / docs)
        rows = np.repeat(np.arange(docs, dtype=np.int64), np.diff(ptr))
        keys, tf = np.unique(tokens.astype(np.int64) * docs + rows,
                             return_counts=True)
        self.rows = (keys % docs).astype(np.int32)
        self.tf = tf.astype(np.float64)
        self.ptr = np.zeros(VOCAB + 1, np.int64)
        np.cumsum(np.bincount(keys // docs, minlength=VOCAB),
                  out=self.ptr[1:])
        self.weight = self.tf / (self.tf + K1 * (
            1.0 - B + B * self.doc_len[self.rows] / self.avgdl))
        df = np.diff(self.ptr).astype(np.float64)
        self.idf = np.log(1.0 + (docs - df + 0.5) / (df + 0.5))
        # the function words' contributions as dense columns: nearly every
        # question holds some, and their lists run to most of the rows
        self.head = np.zeros((HEAD, docs))
        for word in range(HEAD):
            sl = self.span(word)
            self.head[word, self.rows[sl]] = self.idf[word] * self.weight[sl]

    def span(self, word: int) -> slice:
        return slice(self.ptr[word], self.ptr[word + 1])

    def weight_of(self, word: int, ids: np.ndarray) -> np.ndarray:
        """The posting weight of `word` in each of `ids`; 0 where absent."""
        sl = self.span(word)
        mine = self.rows[sl]
        if len(mine) == 0:
            return np.zeros(len(ids))
        at = np.minimum(np.searchsorted(mine, ids), len(mine) - 1)
        return np.where(mine[at] == ids, self.weight[sl][at], 0.0)


def dataset(conf: dict, docs: int, home: Path, fresh: bool) -> dict:
    """The vectors (`corpus.npy`, uint8, handed on as float32), the passages
    (`ptr.npy`, `tokens.npy`) and their postings (made anew), from
    `corpus_seed`; row i the same at any `docs`."""
    kept = {name: home / f"{name}.npy" for name in ("corpus", "ptr", "tokens")}
    if not fresh and all(p.is_file() for p in kept.values()):
        data = {name: np.load(p) for name, p in kept.items()}
    else:
        ptr, tokens = _passages(conf["corpus_seed"], docs)
        data = {"corpus": Mixture(conf["corpus_seed"], conf["dims"])
                .corpus(docs).astype(np.uint8),
                "ptr": ptr, "tokens": tokens}
        for name, p in kept.items():
            np.save(p, data[name])
    data["corpus"] = data["corpus"].astype(np.float32)
    data["postings"] = Postings(data["ptr"], data["tokens"])
    return data


def text_of(words) -> str:
    return " ".join(f"w{w}" for w in words)


def bulk_bodies(conf: dict, data: dict):
    """`perf/data.py`'s vector lines, each with its passage added:
    `{"<field>":[...],"<text_field>":"w3 w17 w3"}`."""
    ptr, tokens = data["ptr"], data["tokens"]
    tail = b',"%s":"' % conf["text_field"].encode()
    word = [b"w%d" % w for w in range(VOCAB)]
    for lo, n, body in vector_bodies(data["corpus"], conf["field"],
                                     conf["bulk_docs_per_request"]):
        lines = body.split(b"\n")
        for i in range(n):
            passage = tokens[ptr[lo + i]:ptr[lo + i + 1]].tolist()
            lines[2 * i + 1] = (lines[2 * i + 1][:-1] + tail + b" ".join(
                word[w] for w in passage) + b'"}')
        yield lo, n, b"\n".join(lines)


class Questions:
    """A sequence of (vector, words, number); `targets` are the rows the
    questions were made from."""

    def __init__(self, vectors: np.ndarray, words: list, targets: np.ndarray):
        self.vectors, self.words, self.targets = vectors, words, targets

    def __len__(self) -> int:
        return len(self.words)

    def __getitem__(self, i: int):
        return self.vectors[i], self.words[i], i


def queries(conf: dict, data: dict, seed: int, stream: int,
            n: int) -> Questions:
    rng = np.random.default_rng([conf["corpus_seed"], 8, seed, stream])
    ptr, tokens, corpus = data["ptr"], data["tokens"], data["corpus"]
    head = law()[:HEAD]
    head_cdf = np.cumsum(head / head.sum())
    top_cdf = np.cumsum(head[:TOP] / head[:TOP].sum())
    targets = rng.integers(len(ptr) - 1, size=n)
    lengths = np.minimum(QUESTION_MIN + rng.poisson(QUESTION_MEAN_MORE, n),
                         QUESTION_MAX)
    if stream == 1:
        # the warm-up serves every term count before the window opens
        lengths[:len(WARM_FIRST)] = WARM_FIRST[:n]
    words = []
    for target, length in zip(targets.tolist(), lengths.tolist()):
        passage = np.unique(tokens[ptr[target]:ptr[target + 1]])
        content = passage[passage >= HEAD]
        take = min(1 + int(rng.binomial(length - 2, CONTENT_SHARE)),
                   len(content))
        words.append(np.concatenate([
            rng.choice(content, take, replace=False),
            np.minimum(np.searchsorted(top_cdf, rng.random(1)), TOP - 1),
            np.minimum(np.searchsorted(
                head_cdf, rng.random(length - 1 - take)), HEAD - 1),
        ]).astype(np.int32))
    noise = rng.normal(0.0, QUERY_SIGMA, (n, corpus.shape[1]))
    vectors = np.clip(corpus[targets] + noise, 0.0, 255.0).astype(np.float32)
    return Questions(vectors, words, targets)


def request(conf: dict, query) -> bytes:
    vector, words, _number = query
    req = conf["request"]
    return json.dumps({
        "size": req["size"],
        "_source": {"excludes": [conf["field"]]},
        "query": {"hybrid": {"queries": [
            {"match": {conf["text_field"]: {"query": text_of(words),
                                            **req["match"]}}},
            {"knn": {conf["field"]: {"vector": [float(x) for x in vector],
                                     **req["knn"]}}}]}},
        "search_pipeline": req["search_pipeline"]}).encode()


def min_max(pool_hi: float, pool_lo: float, score) -> np.ndarray:
    """Upstream's min-max of `score` against a pool's best and lowest: the
    lowest maps to the `FLOOR`, a one-point range to 1.0."""
    score = np.asarray(score, np.float64)
    if pool_hi <= pool_lo:
        return np.ones_like(score)
    return np.maximum((score - pool_lo) / (pool_hi - pool_lo), FLOOR)


class HybridBruteForce:
    """Per question: BM25 over all rows -> its top `size`; exact l2 over
    all rows, score 1 / (1 + d2) -> its top `size`; each pool min-max
    normalised, combined by the weighted arithmetic mean with an absent
    sub-score as 0; the fused top `size`, ties by row."""

    def __init__(self, conf: dict, data: dict):
        self.postings = data["postings"]
        self.exact = Reference(data["corpus"])
        self.docs = len(data["corpus"])     # known ids are 0 .. docs - 1
        combination = conf["request"]["search_pipeline"][
            "phase_results_processors"][0]["normalization-processor"][
            "combination"]
        self.weights = np.asarray(combination["parameters"]["weights"])
        self.pool = conf["request"]["size"]
        # question number -> (lexical hi, lo, knn hi, lo) of the sound
        # reference's pools: what `scores` normalises a served id against
        self.stats: dict[int, tuple] = {}

    # -- the two sub-queries -----------------------------------------------

    def bm25(self, words, bf16: bool = False) -> np.ndarray:
        """BM25 scores of every row, float64; each occurrence of a word in
        the question is a clause of its own, as Lucene's `match` makes it.
        `bf16`: contributions and the running sum rounded to bfloat16."""
        p = self.postings
        if bf16:
            acc = np.zeros(p.docs, np.float32)
            for w in words:
                sl = p.span(int(w))
                rows = p.rows[sl]
                acc[rows] = round_bf16(acc[rows] + round_bf16(
                    (p.idf[w] * p.weight[sl]).astype(np.float32)))
            return acc.astype(np.float64)
        acc = np.zeros(p.docs, np.float64)
        for w in words:
            if w < HEAD:
                acc += p.head[w]
            else:
                sl = p.span(int(w))
                acc[p.rows[sl]] += p.idf[w] * p.weight[sl]
        return acc

    def bm25_of(self, words, ids: np.ndarray) -> np.ndarray:
        return sum(self.postings.idf[w] * self.postings.weight_of(int(w), ids)
                   for w in words)

    @staticmethod
    def _best(scores: np.ndarray, k: int):
        """(rows, scores) of the k best positive scores, ties by row."""
        cand = np.argpartition(scores, max(len(scores) - 4 * k, 0))[-4 * k:]
        cand = cand[scores[cand] > 0]
        order = np.lexsort((cand, -scores[cand]))[:k]
        return cand[order], scores[cand[order]]

    def _fuse(self, lex_ids, lex_scores, knn_ids, knn_scores, size: int):
        """The fused top `size` of two pools, as (ids, scores)."""
        fused: dict[int, float] = {}
        for weight, ids, scores in ((self.weights[0], lex_ids, lex_scores),
                                    (self.weights[1], knn_ids, knn_scores)):
            if len(ids):
                normed = min_max(scores[0], scores[-1], scores)
                for i, s in zip(ids.tolist(), normed.tolist()):
                    fused[i] = fused.get(i, 0.0) + weight * s
        total = float(self.weights.sum())
        ids = np.fromiter(fused, np.int64, len(fused))
        scores = np.fromiter(fused.values(), np.float64, len(fused)) / total
        order = np.lexsort((ids, -scores))[:size]
        return ids[order], scores[order]

    def topk(self, queries: Questions, size: int, gate=None):
        """(ids [Q, size], fused reference scores [Q, size]), best first;
        `gate` is waited on so that the timed path keeps the cores."""
        knn_ids, knn_d2 = self.exact.topk(queries.vectors, self.pool,
                                          gate=gate)
        ids = np.empty((len(queries), size), np.int64)
        scores = np.empty((len(queries), size), np.float64)
        for q in range(len(queries)):
            if gate is not None:
                gate.wait()
            lex_ids, lex_scores = self._best(self.bm25(queries.words[q]),
                                             self.pool)
            knn_scores = 1.0 / (1.0 + knn_d2[q])
            self.stats[q] = (lex_scores[0], lex_scores[-1],
                             knn_scores[0], knn_scores[-1])
            ids[q], scores[q] = self._fuse(lex_ids, lex_scores, knn_ids[q],
                                           knn_scores, size)
        return ids, scores

    def _raw(self, query, ids: np.ndarray):
        """(BM25, l2) scores of any ids of one question, float64."""
        vector, words, _number = query
        return (self.bm25_of(words, ids),
                1.0 / (1.0 + self.exact.d2(vector, ids)))

    def _normalised(self, query, raw, tie: dict | None = None):
        """(lexical, knn) sub-scores of `_raw`'s pair, min-max normalised
        against the sound reference's pools of that question; 0 where a
        document does not reach a pool's lowest score (within `tie`, where
        given)."""
        lex_hi, lex_lo, knn_hi, knn_lo = self.stats[query[2]]
        tie = tie or {"lexical": 0.0, "knn": 0.0}
        lexical, knn = raw
        return (np.where(lexical >= lex_lo * (1.0 - tie["lexical"]),
                         min_max(lex_hi, lex_lo, lexical), 0.0),
                np.where(knn >= knn_lo * (1.0 - tie["knn"]),
                         min_max(knn_hi, knn_lo, knn), 0.0))

    def _parts(self, query, ids: np.ndarray, tie: dict | None = None):
        return self._normalised(query, self._raw(query, ids), tie)

    def _fused_of(self, parts) -> np.ndarray:
        return ((self.weights[0] * parts[0] + self.weights[1] * parts[1])
                / self.weights.sum())

    def scores(self, query, ids: np.ndarray) -> np.ndarray:
        """The reference's fused scores of any served documents of one
        question (`topk` has been asked for it before). Strict: a document
        that misses a pool by a hair carries nothing of that sub-query, so
        where the program's float32 ranking changes two near-tied documents
        at a pool's edge the gap is the floor's weight, 0.0003 or 0.0007."""
        return self._fused_of(self._parts(query, np.asarray(ids, np.int64)))

    def further(self, queries: Questions, served: list) -> dict:
        """`fused_abs_gap`: the widest ABSOLUTE gap of a served fused score
        to the reference's (fused scores lie in (0, 1]; near the 0.001
        floor a relative gap says little). `pool_violations`: served
        documents that are in neither of the reference's pools, ties
        within `TIE` apart: fusion ranks the sub-queries' own top hits and
        nothing else."""
        gap, outside = 0.0, 0
        for q, ids, scores in served:
            raw = self._raw(queries[q], ids)
            want = self._fused_of(self._normalised(queries[q], raw))
            gap = max(gap, float(np.max(np.abs(scores - want))))
            lexical, knn = self._normalised(queries[q], raw, TIE)
            outside += int(((lexical == 0) & (knn == 0)).sum())
        return {"fused_abs_gap": gap, "pool_violations": outside}

    def control(self, queries: Questions, size: int):
        """(ids, scores): the same fusion over pools made one precision
        down: the l2 pool by `perf/data.py`'s bfloat16-query pass, the BM25
        pool with its contributions accumulated in bfloat16."""
        knn_ids, knn_scores = self.exact.topk_lower_precision(
            queries.vectors, self.pool)
        ids = np.empty((len(queries), size), np.int64)
        scores = np.empty((len(queries), size), np.float64)
        for q in range(len(queries)):
            lex_ids, lex_scores = self._best(
                self.bm25(queries.words[q], bf16=True), self.pool)
            ids[q], scores[q] = self._fuse(lex_ids, lex_scores, knn_ids[q],
                                           knn_scores[q], size)
        return ids, scores


def reference(conf: dict, data: dict) -> HybridBruteForce:
    return HybridBruteForce(conf, data)
