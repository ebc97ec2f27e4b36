"""Long documents split into chunks, each chunk's 768-d embedding a nested
object, searched by a `nested` `knn` with score mode max: OpenSearch's
text-chunking pattern for retrieval-augmented generation over MS MARCO's
documents. Every document is `{"<path>": [{"<leaf>": [768 numbers]}, ...]}`
(one object a chunk, no chunk text); every request is one `nested` query on
`<path>` whose inner query is a `knn` (k = 10) on `<path>.<leaf>`, `size`
10, `_source` without the vectors: the 10 best documents, each scored
1 / (1 + d2) of its nearest chunk (MaxP). The six things of
`perf/README.md` "A kind of deployment".

What is the source's and what is set here (no network: MS MARCO's texts and
an encoder's embeddings are not on this machine): 768 dimensions, l2,
k = 10, 384 tokens a chunk and at most 100 chunks a document are the
source's. Set here, each under `assumed` in the configuration: a document's
length in tokens is LogNormal(log `MEDIAN_TOKENS`, `SIGMA_TOKENS`), its
chunk count ceil(tokens / 384) cut to 1 .. 100 (mean 3.84, 23% of one
chunk); a document's centre is row i of `perf/data.py`'s mixture at 768
dimensions, and each chunk is that centre plus an offset along the
mixture's own latent basis (`CHUNK_LATENT_SIGMA`) and N(0,
`CHUNK_NOISE`) a coordinate, rounded and clipped to 0-255, so a document's
chunks lie near each other and are exact in bfloat16; counts come in
fixed blocks keyed by (`corpus_seed`, block) and chunks `SUB_DOCS`
documents at a time, so document i is the same at any `docs`. A query
takes a target document from `--seed` and one of its chunks, plus N(0,
`QUERY_SIGMA`) a coordinate, not rounded.

The reference is plain numpy and shares no code with the program: every
chunk's l2 distance ranked in float32 (queries in blocks), the nearest
chunk a document by a minimum over its rows, the `CANDIDATES` best
documents rescored in float64 over all their chunks, ties by document id.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from perf.data import BLOCK_ROWS, Mixture, bulk_bodies as vector_bodies
from perf.data import round_bf16

CONTROL = "nested reference with the query vector in bfloat16"
CHUNK_TOKENS = 384          # the source's: fixed_token_length
MAX_CHUNKS = 100            # the source's: max_chunk_limit
MEDIAN_TOKENS = 780.0       # set here: document length law (mean 1,286)
SIGMA_TOKENS = 1.0
CHUNK_LATENT_SIGMA = 3.0    # set here: a chunk's offset from its document
CHUNK_NOISE = 2.0
QUERY_SIGMA = 3.0           # the mixture's own noise a coordinate
SUB_DOCS = 1024             # documents a draw of chunks
QUERY_BLOCK = 64            # queries a float32 ranking pass of the reference
CANDIDATES = 4              # x size documents rescored in float64


def _counts(seed: int, docs: int) -> np.ndarray:
    """Chunks a document, int64 [docs]; document i the same at any docs."""
    out = []
    for block, lo in enumerate(range(0, docs, BLOCK_ROWS)):
        rng = np.random.default_rng([seed, 9, block])
        tokens = rng.lognormal(np.log(MEDIAN_TOKENS), SIGMA_TOKENS,
                               BLOCK_ROWS)
        out.append(np.clip(np.ceil(tokens / CHUNK_TOKENS), 1, MAX_CHUNKS)
                   .astype(np.int64)[: min(BLOCK_ROWS, docs - lo)])
    return np.concatenate(out)


def _chunks(seed: int, dims: int, docs: int) -> np.ndarray:
    """[chunks of the first `docs` documents, dims] uint8: each document's
    chunks, in document order, around the mixture's row of that document;
    drawn `SUB_DOCS` documents at a time, keyed by (seed, sub-block), so
    document i's chunks are the same at any `docs`."""
    whole = -(-docs // SUB_DOCS) * SUB_DOCS
    counts = _counts(seed, whole)
    mix = Mixture(seed, dims)
    centres = mix.corpus(whole)
    out = np.empty((int(counts[:docs].sum()), dims), np.uint8)
    at = 0
    for sub, lo in enumerate(range(0, docs, SUB_DOCS)):
        rng = np.random.default_rng([seed, 10, sub])
        mine = counts[lo:lo + SUB_DOCS]
        owner = np.repeat(np.arange(lo, lo + SUB_DOCS), mine)
        z = rng.standard_normal((len(owner), mix.basis.shape[0]))
        x = (centres[owner] + (z * CHUNK_LATENT_SIGMA) @ mix.basis
             + rng.normal(0.0, CHUNK_NOISE, (len(owner), dims)))
        take = int(mine[:docs - lo].sum())
        out[at:at + take] = np.clip(np.rint(x[:take]), 0, 255)
        at += take
    return out


def dataset(conf: dict, docs: int, home: Path, fresh: bool) -> dict:
    """The chunk vectors (`chunks.npy`, uint8, handed on as float32) and
    each document's first chunk (`ptr`, made anew from the counts), from
    `corpus_seed`; document i the same at any `docs`."""
    kept = {name: home / f"{name}.npy" for name in ("chunks", "counts")}
    if not fresh and all(p.is_file() for p in kept.values()):
        data = {name: np.load(p) for name, p in kept.items()}
    else:
        data = {"counts": _counts(conf["corpus_seed"], docs),
                "chunks": _chunks(conf["corpus_seed"], conf["dims"], docs)}
        for name, p in kept.items():
            np.save(p, data[name])
    data["chunks"] = data["chunks"].astype(np.float32)
    data["ptr"] = np.zeros(docs + 1, np.int64)
    np.cumsum(data["counts"], out=data["ptr"][1:])
    return data


def bulk_bodies(conf: dict, data: dict):
    """`{"<path>":[{"<leaf>":[...]},...]}` a document, `_id` its row: the
    chunk objects are `perf/data.py`'s vector lines of the chunk rows."""
    path, leaf = conf["field"].split(".")
    ptr, chunks = data["ptr"], data["chunks"]
    per = conf["bulk_docs_per_request"]
    head = b'{"%s":[' % path.encode()
    for lo in range(0, len(ptr) - 1, per):
        hi = min(lo + per, len(ptr) - 1)
        rows = chunks[ptr[lo]:ptr[hi]]
        _, _, body = next(vector_bodies(rows, leaf, len(rows)))
        objects = body.split(b"\n")[1::2]
        lines = []
        for doc in range(lo, hi):
            lines.append(b'{"index":{"_id":"%d"}}' % doc)
            lines.append(head + b",".join(
                objects[ptr[doc] - ptr[lo]:ptr[doc + 1] - ptr[lo]]) + b"]}")
        yield lo, hi - lo, b"\n".join(lines) + b"\n"


class Queries:
    """A sequence of (vector, number); `targets` are the documents the
    queries were drawn from."""

    def __init__(self, vectors: np.ndarray, targets: np.ndarray):
        self.vectors, self.targets = vectors, targets

    def __len__(self) -> int:
        return len(self.vectors)

    def __getitem__(self, i: int):
        return self.vectors[i], i


def queries(conf: dict, data: dict, seed: int, stream: int,
            n: int) -> Queries:
    rng = np.random.default_rng([conf["corpus_seed"], 11, seed, stream])
    ptr, chunks = data["ptr"], data["chunks"]
    targets = rng.integers(len(ptr) - 1, size=n)
    rows = ptr[targets] + rng.integers(0, np.diff(ptr)[targets])
    noise = rng.normal(0.0, QUERY_SIGMA, (n, chunks.shape[1]))
    return Queries(np.clip(chunks[rows] + noise, 0.0, 255.0)
                   .astype(np.float32), targets)


def request(conf: dict, query) -> bytes:
    vector, _number = query
    req = conf["request"]
    path = conf["field"].split(".")[0]
    return json.dumps({
        "size": req["size"],
        "_source": {"excludes": [path]},
        "query": {"nested": {
            "path": path, "score_mode": req["score_mode"],
            "query": {"knn": {conf["field"]: {
                "vector": [float(x) for x in vector], **req["knn"]}}}}},
    }).encode()


class NestedBruteForce:
    """Per query: every chunk's l2 distance, a document's nearest chunk,
    the documents by it (score 1 / (1 + d2)), ties by document id."""

    def __init__(self, data: dict):
        self.chunks, self.ptr = data["chunks"], data["ptr"]
        self.docs = len(self.ptr) - 1     # known ids are 0 .. docs - 1
        self.norms = np.einsum("nd,nd->n", self.chunks, self.chunks,
                               dtype=np.float64).astype(np.float32)

    def _nearest(self, q: np.ndarray, scale=None) -> np.ndarray:
        """[len(q), docs] float32: each document's least ranking distance
        (|c|^2 - 2 q.c, one float32 pass; `scale` rounds the query)."""
        q = q if scale is None else scale(q)
        part = self.chunks @ (-2.0 * q.T)
        part += self.norms[:, None]
        return np.minimum.reduceat(part, self.ptr[:-1], axis=0).T

    def d2(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Exact float64 squared distance of each of `ids` (documents) to
        its nearest chunk."""
        out = np.empty(len(ids))
        for j, doc in enumerate(np.asarray(ids).tolist()):
            rows = self.chunks[self.ptr[doc]:self.ptr[doc + 1]]
            diff = rows.astype(np.float64) - q.astype(np.float64)
            out[j] = np.einsum("nd,nd->n", diff, diff).min()
        return out

    def topk(self, queries: Queries, size: int, gate=None):
        """(ids [Q, size], reference scores [Q, size]), best first; `gate`
        is waited on so that the timed path keeps the cores."""
        keep = min(CANDIDATES * size, self.docs)
        ids = np.empty((len(queries), size), np.int64)
        scores = np.empty((len(queries), size), np.float64)
        for lo in range(0, len(queries), QUERY_BLOCK):
            if gate is not None:
                gate.wait()
            q = queries.vectors[lo:lo + QUERY_BLOCK]
            near = self._nearest(q)
            cand = np.argpartition(near, keep - 1, axis=1)[:, :keep]
            for i in range(len(q)):
                exact = self.d2(q[i], cand[i])
                order = np.lexsort((cand[i], exact))[:size]
                ids[lo + i] = cand[i][order]
                scores[lo + i] = 1.0 / (1.0 + exact[order])
        return ids, scores

    def scores(self, query, ids: np.ndarray) -> np.ndarray:
        """The reference's scores of any served documents of one query."""
        vector, _number = query
        return 1.0 / (1.0 + self.d2(vector, ids))

    def further(self, queries: Queries, served: list) -> dict:
        """No number beyond the harness's own."""
        return {}

    def control(self, queries: Queries, size: int):
        """(ids, scores) of the same search one precision down: the query
        rounded to bfloat16 (the chunks are integers below 256, exact in
        it), ranked and scored by one float32 pass alone."""
        ids = np.empty((len(queries), size), np.int64)
        scores = np.empty((len(queries), size), np.float64)
        for lo in range(0, len(queries), QUERY_BLOCK):
            q = queries.vectors[lo:lo + QUERY_BLOCK]
            near = self._nearest(q, round_bf16)
            near += np.einsum("bd,bd->b", q, q)[:, None]
            part = np.argpartition(near, size - 1, axis=1)[:, :size]
            vals = np.take_along_axis(near, part, axis=1)
            order = np.argsort(vals, axis=1, kind="stable")
            ids[lo:lo + len(q)] = np.take_along_axis(part, order, axis=1)
            scores[lo:lo + len(q)] = 1.0 / (1.0 + np.maximum(
                np.take_along_axis(vals, order, axis=1), 0.0))
        return ids, scores


def reference(conf: dict, data: dict) -> NestedBruteForce:
    return NestedBruteForce(data)
