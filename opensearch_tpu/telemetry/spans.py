"""Span names of the served `_search` path: one stable literal each, so a
reader (`perf/hostspans.py`, `perf/hostplanes.py`, a dashboard) can match by
name after a refactor. Vary attributes, never names (the TPU013 rule, applied
to spans).

`http_request`, `search` and `mesh.bundle_build` exist always and feed the
ring, the slowlog's trace ids and the exporter. Every other name here is a
DETAIL span
(`tracing.detail` / `tracing.phases`): it exists only in requests whose root
opened while a `jax.profiler` session was running, and is written to the
profiler's trace and the tracer's capture, never to the ring.
"""

from __future__ import annotations

# HTTP front end (rest/http.py)
HTTP_REQUEST = "http_request"
HTTP_PARSE = "http.parse"
HTTP_POOL_WAIT = "http.pool_wait"
HTTP_RESPOND = "http.respond"

# search service (node.py, search/service.py, search/executor.py).
# `search.collect` (the per-shard kNN path: the launch's row, then the
# winners -> hits) carries `dense`: 1 when the request built an n_pad-wide
# array from its kNN selection, else 0
SEARCH = "search"
SEARCH_PARSE = "search.parse"
SEARCH_QUERY_PHASE = "search.query_phase"
SEARCH_COLLECT = "search.collect"
SEARCH_REDUCE = "search.reduce"
SEARCH_FETCH = "search.fetch"
SEARCH_RESPOND = "search.respond"

# dispatch batcher (search/batcher.py)
BATCH_WAIT = "batch.wait"

# mesh program / per-shard ANN (search/distributed_serving.py,
# search/executor.py): `launch.device` runs from the program call to the
# first output's host copy returning, which is the fence. The mesh program
# has one packed output, so that copy is its only one; `launch.fetch` is
# the host copy of the IVF path's second output. `launch` carries `merged`,
# `reason` and, from the mesh program, `devices`, `shards`, `b_pad`,
# `host_copies` (device -> host transfers the launch made: 1); on either
# road it carries `filtered`: 1 when the launch served a filtered query.
# `mesh.bundle_build` (always on) spans the upload of an index's slabs to
# the mesh after a refresh or a recovery: `devices`, `shards`,
# `bytes_per_device`, `staging_bytes` (see `_build_bundle`)
MESH_BUNDLE_BUILD = "mesh.bundle_build"
LAUNCH = "launch"
LAUNCH_HOST_PRE = "launch.host_pre"
LAUNCH_DEVICE = "launch.device"
LAUNCH_FETCH = "launch.fetch"
LAUNCH_HOST_POST = "launch.host_post"

# a filtered kNN request's eligibility (DETAIL; kept beside `ALL`, as
# `mesh.bundle_build` is): everything that turns the request's filter nodes
# into what its launch may return, whatever implements it. Today the filter
# executor over every segment (a keyword clause's mask is made on the host
# from its ordinals' posting lists and uploaded, the clauses composed on the
# device), then on the mesh road the composed mask's copy to the host, its
# upload as the launch's `[S, n_flat]` mask and `valid & mask`, on the
# per-shard road `present & live & mask`. It opens once a filtered request
# (and shard, on the per-shard road) and carries `rows` (the mask's width),
# `eligible` (rows that pass), `clauses`, `postings` (posting entries the
# keyword clauses scattered: work follows these, not the field's pairs; 0
# without a keyword clause), `upload_bytes` (host -> device bytes of the
# launch's mask; a keyword clause's own upload, one byte a row of its
# segment, is not in it)
FILTER_MASK = "filter.mask"

# lexical scoring and hybrid fusion (DETAIL; beside `ALL` as `filter.mask`
# is). `bm25.score` spans one query phase on one shard whose query holds a
# full-text node (`match`, `match_phrase`, `match_phrase_prefix`): from its
# terms' look-ups, through the BM25 launches of every segment, to the
# shard's top-k on the host. It carries `terms` (term rows launched),
# `postings` (the sum of those terms' posting lengths: what a scorer has to
# read at least), `window` (the widest padded gather window, a launch works
# over terms x window elements whatever the lists hold) and `rows` (the
# score columns' width, n_pad summed over the segments). `hybrid.fuse`
# spans `pipeline.fuse_hybrid_results`, the phase-results processor's
# normalisation and combination on the host: `sub_queries`, `pooled` (hits
# of all sub-queries and shards that went in), `shards`.
# `search.query_phase` carries `sub_queries`: a hybrid query's count, 0 for
# any other query
BM25_SCORE = "bm25.score"
HYBRID_FUSE = "hybrid.fuse"

# process
RUNTIME_GC = "runtime.gc"

# the names of a request's own tree: what `perf/hostplanes.py` keeps of the
# host planes (`mesh.bundle_build` is no part of a steady window)
ALL = (
    HTTP_REQUEST, HTTP_PARSE, HTTP_POOL_WAIT, HTTP_RESPOND,
    SEARCH, SEARCH_PARSE, SEARCH_QUERY_PHASE, SEARCH_COLLECT, SEARCH_REDUCE,
    SEARCH_FETCH, SEARCH_RESPOND,
    BATCH_WAIT,
    LAUNCH, LAUNCH_HOST_PRE, LAUNCH_DEVICE, LAUNCH_FETCH, LAUNCH_HOST_POST,
    RUNTIME_GC,
)
