"""Per-shard query execution: query node tree -> device score/mask ops.

The analog of the reference's per-shard query phase
(search/query/QueryPhase.java:96 + ContextIndexSearcher.java:242 and the
QueryBuilder.toQuery compile step): each query node is executed against each
segment's device arrays, producing a dense (scores[n_pad] f32, mask[n_pad]
bool) pair; composition (bool logic) is elementwise on the VPU instead of
Lucene's doc-at-a-time conjunction/disjunction iterators. A kNN node alone
produces its <= k winners as short (docs, scores) arrays (HostNodeResult),
and the dense pair only for a consumer that indexes by document.

Scoring follows Lucene semantics: BM25 with shard-level stats (idf over
summed per-segment doc freqs, avgdl over all segments — matching
IndexSearcher collection statistics), constant 1.0*boost for filter-ish
queries in scoring position, 0.0 scores for filter-only bools.

Sort-by-field runs host-side on the exact int64/float64 host columns (device
computes the match mask; numpy does the argsort) — exact semantics first,
device sort keys are a later optimization. Score sort runs fully on device
ending in lax.top_k.
"""

from __future__ import annotations

import logging
import re
import threading
import time
from dataclasses import dataclass, field as dc_field
from functools import cached_property
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from opensearch_tpu.common.errors import (
    IllegalArgumentException,
    ParsingException,
)
from opensearch_tpu.index.device import DeviceSegment
from opensearch_tpu.index.mapper import (
    FLOAT_TYPES,
    INT_TYPES,
    RANGE_TYPES,
    MapperService,
    parse_date_millis,
)
from opensearch_tpu.index.engine import SearcherSnapshot
from opensearch_tpu.index.segment import (
    HostSegment,
    i64_query_words,
    pad_window,
)
from opensearch_tpu.ops import bm25, filters, knn
from opensearch_tpu.search import profile
from opensearch_tpu.search import query_dsl as q
from opensearch_tpu.telemetry import roofline
from opensearch_tpu.telemetry import spans as span_names
from opensearch_tpu.telemetry import tracing

logger = logging.getLogger(__name__)

I64_MIN = -(2**63)
I64_MAX = 2**63 - 1

# observability: which scan served _exec_KnnQuery selections. Searches run
# on a parallel pool (rest/http.py), so increments go through
# _count_knn_path — a bare `dict[k] += 1` is read-modify-write and drops
# counts under concurrency.
knn_path_stats = {"ann": 0, "fused": 0}
_knn_path_stats_lock = threading.Lock()


def _count_knn_path(kind: str) -> None:
    with _knn_path_stats_lock:
        knn_path_stats[kind] += 1


def _knn_metrics():
    """The EXECUTING node's registry when a request scope is active (the
    batcher's attribution rule), else the attached sink; None without."""
    from opensearch_tpu.search import batcher as batcher_mod

    return tracing.active_metrics() or batcher_mod.default_batcher.metrics


def count_metric(name: str, amount: int = 1) -> None:
    """One of the node's query-phase counters: `knn.collect.dense` /
    `.sparse` (once a request and shard), `knn.filter.postings_builds`,
    `search.bm25.launches` / `.postings` (once a `_bm25` call; the posting
    entries of the terms it looked up), `search.hybrid.requests` (once a
    `hybrid` search, whatever its sub-queries and shards)."""
    metrics = _knn_metrics()
    if metrics is not None:
        metrics.counter(name).add(amount)


def count_knn_filter(requests: int, mask_bytes: int) -> None:
    """`knn.filter.requests` / `.mask_bytes`: filtered kNN queries served
    (once a request on the mesh road, once a request and shard on the
    per-shard road) and the bytes of the eligibility masks built for their
    launches."""
    metrics = _knn_metrics()
    if metrics is not None:
        metrics.counter("knn.filter.requests").add(requests)
        metrics.counter("knn.filter.mask_bytes").add(mask_bytes)


def _keyword_postings(kf):
    """The keyword field with its ordinal-major view built;
    `knn.filter.postings_builds` counts the one build a segment and field,
    so that a build inside a measured window shows."""
    if kf.build_postings():
        count_metric("knn.filter.postings_builds")
    return kf


BM25_TERM_ROWS = 4      # `_bm25` launches term rows in multiples of this

_FULL_TEXT_NODES = (q.MatchQuery, q.MatchPhraseQuery, q.MatchPhrasePrefixQuery)


def _bm25_span(query_node):
    """The `bm25.score` detail span of one shard's query phase where the
    query holds a full-text node; the no-op scope elsewhere, and wherever
    the request is not detailed (the tree is only walked when it is)."""
    scope = tracing.detail(span_names.BM25_SCORE)
    if scope is tracing.NO_DETAIL or any(
            isinstance(n, _FULL_TEXT_NODES)
            for n in q.iter_query_nodes(query_node)):
        return scope
    return tracing.NO_DETAIL


def filter_clauses(node) -> int:
    """Clauses of one filter node: a bool's direct children, else 1 (0 for
    no filter). The `filter.mask` span's `clauses`."""
    if node is None:
        return 0
    if isinstance(node, q.BoolQuery):
        return (len(node.must) + len(node.should) + len(node.filter)
                + len(node.must_not))
    return 1


def _mark_launch_filtered(filtered: bool) -> None:
    """`filtered` on the batcher's `launch` span, from inside its closure."""
    span = tracing.active_tracer().current_span()
    if span is not None and span.name == span_names.LAUNCH:
        span.set_attribute("filtered", int(filtered))


def _pad_query_batch(rows: list) -> np.ndarray:
    """Stack per-request query vectors into a [B_pad, d] batch, B padded to
    the next power of two (zero rows, results sliced off by the caller) so
    merged batch widths share compiled programs instead of retracing per
    distinct concurrency level. The padded batch is a per-launch
    host->device upload: the residency ledger counts it as transient
    (allocated and freed in one step)."""
    from opensearch_tpu.telemetry.device_ledger import (
        KIND_QUERY_BATCH,
        default_ledger,
    )

    b = len(rows)
    b_pad = 1 << (b - 1).bit_length()
    out = np.zeros((b_pad, len(rows[0])), np.float32)
    for i, row in enumerate(rows):
        out[i] = row
    default_ledger.record_transient(KIND_QUERY_BATCH, out.nbytes)
    return out


def _touch_targets(dev, field: str, ann=None) -> list:
    """The ledger allocations a kNN launch over this segment READS — the
    vector column, the live bitmap, and (ANN path) the IVF-PQ slab: the
    launch closures record a heat touch against them with the launch's
    modeled HBM bytes (telemetry/device_ledger.touch; tpulint TPU017)."""
    allocs = getattr(dev, "allocations", None) or {}
    out = [allocs.get(field), allocs.get("_live")]
    if ann is not None:
        out.append(getattr(ann, "allocation", None))
    return [a for a in out if a is not None]


# --------------------------------------------------------------------------
# Shard-level statistics (Lucene collection statistics analog)
# --------------------------------------------------------------------------


class ShardContext:
    def __init__(self, snapshot: SearcherSnapshot, mapper_service: MapperService):
        self.snapshot = snapshot
        self.mapper_service = mapper_service
        # per-query cache: knn nodes select k docs PER SHARD (k-NN plugin
        # semantics), so the top-k cut must span all segments of the shard
        self._knn_cache: dict[int, list] = {}
        # True once a kNN selection of this request was made n_pad wide
        # (HostNodeResult's dense view was touched)
        self.knn_dense = False
        # query_string trees are parsed once per shard, not per segment
        self._qs_cache: dict[int, Any] = {}
        # what this request's BM25 launches on this shard looked up and
        # gathered: the `bm25.score` span's attributes
        self.bm25 = {"terms": 0, "postings": 0, "window": 0, "rows": 0}

    def rewritten_query_string(self, node) -> Any:
        """Parse a query_string/simple_query_string node's text once per
        shard (the two-phase-rewrite analog: QueryStringQueryBuilder rewrites
        to a concrete query before per-segment execution)."""
        cached = self._qs_cache.get(id(node))
        if cached is not None:
            return cached
        from opensearch_tpu.search import query_dsl as qd
        from opensearch_tpu.search.query_string import (
            parse_query_string,
            parse_simple_query_string,
        )

        fields = node.fields or self.default_text_fields()
        if isinstance(node, qd.SimpleQueryStringQuery):
            tree = parse_simple_query_string(node.query, fields, node.default_operator)
        else:
            tree = parse_query_string(node.query, fields, node.default_operator)
        self._qs_cache[id(node)] = tree
        return tree

    def default_text_fields(self) -> list[str]:
        fields = [
            name for name, m in self.mapper_service.mappers.items()
            if m.type in ("text", "keyword")
        ]
        for host, _dev in self.snapshot.segments:
            for name in host.text_fields:
                if name not in fields:
                    fields.append(name)
        return fields or ["_all_absent_"]

    def shard_knn_selection(self, node) -> list:
        """Per segment, the KnnQuery's winners as two short numpy arrays
        (docs int32[w], scores f32[w]), best first, with the top-k cut
        applied across the whole shard (w <= k over all segments together);
        None where the segment has no such field.

        Every exact segment (any size, any k, filter or not) scores through
        ops/pallas_knn.knn_fused; an ANN-indexed segment under an unfiltered
        query through IVF-PQ. Either way only a [1, k_bucket] row comes back
        to the host, and nothing n_pad wide is built from it here."""
        cached = self._knn_cache.get(id(node))
        if cached is not None:
            return cached
        from opensearch_tpu.ops import knn as knn_ops

        # each segment's launch row (vals, ids), None without the field
        seg_rows: list[tuple[np.ndarray, np.ndarray] | None] = []
        valids = self._knn_valid_masks(node)
        for (host, dev), valid in zip(self.snapshot.segments, valids):
            vf = dev.vector_fields.get(node.field)
            if vf is None:
                seg_rows.append(None)
                continue
            # host numpy: the query vector is this path's whole per-request
            # host->device transfer (the profiler counts host-typed args)
            qv = np.asarray([node.vector], np.float32)
            prof = profile.active()
            if vf.ann is not None and node.filter is None:
                # ANN path: IVF-PQ ADC + exact rescore gives candidate-only
                # scores; non-candidates stay -inf (they can never win).
                # Dispatch rides search/batcher.py with a REAL batch key —
                # (kernel "ivfpq", device column, INDEX-BUILD GENERATION,
                # reader generation, k bucket, nprobe bucket, similarity,
                # live precision pair) — so concurrent ANN queries against
                # the same built index coalesce into ONE search_index
                # launch, and a rebuild (fresh build generation) can never
                # merge into an old batch.
                from opensearch_tpu.ops import ivfpq
                from opensearch_tpu.search import ann as ann_mod
                from opensearch_tpu.search import batcher as batcher_mod

                cfg = ann_mod.default_config
                precision = cfg.adc_precision
                mult = cfg.rescore_multiplier
                # the RESOLVED ADC kernel ("pallas" fused scan vs "xla"
                # monolithic lowering) rides the batch key: a policy flip
                # mid-stream starts new batches, it never re-routes one —
                # and a rebuild (fresh build generation) can never merge
                # old-generation queries into the new kernel variant
                kernel = ann_mod.resolve_kernel(cfg.kernel)
                # bucket k AND nprobe to powers of two: both are static jit
                # args, so raw values would compile a fresh program per
                # distinct request shape (the query-shape cache concern,
                # SURVEY.md §7 hard part #3). Extra candidates/probes are
                # harmless — the shard-level cut below still takes exactly
                # node.k, and more probes only add recall.
                nprobe_req = int(
                    (node.method_parameters or {}).get(
                        "nprobe", vf.nprobe_default
                    )
                )
                nprobe = ann_mod.bucket_nprobe(
                    nprobe_req, vf.ann.params.nlist)
                k_req = max(1, min(node.k, host.n_docs))
                k_bucket = 1 << (k_req - 1).bit_length()
                sim = knn_ops.canonical_similarity(vf.similarity)
                gen = self.snapshot.generation

                def ann_key(kb: int):
                    return ("ivfpq", id(vf), vf.ann.build_generation, gen,
                            kb, nprobe, sim, precision, mult, kernel)

                rerank = ivfpq.default_rerank(k_bucket, mult)
                rescore = ivfpq.rescore_pool(vf.ann, k_bucket, nprobe,
                                             rerank)
                # roofline family per kernel variant: the fused Pallas
                # scan has its OWN cost model (no per-slot LUT gather
                # traffic, no [B, nprobe, L_pad] intermediate), so the
                # report can show exactly what the swap bought
                family = ("ivfpq_adc_pallas" if kernel == "pallas"
                          else "ivfpq_search")

                touch_allocs = _touch_targets(dev, node.field, ann=vf.ann)

                def launch_ann(rows):
                    _mark_launch_filtered(False)
                    with profile.profiling(None):
                        with tracing.detail(span_names.LAUNCH_HOST_PRE):
                            q_batch = _pad_query_batch(rows)
                            t0 = time.perf_counter_ns()
                            queries, probes = ivfpq.select_probes(
                                vf.ann, q_batch, nprobe, kernel)
                        with tracing.detail(span_names.LAUNCH_DEVICE):
                            b_vals, b_ids = ivfpq.search_probed(
                                vf.ann, vf.vectors, vf.norms_sq, valid,
                                queries, probes, k=k_bucket, nprobe=nprobe,
                                similarity=vf.similarity,
                                adc_precision=precision,
                                rescore_multiplier=mult,
                            )
                            # host materialization is the fence for this
                            # launch: the first copy doubles as the wait
                            b_vals = np.asarray(b_vals)
                        with tracing.detail(span_names.LAUNCH_FETCH):
                            b_ids = np.asarray(b_ids)
                    wall_ns = time.perf_counter_ns() - t0
                    with tracing.detail(span_names.LAUNCH_HOST_POST):
                        # roofline accounting: one fenced launch against
                        # the variant's cost model, keyed per ADC precision
                        # so the report can compare the lowerings
                        # (ANNS-AMP)
                        launch_params = dict(
                            b=int(q_batch.shape[0]),
                            nlist=vf.ann.params.nlist, d=vf.ann.params.d,
                            m=vf.ann.params.m, ks=vf.ann.params.ks,
                            nprobe=nprobe, l_pad=vf.ann.l_pad,
                            rescore=rescore, adc_precision=precision,
                        )
                        roofline.record_launch(
                            f"{family}[{precision}]", wall_ns,
                            **launch_params,
                        )
                        # heat touch against the structures this launch
                        # READ (IVF-PQ slab + rescore column + live
                        # bitmap), bytes from the same cost model the
                        # roofline fold used
                        from opensearch_tpu.telemetry.device_ledger import (
                            default_ledger,
                        )

                        default_ledger.touch(
                            touch_allocs, family=f"{family}[{precision}]",
                            params=launch_params)
                        retraced = profile.signature_retraced(
                            "ivfpq_search", (vf.vectors, q_batch),
                            (k_bucket, nprobe, precision, mult, kernel))
                        return (
                            [(b_vals[i], b_ids[i])
                             for i in range(len(rows))],
                            retraced,
                        )

                # cross-k coalescing: this request may ride an already-
                # forming batch of the next-larger k buckets (its rows
                # truncate for free); it never creates one
                out = batcher_mod.dispatch(
                    ann_key(k_bucket), qv[0], launch_ann, shards=1,
                    kind="ann", rank=k_bucket,
                    alt_keys=(ann_key(k_bucket * 2), ann_key(k_bucket * 4)),
                    family=family,
                    # generation-free family for the wait auto-tuner: a
                    # rebuild/refresh must not reset the learned window
                    tune_key=("ivfpq", id(self.mapper_service),
                              node.field, k_bucket),
                )
                if prof is not None:
                    prof.record_kernel(
                        family, out.kernel_share_ns,
                        int(qv.nbytes), out.retraced,
                        annotations={
                            "adc_precision": precision,
                            "rescore_candidates": rescore,
                            "nprobe": nprobe,
                            "kernel": kernel,
                        },
                    )
                metrics = _knn_metrics()
                if metrics is not None:
                    metrics.histogram("knn.batch.nprobe").record(nprobe)
                _count_knn_path("ann")
                # per request, after the (shared) launch: its own row
                with tracing.detail(span_names.SEARCH_COLLECT) as collect:
                    seg_rows.append(out.value)
                    collect.set_attribute("dense", int(self.knn_dense))
            else:
                k_req = max(1, min(int(node.k), host.n_docs))
                # k is a static jit arg: bucket to the next power of two so
                # distinct request ks share compiled programs (same concern
                # as the ANN branch above)
                k_bucket = 1 << (k_req - 1).bit_length()
                sim = knn_ops.canonical_similarity(vf.similarity)
                # cross-request micro-batching (search/batcher.py):
                # concurrent filterless queries over this SAME segment
                # column + reader generation coalesce into one padded
                # batch launch. Filtered queries carry a request-private
                # valid mask, so they never merge (key=None -> solo).
                # The key's generation term is the snapshot-safety
                # invariant: a refresh mid-flight is a different key.
                from opensearch_tpu.ops import pallas_knn as pallas_knn_ops
                from opensearch_tpu.search import batcher as batcher_mod
                from opensearch_tpu.search.ann import (
                    default_config as ann_config,
                )

                # EXACT-path kernel policy (search.knn.kernel): what the one
                # rule RESOLVES it to for a k bucket, and the scan
                # precision, ride the batch key, so a live flip starts new
                # batches and never re-ranks an in-flight one
                policy = ann_config.exact_kernel
                score_precision = ann_config.score_precision
                impl, interpret = pallas_knn_ops.fused_impl(policy, k_bucket)

                def fused_key(kb: int):
                    return ("knn_fused", id(vf),
                            self.snapshot.generation, kb, sim,
                            score_precision,
                            *pallas_knn_ops.fused_impl(policy, kb))

                key = fused_key(k_bucket) if node.filter is None else None
                # cross-k coalescing: ride an already-forming batch of the
                # next-larger k buckets (result rows truncate for free)
                alt_keys = (
                    (fused_key(k_bucket * 2), fused_key(k_bucket * 4))
                    if key is not None else ()
                )

                touch_allocs = _touch_targets(dev, node.field)

                def launch_fused(rows):
                    _mark_launch_filtered(node.filter is not None)
                    q_batch = _pad_query_batch(rows)
                    t0 = time.perf_counter_ns()
                    with profile.profiling(None):
                        b_vals, b_ids = pallas_knn_ops.knn_fused(
                            vf.vectors, vf.norms_sq, valid, q_batch,
                            k=k_bucket, similarity=sim,
                            score_precision=score_precision,
                            impl=impl, interpret=interpret,
                        )
                    # host materialization is the fence for this launch
                    b_vals = np.asarray(b_vals)
                    b_ids = np.asarray(b_ids)
                    launch_params = dict(
                        b=int(q_batch.shape[0]),
                        n=int(vf.vectors.shape[0]),
                        d=int(vf.vectors.shape[1]), k=k_bucket,
                        r=pallas_knn_ops.fused_pool_width(
                            k_bucket, score_precision),
                        precision=score_precision,
                    )
                    roofline.record_launch(
                        f"knn_fused_pallas[{score_precision}]",
                        time.perf_counter_ns() - t0,
                        **launch_params,
                    )
                    # heat touch: the column + live bitmap this scan read,
                    # bytes from the same cost model
                    from opensearch_tpu.telemetry.device_ledger import (
                        default_ledger,
                    )

                    default_ledger.touch(
                        touch_allocs, family="knn_fused_pallas",
                        params=launch_params)
                    retraced = profile.signature_retraced(
                        "knn_fused_pallas", (vf.vectors, q_batch),
                        (k_bucket, sim, score_precision, impl, interpret))
                    return (
                        [(b_vals[i], b_ids[i]) for i in range(len(rows))],
                        retraced,
                    )

                # shards=1: this is the per-shard fallback path (the
                # shard-mesh launch in service.py passes its mesh width);
                # the batcher's cross-shard stats stay honest
                out = batcher_mod.dispatch(
                    key, qv[0], launch_fused,
                    shards=1, rank=k_bucket,
                    alt_keys=alt_keys,
                    family="knn_fused_pallas",
                    tune_key=("knn_fused_pallas",
                              id(self.mapper_service), node.field,
                              k_bucket))
                if prof is not None:
                    # a batched operator owns its SHARE of the fenced
                    # kernel wall (merged launches split evenly)
                    prof.record_kernel(
                        "knn_fused_pallas", out.kernel_share_ns,
                        int(qv.nbytes), out.retraced,
                        annotations={
                            "score_precision": score_precision,
                            "kernel": impl,
                        },
                    )
                seg_rows.append(out.value)
                _count_knn_path("fused")
        # a row's finite entries with a document ARE its segment's
        # candidates: min(k, n_docs) or more (the batch leader may have run a
        # larger k bucket), of which the shard cut takes exactly node.k
        candidates: list[tuple[float, int, int]] = []
        for seg_idx, row in enumerate(seg_rows):
            if row is not None:
                vals, ids = row
                keep = (ids >= 0) & np.isfinite(vals)
                candidates.extend(
                    (v, seg_idx, d)
                    for v, d in zip(vals[keep].tolist(), ids[keep].tolist()))
        candidates.sort(key=lambda c: (-c[0], c[1], c[2]))
        winners = candidates[: node.k]
        out = [
            None if row is None else (
                np.array([d for _, si, d in winners if si == i], np.int32),
                np.array([v for v, si, _ in winners if si == i], np.float32))
            for i, row in enumerate(seg_rows)
        ]
        self._knn_cache[id(node)] = out
        return out

    def _knn_valid_masks(self, node) -> list:
        """Per segment, the rows a KnnQuery's launch may return: `present &
        live`, under a filter also the filter executor's mask; None where
        the segment has no such field. A filtered query's masks are made
        under ONE `filter.mask` span, before the first launch."""
        segments = self.snapshot.segments
        valids = [
            None if (vf := dev.vector_fields.get(node.field)) is None
            else vf.present & dev.live for _host, dev in segments]
        if node.filter is None:
            return valids
        with tracing.detail(span_names.FILTER_MASK) as masked:
            postings = 0
            for i, (host, dev) in enumerate(segments):
                if valids[i] is not None:
                    ex = SegmentExecutor(self, host, dev)
                    valids[i] = valids[i] & ex.execute(node.filter).mask
                    postings += ex.postings
            built = [v for v in valids if v is not None]
            if masked.detail is not None:
                masked.set_attribute("rows", sum(int(v.size) for v in built))
                masked.set_attribute("eligible", sum(
                    int(jnp.count_nonzero(v)) for v in built))
                masked.set_attribute("clauses", filter_clauses(node.filter))
                masked.set_attribute("postings", postings)
                # the flat mask is composed on the device: no upload of it
                # (a keyword clause uploads its own mask inside the executor)
                masked.set_attribute("upload_bytes", 0)
        count_knn_filter(1, sum(int(v.nbytes) for v in built))
        return valids

    def mlt_rewrite(self, node) -> Any:
        """MoreLikeThisQuery -> bool-should of term queries, selected by
        TF-IDF over the shard's stats (MoreLikeThisQueryBuilder's term
        selection). Cached per shard."""
        cached = self._qs_cache.get(("mlt", id(node)))
        if cached is not None:
            return cached
        import math

        from opensearch_tpu.search import query_dsl as qd

        fields = node.fields or [
            f for f, m in self.mapper_service.mappers.items()
            if m.type == "text"
        ]
        total_docs = max(self.snapshot.num_docs, 1)

        def shard_doc_freq(field, term):
            return sum(
                host.text_fields[field].doc_freq(term)
                for host, _ in self.snapshot.segments
                if field in host.text_fields
            )

        scored: list[tuple[float, str, str]] = []
        for field in fields:
            tf_counts: dict[str, int] = {}
            for text in node.like_texts:
                for term in self.mapper_service.analyze_query_text(field, text):
                    tf_counts[term] = tf_counts.get(term, 0) + 1
            for term, tf in tf_counts.items():
                if tf < node.min_term_freq:
                    continue
                df = shard_doc_freq(field, term)
                if df < node.min_doc_freq or df == 0:
                    continue  # absent terms can never match this shard
                idf = math.log(1.0 + total_docs / df)
                scored.append((tf * idf, field, term))
        scored.sort(key=lambda s: (-s[0], s[1], s[2]))
        top = scored[: node.max_query_terms]
        should = [
            qd.TermQuery(field=f, value=t, boost=w) for w, f, t in top
        ]
        msm = node.minimum_should_match
        try:
            if isinstance(msm, str) and msm.endswith("%"):
                msm_n = int(len(should) * int(msm[:-1]) / 100)
            else:
                msm_n = int(msm)
        except ValueError:
            raise ParsingException(
                f"unsupported [minimum_should_match] value [{msm}] for "
                "[more_like_this] (use an integer or \"N%\")"
            ) from None
        tree = qd.BoolQuery(
            should=should, minimum_should_match=max(msm_n, 1) if should else None,
            boost=node.boost,
        ) if should else qd.MatchNoneQuery()
        self._qs_cache[("mlt", id(node))] = tree
        return tree

    def percolate_masks(self, node) -> list:
        """Per-segment bool masks for a PercolateQuery: each live doc whose
        stored query (at node.field in _source) matches ANY of the provided
        documents. The documents build one tiny in-memory index; each
        stored query executes against it (the percolator module's memory-
        index approach)."""
        cached = self._qs_cache.get(("perc", id(node)))
        if cached is not None:
            return cached
        import json as _json

        import numpy as np

        from opensearch_tpu.index.device import to_device
        from opensearch_tpu.index.engine import SearcherSnapshot
        from opensearch_tpu.index.segment import SegmentBuilder
        from opensearch_tpu.search import query_dsl as qd

        # a search must never mutate index schema: percolated documents are
        # parsed against a CLONE of the mapper service so dynamic mappings
        # introduced by the candidate doc stay local to this query
        import copy as _copy

        tmp_ms = _copy.copy(self.mapper_service)
        tmp_ms.mappers = dict(self.mapper_service.mappers)
        builder = SegmentBuilder(tmp_ms, "_percolate_tmp")
        for i, doc in enumerate(node.documents):
            builder.add(
                tmp_ms.parse_document(f"_tmp_{i}", doc), seq_no=i
            )
        tmp_host = builder.build()
        tmp_dev = to_device(tmp_host)
        tmp_snap = SearcherSnapshot(segments=[(tmp_host, tmp_dev)], generation=0)
        tmp_ctx = ShardContext(tmp_snap, tmp_ms)
        tmp_ex = SegmentExecutor(tmp_ctx, tmp_host, tmp_dev)

        try:
            masks = []
            for host, dev in self.snapshot.segments:
                mask = np.zeros(dev.n_pad, bool)
                for d in range(host.n_docs):
                    if not host.live[d]:
                        continue
                    source = _json.loads(host.sources[d])
                    stored = source.get(node.field)
                    if not isinstance(stored, dict):
                        continue
                    try:
                        parsed = qd.parse_query(stored)
                        r = tmp_ex.execute(parsed)
                        if bool(np.asarray(r.mask)[: tmp_host.n_docs].any()):
                            mask[d] = True
                    except Exception as e:  # noqa: BLE001
                        # malformed stored query never matches
                        logger.debug(
                            "percolate: stored query for doc %d unusable: %s",
                            d, e)
                        continue
                masks.append(mask)
        finally:
            # the throwaway memory-index's device arrays die with this
            # query: release their residency-ledger entries (to_device
            # registered them; without this every percolate query leaked
            # resident_bytes forever)
            tmp_dev.free_allocations(reason="percolate-transient")
        self._qs_cache[("perc", id(node))] = masks
        return masks

    def join_masks(self, node) -> list:
        """Per-segment masks for has_child / has_parent / parent_id.

        Children are routed to the parent's shard (callers index with
        routing=parent id), so the join closes over this shard's segments
        (parent-join module invariant)."""
        cached = self._qs_cache.get(("join", id(node)))
        if cached is not None:
            return cached
        import json as _json

        import numpy as np

        from opensearch_tpu.search import query_dsl as qd

        join_field = None
        for f, m in self.mapper_service.mappers.items():
            if m.type == "join":
                join_field = f
                break
        name_col = f"{join_field}#name" if join_field else None

        def names_of(host):
            kf = host.keyword_fields.get(name_col) if name_col else None
            return kf

        def doc_relation(host, d):
            kf = names_of(host)
            if kf is None:
                return None
            o = kf.first_ord[d]
            return kf.ord_values[o] if o >= 0 else None

        def doc_parent(host, d):
            kf = host.keyword_fields.get(f"{join_field}#parent")
            if kf is None:
                return None
            o = kf.first_ord[d]
            return kf.ord_values[o] if o >= 0 else None

        masks = []
        if isinstance(node, qd.ParentIdQuery):
            for host, dev in self.snapshot.segments:
                mask = np.zeros(dev.n_pad, bool)
                for d in range(host.n_docs):
                    if (host.live[d] and doc_relation(host, d) == node.type
                            and doc_parent(host, d) == node.id):
                        mask[d] = True
                masks.append(mask)
        elif isinstance(node, qd.HasChildQuery):
            # which relation is the parent of node.type? (multi-level joins:
            # a mid-level relation is both a child and a parent)
            join_mapper = self.mapper_service.mappers.get(join_field)
            parent_names = {
                p for p, children in (
                    (join_mapper.relations or {}) if join_mapper else {}
                ).items()
                if node.type in children
            }
            # pass 1: matching children -> parent ids (across segments)
            parent_counts: dict[str, int] = {}
            for host, dev in self.snapshot.segments:
                ex = SegmentExecutor(self, host, dev)
                child_mask = np.asarray(ex.execute(node.query).mask)
                for d in range(host.n_docs):
                    if (host.live[d] and child_mask[d]
                            and doc_relation(host, d) == node.type):
                        p = doc_parent(host, d)
                        if p is not None:
                            parent_counts[p] = parent_counts.get(p, 0) + 1
            wanted = {
                p for p, c in parent_counts.items()
                if node.min_children <= c <= node.max_children
            }
            # pass 2: docs of the parent relation whose _id is in the set
            for host, dev in self.snapshot.segments:
                mask = np.zeros(dev.n_pad, bool)
                for d in range(host.n_docs):
                    if (host.live[d] and host.doc_ids[d] in wanted
                            and doc_relation(host, d) in parent_names):
                        mask[d] = True
                masks.append(mask)
        elif isinstance(node, qd.HasParentQuery):
            # pass 1: matching parents -> their _ids
            parent_ids: set[str] = set()
            for host, dev in self.snapshot.segments:
                ex = SegmentExecutor(self, host, dev)
                pmask = np.asarray(ex.execute(node.query).mask)
                for d in range(host.n_docs):
                    if (host.live[d] and pmask[d]
                            and doc_relation(host, d) == node.parent_type):
                        parent_ids.add(host.doc_ids[d])
            # pass 2: children pointing at those parents
            masks = []
            for host, dev in self.snapshot.segments:
                mask = np.zeros(dev.n_pad, bool)
                for d in range(host.n_docs):
                    if (host.live[d]
                            and doc_parent(host, d) in parent_ids):
                        mask[d] = True
                masks.append(mask)
        self._qs_cache[("join", id(node))] = masks
        return masks

    def text_stats(self, field: str) -> tuple[int, float]:
        """(doc_count, avgdl) across all segments of the shard."""
        doc_count = 0
        total_terms = 0.0
        for host, _ in self.snapshot.segments:
            tf = host.text_fields.get(field)
            if tf is not None:
                doc_count += tf.docs_with_field
                total_terms += tf.total_terms
        if doc_count == 0:
            return 0, 1.0
        return doc_count, total_terms / doc_count

    def text_df(self, field: str, term: str) -> int:
        return sum(
            host.text_fields[field].doc_freq(term)
            for host, _ in self.snapshot.segments
            if field in host.text_fields
        )

    def keyword_df(self, field: str, value: str) -> int:
        df = 0
        for host, _ in self.snapshot.segments:
            kf = host.keyword_fields.get(field)
            if kf is None:
                continue
            o = kf.ord_dict.get(value)
            if o is not None:
                offsets = _keyword_postings(kf).ord_offsets
                df += int(offsets[o + 1] - offsets[o])
        return df

    def keyword_doc_count(self, field: str) -> int:
        return sum(
            int((host.keyword_fields[field].first_ord >= 0).sum())
            for host, _ in self.snapshot.segments
            if field in host.keyword_fields
        )


# --------------------------------------------------------------------------
# Node execution against one segment
# --------------------------------------------------------------------------


def _phrase_match(lists: list, slop: int, terms: list | None = None) -> bool:
    """True iff one position per term can be chosen with total displacement
    cost Σ|p_i - p_{i-1} - 1| ≤ slop (slop 0 = exact adjacency; adjacent
    swaps cost 2, matching Lucene's sloppy-phrase distance). Repeated query
    terms must land on distinct positions (SloppyPhraseScorer repeats)."""
    if any(len(lst) == 0 for lst in lists):
        return False
    if terms is not None and len(set(terms)) < len(terms):
        # exhaustive search with the distinct-position constraint for
        # repeated terms; per-doc tf keeps the space tiny, but cap it
        def rec(i: int, prev_p: int | None, cost: int,
                used: dict[str, set], budget: list[int]) -> bool:
            if budget[0] <= 0:
                return False
            if cost > slop:
                return False
            if i == len(lists):
                return True
            t = terms[i]
            for p in lists[i]:
                p = int(p)
                if p in used.get(t, ()):
                    continue
                budget[0] -= 1
                step = 0 if prev_p is None else abs(p - prev_p - 1)
                used.setdefault(t, set()).add(p)
                if rec(i + 1, p, cost + step, used, budget):
                    return True
                used[t].discard(p)
            return False

        return rec(0, None, 0, {}, [200_000])
    prev = {int(p): 0 for p in lists[0]}
    for lst in lists[1:]:
        cur: dict[int, int] = {}
        for p in lst:
            p = int(p)
            cur[p] = min(c + abs(p - pq - 1) for pq, c in prev.items())
        prev = cur
        if min(prev.values()) > slop:
            return False  # costs only grow downstream
    return min(prev.values()) <= slop


@dataclass
class NodeResult:
    scores: jnp.ndarray            # f32 [n_pad], 0 where not matching
    mask: jnp.ndarray              # bool [n_pad]
    scoring: bool                  # False => pure filter (score ignored)


class HostNodeResult:
    """NodeResult duck-type for a kNN selection: the shard cut's winners in
    this segment, `docs` int32[w] and `doc_scores` f32[w] with w <= k, as
    execute_query_phase's host fast path reads them. A consumer that
    indexes by document touches the dense view, one scatter of the winners
    built once: `host_scores` (f32 [n_pad], 0 where unselected) and
    `host_mask` (bool [n_pad]) for aggregations, `.scores` / `.mask` (their
    device copies) for a COMPOUND parent (knn inside bool, rescore, ...) or
    a sort, so query semantics never change. The first touch in a request
    counts it `knn.collect.dense`; untouched, it counts `.sparse`."""

    scoring = True

    def __init__(self, ctx: ShardContext, n_pad: int, docs: np.ndarray,
                 doc_scores: np.ndarray):
        self.ctx, self.n_pad = ctx, n_pad
        self.docs, self.doc_scores = docs, doc_scores

    @cached_property
    def _dense(self) -> tuple[np.ndarray, np.ndarray]:
        if not self.ctx.knn_dense:
            self.ctx.knn_dense = True
            count_metric("knn.collect.dense")
        scores = np.zeros(self.n_pad, np.float32)
        scores[self.docs] = self.doc_scores
        mask = np.zeros(self.n_pad, bool)
        mask[self.docs] = True
        return scores, mask

    host_scores = property(lambda self: self._dense[0])
    host_mask = property(lambda self: self._dense[1])
    scores = cached_property(lambda self: jnp.asarray(self._dense[0]))
    mask = cached_property(lambda self: jnp.asarray(self._dense[1]))


def _const_result(mask: jnp.ndarray, boost: float, scoring: bool) -> NodeResult:
    scores = jnp.where(mask, jnp.float32(boost), jnp.float32(0.0))
    return NodeResult(scores=scores, mask=mask, scoring=scoring)


def _empty(dev: DeviceSegment) -> NodeResult:
    z = jnp.zeros(dev.n_pad, jnp.float32)
    return NodeResult(scores=z, mask=jnp.zeros(dev.n_pad, bool), scoring=False)


class SegmentExecutor:
    def __init__(self, ctx: ShardContext, host: HostSegment, dev: DeviceSegment):
        self.ctx = ctx
        self.host = host
        self.dev = dev
        # posting entries this executor's keyword clauses scattered into
        # masks: the `filter.mask` span's `postings`
        self.postings = 0

    # -- text scoring ------------------------------------------------------

    def _bm25(self, field: str, terms: list[str], boost: float) -> tuple[NodeResult, jnp.ndarray]:
        """Returns (result, per-doc matched-term counts)."""
        dev_tf = self.dev.text_fields.get(field)
        host_tf = self.host.text_fields.get(field)
        if dev_tf is None or host_tf is None or not terms:
            return _empty(self.dev), jnp.zeros(self.dev.n_pad, jnp.int32)
        doc_count, avgdl = self.ctx.text_stats(field)
        offs, lens, idfs = [], [], []
        for t in terms:
            tid = host_tf.term_dict.get(t)
            if tid is None:
                offs.append(0)
                lens.append(0)
                idfs.append(0.0)
            else:
                offs.append(int(host_tf.term_offsets[tid]))
                lens.append(int(host_tf.term_offsets[tid + 1] - host_tf.term_offsets[tid]))
                idfs.append(bm25.idf(self.ctx.text_df(field, t), doc_count))
        window = pad_window(max(lens) if lens else 1)
        postings = sum(lens)
        tally = self.ctx.bm25
        tally["terms"] += len(terms)
        tally["postings"] += postings
        tally["window"] = max(tally["window"], window)
        tally["rows"] += self.dev.n_pad
        count_metric("search.bm25.launches")
        count_metric("search.bm25.postings", postings)
        # the launch's shapes follow the term rows: rows of length 0 (no
        # posting, no score, no count) fill them up to a multiple of
        # BM25_TERM_ROWS, so that queries of 3-20 terms meet five shapes to
        # compile and not eighteen
        pad = -len(terms) % BM25_TERM_ROWS
        offs += [0] * pad
        lens += [0] * pad
        idfs += [0.0] * pad
        # per-term metadata stays HOST numpy here: these columns are the
        # only per-query host->device traffic of the BM25 path (postings
        # are HBM-resident), and the profiler counts transfer bytes from
        # host-typed kernel arguments
        scores, counts = bm25.bm25_term_scores(
            dev_tf.postings_docs,
            dev_tf.postings_tfs,
            dev_tf.doc_len,
            np.asarray(offs, np.int32),
            np.asarray(lens, np.int32),
            np.asarray(idfs, np.float32),
            np.float32(avgdl),
            n_pad=self.dev.n_pad,
            window=window,
        )
        mask = counts > 0
        return NodeResult(scores=scores * boost, mask=mask, scoring=True), counts

    # -- dispatch ----------------------------------------------------------

    def execute(self, node: q.QueryNode) -> NodeResult:
        method = getattr(self, f"_exec_{type(node).__name__}", None)
        if method is None:
            raise ParsingException(f"unexecutable query node [{type(node).__name__}]")
        prof = profile.active()
        if prof is None:
            return method(node)
        # deep profiler: nested execute() calls (bool children, rescore,
        # function_score inners) build the per-operator tree; same node
        # across segments accumulates into one entry
        with prof.operator(type(node).__name__, profile.describe_node(node)):
            return method(node)

    def _exec_MatchAllQuery(self, node: q.MatchAllQuery) -> NodeResult:
        return _const_result(self.dev.live, node.boost, scoring=True)

    def _exec_SliceQuery(self, node: q.SliceQuery) -> NodeResult:
        """Sliced scroll: murmur3(_id) % max == id (SliceBuilder's default
        _id-based partitioning). Hash per doc computed once per segment."""
        from opensearch_tpu.common.hashing import murmur3_x86_32

        host = self.host
        cache = getattr(host, "_slice_hash_cache", None)
        if cache is None:
            cache = np.asarray(
                [murmur3_x86_32(i.encode()) & 0xFFFFFFFF
                 for i in host.doc_ids],
                np.uint32,
            )
            host._slice_hash_cache = cache
        sel = np.zeros(self.dev.n_pad, bool)
        sel[: host.n_docs] = (cache % np.uint32(node.max)) == node.id
        mask = jnp.asarray(sel) & self.dev.live
        return _const_result(mask, node.boost, scoring=False)

    def _exec_MatchNoneQuery(self, node: q.MatchNoneQuery) -> NodeResult:
        return _empty(self.dev)

    def _exec_MatchQuery(self, node: q.MatchQuery) -> NodeResult:
        mapper = self.ctx.mapper_service.field_mapper(node.field)
        if mapper is None and \
                self.ctx.mapper_service.flat_object_parent(node.field):
            return self._exec_TermQuery(
                q.TermQuery(field=node.field, value=node.query, boost=node.boost)
            )
        if mapper is not None and mapper.type != "text":
            # match on non-text behaves like a term query (no analysis)
            return self._exec_TermQuery(
                q.TermQuery(field=node.field, value=node.query, boost=node.boost)
            )
        terms = self.ctx.mapper_service.analyze_query_text(node.field, node.query)
        if not terms:
            # zero analyzed tokens (e.g. all stopwords) matches nothing,
            # like the reference's MatchNoDocsQuery rewrite
            return _empty(self.dev)
        result, counts = self._bm25(node.field, terms, node.boost)
        if node.operator == "and":
            result = NodeResult(
                scores=result.scores, mask=counts >= len(terms), scoring=True
            )
        elif node.minimum_should_match is not None:
            result = NodeResult(
                scores=result.scores,
                mask=counts >= node.minimum_should_match,
                scoring=True,
            )
        return NodeResult(result.scores, result.mask & self.dev.live, True)

    def _exec_MatchPhraseQuery(self, node: q.MatchPhraseQuery) -> NodeResult:
        # Device conjunction narrows candidates; position postings
        # (HostTextField positions CSR) verify adjacency host-side
        # (MatchPhraseQueryBuilder -> Lucene PhraseQuery semantics).
        terms = self.ctx.mapper_service.analyze_query_text(node.field, node.query)
        if not terms:
            return _empty(self.dev)
        result, counts = self._bm25(node.field, terms, node.boost)
        conj = (counts >= len(terms)) & self.dev.live
        host_tf = self.host.text_fields.get(node.field)
        if len(terms) <= 1 or host_tf is None or not host_tf.has_positions:
            # single term, or a legacy segment without position postings:
            # conjunction is the best available answer
            return NodeResult(result.scores, conj, True)
        cand = np.nonzero(np.asarray(conj)[: self.host.n_docs])[0]
        verified = np.zeros(self.dev.n_pad, bool)
        for d in cand:
            lists = [host_tf.term_positions(t, int(d)) for t in terms]
            if _phrase_match(lists, node.slop, terms):
                verified[d] = True
        mask = jnp.asarray(verified)
        return NodeResult(jnp.where(mask, result.scores, 0.0), mask, True)

    def _exec_IntervalsQuery(self, node: q.IntervalsQuery) -> NodeResult:
        from opensearch_tpu.search import intervals as iv

        host_tf = self.host.text_fields.get(node.field)
        if host_tf is None or not host_tf.has_positions:
            return _empty(self.dev)
        ms = self.ctx.mapper_service

        def analyze(text: str, analyzer: str | None) -> list[str]:
            if analyzer:
                return ms.analysis.get(analyzer).analyze(text)
            return ms.analyze_query_text(node.field, text)

        ctx = iv.IntervalContext(
            analyze=analyze,
            vocab=host_tf.terms,
            positions=lambda t, d: host_tf.term_positions(t, d),
            edit_distance_at_most=_edit_distance_at_most,
            fuzziness_distance=_fuzziness_distance,
        )
        # candidate docs: union of posting lists of every involved term
        cand: set[int] = set()
        for t in ctx.leaf_terms(node.source):
            tid = host_tf.term_dict.get(t)
            if tid is None:
                continue
            off = int(host_tf.term_offsets[tid])
            end = int(host_tf.term_offsets[tid + 1])
            cand.update(int(d) for d in host_tf.postings_docs[off:end])
        live = np.asarray(self.dev.live)
        mask = np.zeros(self.dev.n_pad, bool)
        for d in sorted(cand):
            if live[d] and iv.evaluate(node.source, ctx, d):
                mask[d] = True
        return _const_result(jnp.asarray(mask), node.boost, scoring=True)

    def _exec_MultiMatchQuery(self, node: q.MultiMatchQuery) -> NodeResult:
        msm = node.minimum_should_match

        def fboost(f: str) -> float:
            return node.boost * node.field_boosts.get(f, 1.0)

        if node.type == "bool_prefix":
            per_field = [
                self._exec_MatchBoolPrefixQuery(q.MatchBoolPrefixQuery(
                    field=f, query=node.query, operator=node.operator,
                    minimum_should_match=msm, fuzziness=node.fuzziness,
                    analyzer=node.analyzer, boost=fboost(f),
                ))
                for f in node.fields
            ]
        elif node.type == "phrase":
            per_field = [
                self._exec_MatchPhraseQuery(q.MatchPhraseQuery(
                    field=f, query=node.query, slop=node.slop,
                    boost=fboost(f)))
                for f in node.fields
            ]
        elif node.type == "phrase_prefix":
            per_field = [
                self._exec_MatchPhrasePrefixQuery(q.MatchPhrasePrefixQuery(
                    field=f, query=node.query, boost=fboost(f)))
                for f in node.fields
            ]
        else:
            per_field = None
        if per_field is not None:
            if not per_field:
                return _empty(self.dev)
            mask = per_field[0].mask
            scores = per_field[0].scores
            for s in per_field[1:]:
                mask = mask | s.mask
                scores = jnp.maximum(scores, s.scores)
            return NodeResult(scores=scores, mask=mask, scoring=True)
        subs = [
            self._exec_MatchQuery(q.MatchQuery(
                field=f, query=node.query, boost=fboost(f),
                operator=node.operator,
                minimum_should_match=(
                    int(msm) if isinstance(msm, int) or
                    (isinstance(msm, str) and msm.lstrip("-").isdigit())
                    else None),
            ))
            for f in node.fields
        ]
        if not subs:
            return _empty(self.dev)
        mask = subs[0].mask
        for s in subs[1:]:
            mask = mask | s.mask
        if node.type == "most_fields":
            scores = sum((s.scores for s in subs[1:]), subs[0].scores)
        else:  # best_fields: max over fields
            scores = subs[0].scores
            for s in subs[1:]:
                scores = jnp.maximum(scores, s.scores)
        return NodeResult(scores=scores, mask=mask, scoring=True)

    def _normalize_kw(self, field: str, value: str) -> str:
        mapper = self.ctx.mapper_service.field_mapper(field)
        if mapper is not None and mapper.normalizer == "lowercase":
            return value.lower()
        return value

    def _exec_TermQuery(self, node: q.TermQuery) -> NodeResult:
        field, value = node.field, node.value
        if field == "_id":
            return self._exec_IdsQuery(q.IdsQuery(values=[str(value)],
                                                  boost=node.boost))
        mapper = self.ctx.mapper_service.field_mapper(field)
        if mapper is None:
            # sub-path of a flat_object field -> term on the shared
            # "{root}#paths" column with a "sub.path=value" entry
            flat = self.ctx.mapper_service.flat_object_parent(field)
            if flat is not None:
                root, subpath = flat
                return self._exec_TermQuery(q.TermQuery(
                    field=f"{root}#paths", value=f"{subpath}={value}",
                    case_insensitive=node.case_insensitive,
                    boost=node.boost,
                ))
        ftype = mapper.type if mapper else None
        if ftype == "flat_object":
            ftype = "keyword"
        if mapper is not None and mapper.normalizer == "lowercase" \
                and isinstance(value, str):
            value = value.lower()
        if ftype == "text":
            result, _counts = self._bm25(field, [str(value)], node.boost)
            return NodeResult(result.scores, result.mask & self.dev.live, True)
        if ftype == "keyword" or (ftype is None and field in self.host.keyword_fields):
            if node.case_insensitive:
                want = str(value).lower()
                return self._multi_term_result(
                    field, lambda t: t.lower() == want, node.boost
                )
            if mapper is not None and mapper.original_type == "ip" \
                    and "/" in str(value):
                # CIDR term: any stored address inside the subnet
                import ipaddress

                try:
                    net = ipaddress.ip_network(str(value), strict=False)
                except ValueError as e:
                    raise IllegalArgumentException(
                        f"invalid IP subnet [{value}]: {e}"
                    ) from None
                return self._multi_term_result(
                    field,
                    lambda t: (lambda a: a is not None and a in net)(
                        _try_ip(t)
                    ),
                    node.boost,
                )
            kf_host = self.host.keyword_fields.get(field)
            if kf_host is None:
                return _empty(self.dev)
            mask = self._keyword_mask(
                kf_host, (kf_host.ord_dict.get(str(value), -3),))
            # keyword term scoring: norms omitted -> idf * tf/(tf+k1), tf=1
            df = self.ctx.keyword_df(field, str(value))
            doc_count = max(self.ctx.keyword_doc_count(field), 1)
            score = bm25.idf(df, doc_count) / (1.0 + bm25.K1_DEFAULT) if df else 0.0
            return _const_result(mask, score * node.boost, scoring=True)
        if ftype in ("boolean",):
            want = 1 if value in (True, "true", 1) else 0
            return self._numeric_range(field, want, None, want, None, node.boost)
        if ftype == "date":
            if mapper.resolution == "nanos":
                from opensearch_tpu.index.mapper import parse_date_nanos

                ms = parse_date_nanos(value)
            else:
                ms = parse_date_millis(value)
            return self._numeric_range(field, ms, None, ms, None, node.boost)
        if ftype in INT_TYPES or ftype in FLOAT_TYPES or ftype is None:
            return self._numeric_range(field, value, None, value, None, node.boost)

        raise IllegalArgumentException(f"term query on unsupported field [{field}]")

    def _exec_TermsQuery(self, node: q.TermsQuery) -> NodeResult:
        if node.field == "_id":
            return self._exec_IdsQuery(q.IdsQuery(
                values=[str(v) for v in node.values], boost=node.boost))
        mapper = self.ctx.mapper_service.field_mapper(node.field)
        if mapper is None:
            flat = self.ctx.mapper_service.flat_object_parent(node.field)
            if flat is not None:
                root, subpath = flat
                return self._exec_TermsQuery(q.TermsQuery(
                    field=f"{root}#paths",
                    values=[f"{subpath}={v}" for v in node.values],
                    boost=node.boost,
                ))
        ftype = mapper.type if mapper else None
        if ftype in ("keyword", "flat_object"):
            kf_host = self.host.keyword_fields.get(node.field)
            if kf_host is None:
                return _empty(self.dev)
            mask = self._keyword_mask(kf_host, [
                kf_host.ord_dict.get(self._normalize_kw(node.field, str(v)), -3)
                for v in node.values
            ])
            return _const_result(mask, node.boost, scoring=True)
        # numeric/text fallback: OR of term queries
        out: NodeResult | None = None
        for v in node.values:
            r = self._exec_TermQuery(q.TermQuery(field=node.field, value=v, boost=node.boost))
            out = r if out is None else NodeResult(
                jnp.maximum(out.scores, r.scores), out.mask | r.mask, True
            )
        return out if out is not None else _empty(self.dev)

    def _exec_range_field(self, node: q.RangeQuery, mapper) -> NodeResult:
        """Range query against a RANGE FIELD (doc values are intervals in
        the `{field}#lo`/`{field}#hi` columns):
          intersects: doc.lo <= q.hi  AND doc.hi >= q.lo
          contains:   doc.lo <= q.lo  AND doc.hi >= q.hi
          within:     doc.lo >= q.lo  AND doc.hi <= q.hi
        (RangeFieldMapper's BKD relation queries in columnar form)."""
        from opensearch_tpu.index.mapper import range_value_bounds

        try:
            q_lo, q_hi = range_value_bounds(
                mapper.type,
                {"gte": node.gte, "gt": node.gt,
                 "lte": node.lte, "lt": node.lt},
                mapper.format,
            )
        except (ValueError, TypeError) as e:
            raise IllegalArgumentException(
                f"failed to parse range query on [{node.field}]: {e}"
            ) from None
        lo_f, hi_f = f"{node.field}#lo", f"{node.field}#hi"
        relation = node.relation or "intersects"
        if relation == "contains":
            a = self._numeric_range(lo_f, None, None, q_lo, None, 1.0)
            b = self._numeric_range(hi_f, q_hi, None, None, None, 1.0)
        elif relation == "within":
            a = self._numeric_range(lo_f, q_lo, None, None, None, 1.0)
            b = self._numeric_range(hi_f, None, None, q_hi, None, 1.0)
        elif relation == "intersects":
            a = self._numeric_range(lo_f, None, None, q_hi, None, 1.0)
            b = self._numeric_range(hi_f, q_lo, None, None, None, 1.0)
        else:
            raise IllegalArgumentException(
                f"[range] unknown relation [{relation}]")
        mask = a.mask & b.mask & self.dev.live
        return _const_result(mask, node.boost, scoring=True)

    def _numeric_range(
        self, field: str, gte: Any, gt: Any, lte: Any, lt: Any, boost: float
    ) -> NodeResult:
        nf_dev = self.dev.numeric_fields.get(field)
        nf_host = self.host.numeric_fields.get(field)
        if nf_dev is None:
            return _empty(self.dev)
        mapper = self.ctx.mapper_service.field_mapper(field)
        is_date = mapper is not None and mapper.type == "date"
        nanos = is_date and mapper.resolution == "nanos"
        unsigned = mapper is not None and \
            mapper.original_type == "unsigned_long"

        def conv(v: Any) -> Any:
            if v is None:
                return None
            if nanos:
                from opensearch_tpu.index.mapper import parse_date_nanos

                return parse_date_nanos(v)
            if unsigned:
                return int(str(v), 10) - 2**63  # biased storage
            return parse_date_millis(v) if is_date else v

        gte, gt, lte, lt = conv(gte), conv(gt), conv(lte), conv(lt)
        if nf_host is not None and nf_host.mv_offsets is not None:
            # multi-valued docs: a doc matches if ANY value is in range
            # (SortedNumericDocValues semantics) — vectorized host CSR scan
            mv = nf_host.mv_values
            if nf_host.kind == "int":
                lo_b = I64_MIN if gte is None and gt is None else (
                    int(gte) if gte is not None else int(gt) + 1)
                hi_b = I64_MAX if lte is None and lt is None else (
                    int(lte) if lte is not None else int(lt) - 1)
                sel = (mv >= lo_b) & (mv <= hi_b)
            else:
                lo_v = float(gte) if gte is not None else (
                    float(gt) if gt is not None else -np.inf)
                hi_v = float(lte) if lte is not None else (
                    float(lt) if lt is not None else np.inf)
                sel = np.ones(len(mv), bool)
                sel &= (mv > lo_v) if gt is not None else (mv >= lo_v)
                sel &= (mv < hi_v) if lt is not None else (mv <= hi_v)
            mask_host = np.zeros(self.dev.n_pad, bool)
            idx = np.nonzero(sel)[0]
            if len(idx):
                # entry index -> owning doc via the CSR offsets
                doc_of = np.searchsorted(nf_host.mv_offsets, idx, side="right") - 1
                mask_host[np.unique(doc_of)] = True
            return _const_result(
                jnp.asarray(mask_host) & self.dev.live, boost, scoring=True
            )
        if nf_dev.kind == "int":
            lo_bound = I64_MIN if gte is None and gt is None else (
                int(gte) if gte is not None else int(gt) + 1
            )
            hi_bound = I64_MAX if lte is None and lt is None else (
                int(lte) if lte is not None else int(lt) - 1
            )
            ghi, glo = i64_query_words(lo_bound)
            lhi, llo = i64_query_words(hi_bound)
            mask = filters.range_mask_i64(
                nf_dev.hi, nf_dev.lo, nf_dev.present,
                jnp.int32(ghi), jnp.int32(glo), jnp.int32(lhi), jnp.int32(llo),
            )
        else:
            lo_v = float(gte) if gte is not None else (float(gt) if gt is not None else -np.inf)
            hi_v = float(lte) if lte is not None else (float(lt) if lt is not None else np.inf)
            mask = filters.range_mask_f32(
                nf_dev.values, nf_dev.present,
                jnp.float32(lo_v), jnp.float32(hi_v),
                jnp.asarray(gt is not None), jnp.asarray(lt is not None),
            )
        return _const_result(mask & self.dev.live, boost, scoring=True)

    def _exec_RangeQuery(self, node: q.RangeQuery) -> NodeResult:
        mapper = self.ctx.mapper_service.field_mapper(node.field)
        if mapper is not None and mapper.type in RANGE_TYPES:
            return self._exec_range_field(node, mapper)
        if mapper is not None and mapper.type == "flat_object":
            # the root column is keyword-shaped: lexicographic range
            from opensearch_tpu.index.mapper import FieldMapper as _FM

            mapper = _FM(node.field, "keyword")
        if mapper is None:
            flat = self.ctx.mapper_service.flat_object_parent(node.field)
            if flat is not None:
                root, sub = flat
                # lexicographic range inside the "sub=value" entries; the
                # constant "sub=" prefix keeps bounds within this sub-path
                return self._exec_RangeQuery(q.RangeQuery(
                    field=f"{root}#paths",
                    gte=(f"{sub}={node.gte}" if node.gte is not None
                         else f"{sub}="),
                    gt=f"{sub}={node.gt}" if node.gt is not None else None,
                    lte=(f"{sub}={node.lte}" if node.lte is not None
                         else f"{sub}=\uffff"),
                    lt=f"{sub}={node.lt}" if node.lt is not None else None,
                    boost=node.boost,
                ))
        if mapper is not None and mapper.type == "keyword":
            # lexicographic range over ordinals (ordinals are sorted)
            kf_host = self.host.keyword_fields.get(node.field)
            if kf_host is None:
                return _empty(self.dev)
            import bisect

            vals = kf_host.ord_values
            lo = 0
            hi = len(vals) - 1
            if node.gte is not None:
                lo = bisect.bisect_left(vals, str(node.gte))
            if node.gt is not None:
                lo = max(lo, bisect.bisect_right(vals, str(node.gt)))
            if node.lte is not None:
                hi = bisect.bisect_right(vals, str(node.lte)) - 1
            if node.lt is not None:
                hi = min(hi, bisect.bisect_left(vals, str(node.lt)) - 1)
            if hi < lo:
                return _empty(self.dev)
            mask = self._keyword_mask(kf_host, range(lo, hi + 1))
            return _const_result(mask, node.boost, scoring=True)
        return self._exec_range_numeric(node)

    def _exec_range_numeric(self, node: q.RangeQuery) -> NodeResult:
        return self._numeric_range(node.field, node.gte, node.gt, node.lte, node.lt, node.boost)

    def _exec_TermsSetQuery(self, node: q.TermsSetQuery) -> NodeResult:
        """Per-doc msm: count matching terms against the msm field's value
        (TermsSetQueryBuilder -> CoveringQuery)."""
        field = node.field
        mapper = self.ctx.mapper_service.field_mapper(field)
        if mapper is None:
            flat = self.ctx.mapper_service.flat_object_parent(field)
            if flat is not None:
                root, subpath = flat
                return self._exec_TermsSetQuery(q.TermsSetQuery(
                    field=f"{root}#paths",
                    terms=[f"{subpath}={t}" for t in node.terms],
                    minimum_should_match_field=node.minimum_should_match_field,
                    minimum_should_match_script=node.minimum_should_match_script,
                    boost=node.boost,
                ))
        kf_host = self.host.keyword_fields.get(field)
        counts = np.zeros(self.host.n_docs, np.int64)
        if kf_host is not None:
            for v in node.terms:
                val = self._normalize_kw(field, str(v))
                o = kf_host.ord_dict.get(val)
                if o is None:
                    continue
                sel = kf_host.mv_ords == o
                np.add.at(counts, kf_host.mv_docs[sel], 1)
        elif mapper is not None and mapper.type == "text":
            tf_host = self.host.text_fields.get(field)
            if tf_host is not None:
                for v in node.terms:
                    tid = tf_host.term_dict.get(str(v))
                    if tid is None:
                        continue
                    off = int(tf_host.term_offsets[tid])
                    end = int(tf_host.term_offsets[tid + 1])
                    counts[tf_host.postings_docs[off:end]] += 1
        if node.minimum_should_match_field:
            nf = self.host.numeric_fields.get(node.minimum_should_match_field)
            if nf is None:
                return _empty(self.dev)
            msm = np.where(
                nf.present[: self.host.n_docs],
                (nf.values_i64 if nf.kind == "int" else nf.values_f64)[
                    : self.host.n_docs],
                np.iinfo(np.int32).max,
            )
        elif node.minimum_should_match_script:
            from opensearch_tpu.script import default_script_service

            src = str(node.minimum_should_match_script.get("source", ""))
            # common pattern: params.num_terms or a constant
            if "num_terms" in src:
                msm = np.full(self.host.n_docs, len(node.terms))
            else:
                try:
                    msm = np.full(self.host.n_docs, int(float(src)))
                except ValueError:
                    msm = np.full(self.host.n_docs, 1)
        else:
            raise IllegalArgumentException(
                "[terms_set] requires [minimum_should_match_field] or "
                "[minimum_should_match_script]"
            )
        mask_host = np.zeros(self.dev.n_pad, bool)
        mask_host[: self.host.n_docs] = (counts >= msm) & (counts > 0)
        return _const_result(
            jnp.asarray(mask_host) & self.dev.live, node.boost, scoring=True
        )

    def _exec_DistanceFeatureQuery(self, node: q.DistanceFeatureQuery) -> NodeResult:
        """score = boost * pivot / (pivot + distance(origin, value))."""
        field = node.field
        mapper = self.ctx.mapper_service.field_mapper(field)
        n = self.host.n_docs
        lat_f = self.host.numeric_fields.get(f"{field}#lat")
        if mapper is not None and mapper.type == "geo_point" \
                or lat_f is not None:
            lon_f = self.host.numeric_fields.get(f"{field}#lon")
            if lat_f is None or lon_f is None:
                return _empty(self.dev)
            o_lat, o_lon = _parse_geo_origin(node.origin)
            pivot_m = _parse_distance_meters(node.pivot)
            lat = lat_f.values_f64[:n]
            lon = lon_f.values_f64[:n]
            dist = _haversine_m(o_lat, o_lon, lat, lon)
            present = lat_f.present[:n]
            score = np.where(present, pivot_m / (pivot_m + dist), 0.0)
        else:
            nf = self.host.numeric_fields.get(field)
            if nf is None:
                return _empty(self.dev)
            is_date = mapper is not None and mapper.type == "date"
            if is_date and getattr(mapper, "resolution", "millis") == "nanos":
                from opensearch_tpu.index.mapper import parse_date_nanos

                origin = float(parse_date_nanos(str(node.origin)))
                pivot = float(_duration_millis(node.pivot)) * 1e6
            elif is_date:
                origin = float(_parse_date_or_now(node.origin))
                pivot = float(_duration_millis(node.pivot))
            else:
                origin = float(node.origin)
                pivot = float(node.pivot)
            vals = (nf.values_i64 if nf.kind == "int" else nf.values_f64)[:n]
            dist = np.abs(vals.astype(np.float64) - origin)
            score = np.where(nf.present[:n], pivot / (pivot + dist), 0.0)
        scores = np.zeros(self.dev.n_pad, np.float32)
        scores[:n] = score * node.boost
        mask = jnp.asarray(scores > 0) & self.dev.live
        return NodeResult(
            scores=jnp.where(mask, jnp.asarray(scores), 0.0), mask=mask,
            scoring=True,
        )

    def _exec_RankFeatureQuery(self, node: q.RankFeatureQuery) -> NodeResult:
        """saturation: v/(v+pivot) (default pivot = field mean); log:
        ln(sf + v); sigmoid: v^e/(v^e + pivot^e); linear: v."""
        nf = self.host.numeric_fields.get(node.field)
        if nf is None:
            return _empty(self.dev)
        n = self.host.n_docs
        vals = (nf.values_i64 if nf.kind == "int" else nf.values_f64)[:n]
        vals = vals.astype(np.float64)
        present = nf.present[:n]
        def default_pivot() -> float:
            # approximate geometric mean over the WHOLE shard (the
            # reference computes the pivot from index-level stats; a
            # per-segment pivot would rank equal-feature docs differently
            # across segments)
            total, count = 0.0, 0
            for h, _d in self.ctx.snapshot.segments:
                f = h.numeric_fields.get(node.field)
                if f is None:
                    continue
                v = (f.values_i64 if f.kind == "int" else f.values_f64)[
                    : h.n_docs]
                p = f.present[: h.n_docs]
                total += float(v[p].sum())
                count += int(p.sum())
            return max(total / count if count else 1.0, 1e-9)

        if node.function == "log":
            score = np.log(np.maximum(node.scaling_factor + vals, 1e-12))
        elif node.function == "linear":
            score = vals
        elif node.function == "sigmoid":
            pivot = node.pivot if node.pivot is not None else default_pivot()
            ve = np.power(vals, node.exponent)
            score = ve / (ve + pivot ** node.exponent)
        else:  # saturation
            pivot = node.pivot if node.pivot is not None else default_pivot()
            score = vals / (vals + pivot)
        scores = np.zeros(self.dev.n_pad, np.float32)
        scores[:n] = np.where(present, score, 0.0) * node.boost
        mask = jnp.asarray(np.pad(present, (0, self.dev.n_pad - n))) & self.dev.live
        return NodeResult(
            scores=jnp.where(mask, jnp.asarray(scores), 0.0), mask=mask,
            scoring=True,
        )

    def _geo_columns(self, field: str):
        lat_f = self.host.numeric_fields.get(f"{field}#lat")
        lon_f = self.host.numeric_fields.get(f"{field}#lon")
        if lat_f is None or lon_f is None:
            return None
        n = self.host.n_docs
        return (lat_f.values_f64[:n], lon_f.values_f64[:n],
                lat_f.present[:n])

    def _geo_match_docs(self, field: str, point_pred) -> np.ndarray | None:
        """bool[n_docs] — doc matches if ANY of its points satisfies
        `point_pred(lat_array, lon_array) -> bool_array` (multi-valued
        geo_point docs hold parallel lat/lon CSRs)."""
        lat_f = self.host.numeric_fields.get(f"{field}#lat")
        lon_f = self.host.numeric_fields.get(f"{field}#lon")
        if lat_f is None or lon_f is None:
            return None
        n = self.host.n_docs
        out = np.zeros(n, bool)
        if lat_f.mv_offsets is not None and lon_f.mv_offsets is not None:
            sel = point_pred(lat_f.mv_values, lon_f.mv_values)
            idx = np.nonzero(sel)[0]
            if len(idx):
                doc_of = np.searchsorted(lat_f.mv_offsets, idx,
                                         side="right") - 1
                out[np.unique(doc_of)] = True
            return out
        sel = point_pred(lat_f.values_f64[:n], lon_f.values_f64[:n])
        out[:n] = lat_f.present[:n] & sel
        return out

    def _exec_GeoDistanceQuery(self, node: q.GeoDistanceQuery) -> NodeResult:
        o_lat, o_lon = _parse_geo_origin(node.point)
        radius = _parse_distance_meters(node.distance)
        sel = self._geo_match_docs(
            node.field,
            lambda la, lo: _haversine_m(o_lat, o_lon, la, lo) <= radius,
        )
        if sel is None:
            return _empty(self.dev)
        mask_host = np.zeros(self.dev.n_pad, bool)
        mask_host[: self.host.n_docs] = sel
        return _const_result(jnp.asarray(mask_host) & self.dev.live,
                             node.boost, scoring=True)

    def _exec_GeoShapeQuery(self, node: q.GeoShapeQuery) -> NodeResult:
        """geo_shape over point columns: the shape's bounding box is the
        match region (exact for envelope/point; polygon matches by bbox —
        a documented approximation of the reference's tessellated shapes)."""
        shape = node.shape or {}
        styp = str(shape.get("type", "")).lower()
        coords = shape.get("coordinates")
        if styp == "point":
            lons = [coords[0]]
            lats = [coords[1]]
        elif styp == "envelope":
            (tl_lon, tl_lat), (br_lon, br_lat) = coords
            lons = [tl_lon, br_lon]
            lats = [tl_lat, br_lat]
        elif styp in ("polygon", "multipoint", "linestring"):
            flat = coords[0] if styp == "polygon" else coords
            lons = [c[0] for c in flat]
            lats = [c[1] for c in flat]
        else:
            raise IllegalArgumentException(
                f"[geo_shape] unsupported shape type [{styp}]"
            )
        lat_hi, lat_lo = max(lats), min(lats)
        lon_hi, lon_lo = max(lons), min(lons)

        def pred(la, lo):
            inside = (la >= lat_lo) & (la <= lat_hi) \
                & (lo >= lon_lo) & (lo <= lon_hi)
            return ~inside if node.relation == "disjoint" else inside

        sel = self._geo_match_docs(node.field, pred)
        if sel is None:
            return _empty(self.dev)
        mask_host = np.zeros(self.dev.n_pad, bool)
        mask_host[: self.host.n_docs] = sel
        return _const_result(jnp.asarray(mask_host) & self.dev.live,
                             node.boost, scoring=True)

    def _exec_GeoBoundingBoxQuery(self, node: q.GeoBoundingBoxQuery) -> NodeResult:
        tl_lat, tl_lon = _parse_geo_origin(node.top_left)
        br_lat, br_lon = _parse_geo_origin(node.bottom_right)

        def pred(la, lo):
            box = (la <= tl_lat) & (la >= br_lat)
            if tl_lon <= br_lon:
                return box & (lo >= tl_lon) & (lo <= br_lon)
            return box & ((lo >= tl_lon) | (lo <= br_lon))

        sel = self._geo_match_docs(node.field, pred)
        if sel is None:
            return _empty(self.dev)
        mask_host = np.zeros(self.dev.n_pad, bool)
        mask_host[: self.host.n_docs] = sel
        return _const_result(jnp.asarray(mask_host) & self.dev.live,
                             node.boost, scoring=True)

    def _exec_ExistsQuery(self, node: q.ExistsQuery) -> NodeResult:
        field = node.field
        flat = self.ctx.mapper_service.flat_object_parent(field)
        if flat is not None and self.ctx.mapper_service.mappers.get(field) is None:
            root, subpath = flat
            # sub-path exists == any "{subpath}=value" entry in #paths, or
            # any deeper "{subpath}.x=value" entry
            r1 = self._exec_PrefixQuery(q.PrefixQuery(
                field=f"{root}#paths", value=f"{subpath}=", boost=node.boost))
            r2 = self._exec_PrefixQuery(q.PrefixQuery(
                field=f"{root}#paths", value=f"{subpath}.", boost=node.boost))
            return NodeResult(jnp.maximum(r1.scores, r2.scores),
                              r1.mask | r2.mask, True)
        masks = []
        if field not in self.dev.numeric_fields \
                and field not in self.dev.vector_fields \
                and field not in self.dev.keyword_fields \
                and field not in self.dev.text_fields:
            # object prefix: exists == any mapped child exists
            children = [
                name for name in self.ctx.mapper_service.mappers
                if name.startswith(f"{field}.")
            ]
            if children:
                out = None
                for child in children:
                    r = self._exec_ExistsQuery(
                        q.ExistsQuery(field=child, boost=node.boost)
                    )
                    out = r if out is None else NodeResult(
                        jnp.maximum(out.scores, r.scores),
                        out.mask | r.mask, True,
                    )
                if out is not None:
                    return out
        if field in self.dev.numeric_fields:
            masks.append(self.dev.numeric_fields[field].present)
        if field in self.dev.vector_fields:
            masks.append(self.dev.vector_fields[field].present)
        if field in self.dev.keyword_fields:
            masks.append(self.dev.keyword_fields[field].first_ord >= 0)
        if field in self.dev.text_fields:
            masks.append(self.dev.text_fields[field].doc_len > 0)
        if not masks:
            return _empty(self.dev)
        mask = masks[0]
        for m in masks[1:]:
            mask = mask | m
        return _const_result(mask & self.dev.live, node.boost, scoring=True)

    def _exec_IdsQuery(self, node: q.IdsQuery) -> NodeResult:
        mask_host = np.zeros(self.dev.n_pad, dtype=bool)
        for doc_id in node.values:
            # doc_index (not local_doc): liveness comes from the snapshot's
            # device mask, so pinned PIT/scroll readers stay point-in-time
            d = self.host.doc_index(doc_id)
            if d is not None:
                mask_host[d] = True
        return _const_result(jnp.asarray(mask_host) & self.dev.live, node.boost, True)

    def _exec_ConstantScoreQuery(self, node: q.ConstantScoreQuery) -> NodeResult:
        inner = self.execute(node.filter)
        return _const_result(inner.mask, node.boost, scoring=True)

    def _exec_BoolQuery(self, node: q.BoolQuery) -> NodeResult:
        n_pad = self.dev.n_pad
        mask = self.dev.live
        scores = jnp.zeros(n_pad, jnp.float32)
        any_scoring = False
        for sub in node.must:
            r = self.execute(sub)
            mask = mask & r.mask
            if r.scoring:
                any_scoring = True
            scores = scores + r.scores
        for sub in node.filter:
            r = self.execute(sub)
            mask = mask & r.mask
        for sub in node.must_not:
            r = self.execute(sub)
            mask = mask & ~r.mask
        if node.should:
            should_results = [self.execute(sub) for sub in node.should]
            should_count = jnp.zeros(n_pad, jnp.int32)
            for r in should_results:
                should_count = should_count + r.mask.astype(jnp.int32)
                scores = scores + jnp.where(r.mask, r.scores, 0.0)
                if r.scoring:
                    any_scoring = True
            msm = node.minimum_should_match
            if msm is None:
                msm = 1 if not (node.must or node.filter) else 0
            if msm > 0:
                mask = mask & (should_count >= msm)
        # scores of non-matching docs must be zeroed (a must_not can strike
        # a doc that a should scored)
        scores = jnp.where(mask, scores, 0.0) * node.boost
        return NodeResult(scores=scores, mask=mask, scoring=any_scoring)

    def _exec_KnnQuery(self, node: q.KnnQuery) -> NodeResult:
        # k applies per SHARD (top-k cut across all its segments) — the
        # ShardContext caches the shard-wide selection per query node
        selections = self.ctx.shard_knn_selection(node)
        seg_idx = next(
            i for i, (h, d) in enumerate(self.ctx.snapshot.segments) if d is self.dev
        )
        selection = selections[seg_idx]
        if selection is None:
            return _empty(self.dev)
        # the shard cut already chose the winners: they travel as they are
        docs, scores = selection
        if node.boost != 1.0:
            scores = scores * np.float32(node.boost)
        return HostNodeResult(self.ctx, self.dev.n_pad, docs, scores)

    def _exec_ScriptScoreQuery(self, node: q.ScriptScoreQuery) -> NodeResult:
        inner = self.execute(node.query) if node.query else self._exec_MatchAllQuery(q.MatchAllQuery())
        vf = self.dev.vector_fields.get(node.field)
        if vf is None:
            return _empty(self.dev)
        valid = vf.present & inner.mask
        # host numpy: counted as this request's host->device transfer
        qv = np.asarray([node.query_vector], np.float32)
        if node.function == "knn_score":
            scores = knn.exact_knn_scores(qv, vf.vectors, vf.norms_sq, valid, node.space_type)[0]
            scores = jnp.where(valid, scores, 0.0)
        else:
            raw = knn.raw_similarity(
                qv, vf.vectors, vf.norms_sq,
                "l2_norm" if node.space_type == "l2_raw" else node.space_type,
            )[0]
            if node.space_type == "l2_raw":
                raw = jnp.maximum(-raw, 0.0)  # l2Squared returns the distance
            scores = jnp.where(valid, raw + node.add_constant, 0.0)
        return NodeResult(scores=scores * node.boost, mask=valid, scoring=True)

    def _exec_GenericScriptScoreQuery(self, node: q.GenericScriptScoreQuery) -> NodeResult:
        """Per-doc host evaluation (the reference's ScriptScoreFunction runs
        a compiled script per collected doc — same cost model; the vector
        patterns take the fused device path instead)."""
        from opensearch_tpu.script import default_script_service

        inner = self.execute(node.query) if node.query else self._exec_MatchAllQuery(
            q.MatchAllQuery()
        )
        ast, params = default_script_service.compile(node.script)
        mask_host = np.asarray(inner.mask)[: self.host.n_docs]
        base_scores = np.asarray(inner.scores)[: self.host.n_docs]
        scores = np.zeros(self.dev.n_pad, np.float32)
        ms = self.ctx.mapper_service
        for d in np.nonzero(mask_host)[0]:
            scores[d] = default_script_service.score(
                ast, params, self.host, int(d), ms, score=float(base_scores[d])
            )
        return NodeResult(
            scores=jnp.asarray(scores) * node.boost, mask=inner.mask, scoring=True
        )

    def _exec_ScriptQuery(self, node: q.ScriptQuery) -> NodeResult:
        from opensearch_tpu.script import default_script_service

        ast, params = default_script_service.compile(node.script)
        live_host = np.asarray(self.dev.live)[: self.host.n_docs]
        mask = np.zeros(self.dev.n_pad, bool)
        ms = self.ctx.mapper_service
        for d in np.nonzero(live_host)[0]:
            out = default_script_service.field(ast, params, self.host, int(d), ms)
            if out:
                mask[d] = True
        return _const_result(jnp.asarray(mask), node.boost, scoring=True)

    # -- multi-term (term-enumeration) queries -----------------------------
    # The reference rewrites these to constant-score over the matching term
    # set (MultiTermQuery CONSTANT_SCORE_REWRITE); here the term dictionary
    # walk happens host-side (same place Lucene's FST walk runs) and only
    # the final doc mask touches the device.

    def _host_mask_for_terms(self, field: str, match_fn) -> np.ndarray:
        mask = np.zeros(self.dev.n_pad, bool)
        host_tf = self.host.text_fields.get(field)
        if host_tf is not None:
            for tid, term in enumerate(host_tf.terms):
                if match_fn(term):
                    off = int(host_tf.term_offsets[tid])
                    end = int(host_tf.term_offsets[tid + 1])
                    mask[host_tf.postings_docs[off:end]] = True
        kf = self.host.keyword_fields.get(field)
        if kf is not None:
            mask |= self._keyword_host_mask(
                kf, [o for o, v in enumerate(kf.ord_values) if match_fn(v)])
        return mask

    def _keyword_host_mask(self, kf, ords) -> np.ndarray:
        """bool [n_pad] on the host: the docs of this segment that hold any
        of `ords` (a collection of ordinals, or a `range` of them) in the
        keyword field `kf`, from those ordinals' posting lists alone."""
        mask, postings = filters.keyword_mask_from_postings(
            _keyword_postings(kf), ords, self.dev.n_pad)
        self.postings += postings
        return mask

    def _keyword_mask(self, kf, ords) -> jnp.ndarray:
        """`_keyword_host_mask`, uploaded and cut to the live docs."""
        return jnp.asarray(self._keyword_host_mask(kf, ords)) & self.dev.live

    def _multi_term_result(self, field: str, match_fn, boost: float) -> NodeResult:
        mask = jnp.asarray(self._host_mask_for_terms(field, match_fn)) & self.dev.live
        return _const_result(mask, boost, scoring=True)

    def _exec_PrefixQuery(self, node: q.PrefixQuery) -> NodeResult:
        if self.ctx.mapper_service.field_mapper(node.field) is None:
            flat = self.ctx.mapper_service.flat_object_parent(node.field)
            if flat is not None:
                root, subpath = flat
                return self._exec_PrefixQuery(q.PrefixQuery(
                    field=f"{root}#paths", value=f"{subpath}={node.value}",
                    case_insensitive=node.case_insensitive,
                    boost=node.boost,
                ))
        prefix = self._normalize_kw(node.field, node.value)
        prefix = prefix.lower() if node.case_insensitive else prefix
        if node.case_insensitive:
            return self._multi_term_result(
                node.field, lambda t: t.lower().startswith(prefix), node.boost
            )
        return self._multi_term_result(
            node.field, lambda t: t.startswith(prefix), node.boost
        )

    def _exec_WildcardQuery(self, node: q.WildcardQuery) -> NodeResult:
        if self.ctx.mapper_service.field_mapper(node.field) is None:
            flat = self.ctx.mapper_service.flat_object_parent(node.field)
            if flat is not None:
                root, subpath = flat
                return self._exec_WildcardQuery(q.WildcardQuery(
                    field=f"{root}#paths", value=f"{subpath}={node.value}",
                    case_insensitive=node.case_insensitive,
                    boost=node.boost,
                ))
        wc_value = self._normalize_kw(node.field, node.value)
        m_wc = self.ctx.mapper_service.field_mapper(node.field)
        if m_wc is not None and m_wc.type == "text":
            # wildcard patterns normalize through the analyzer chain
            # (lowercase) like the classic parser's multi-term handling
            wc_value = wc_value.lower()
        rx = _wildcard_to_regex(wc_value, node.case_insensitive)
        return self._multi_term_result(
            node.field, lambda t: rx.match(t) is not None, node.boost
        )

    def _exec_RegexpQuery(self, node: q.RegexpQuery) -> NodeResult:
        value = self._normalize_kw(node.field, node.value)
        m = self.ctx.mapper_service.field_mapper(node.field)
        if m is not None and m.type == "text":
            # analyzed text is lowercased; the classic parser normalizes
            # multi-term patterns through the analyzer chain
            value = value.lower()
        node = q.RegexpQuery(field=node.field, value=value,
                             case_insensitive=node.case_insensitive,
                             boost=node.boost)
        if len(node.value) > 1000:
            raise IllegalArgumentException(
                f"The length of regex [{len(node.value)}] used in the "
                f"Regexp Query request has exceeded the allowed maximum "
                f"of [1000]. This maximum can be set by changing the "
                f"[index.max_regex_length] index level setting."
            )
        try:
            rx = re.compile(
                node.value, re.IGNORECASE if node.case_insensitive else 0
            )
        except re.error as e:
            raise IllegalArgumentException(f"invalid regexp [{node.value}]: {e}")
        return self._multi_term_result(
            node.field, lambda t: rx.fullmatch(t) is not None, node.boost
        )

    def _exec_FuzzyQuery(self, node: q.FuzzyQuery) -> NodeResult:
        value = node.value
        max_d = _fuzziness_distance(node.fuzziness, value)
        plen = node.prefix_length

        def match(t: str) -> bool:
            if plen and t[:plen] != value[:plen]:
                return False
            if abs(len(t) - len(value)) > max_d:
                return False
            return _edit_distance_at_most(value, t, max_d)

        return self._multi_term_result(node.field, match, node.boost)

    def _exec_MatchPhrasePrefixQuery(self, node: q.MatchPhrasePrefixQuery) -> NodeResult:
        terms = self.ctx.mapper_service.analyze_query_text(node.field, node.query)
        if not terms:
            return _empty(self.dev)
        *body_terms, last = terms
        result = None
        if body_terms:
            r, counts = self._bm25(node.field, body_terms, node.boost)
            result = NodeResult(r.scores, counts >= len(body_terms), True)
        # expand the final term as a prefix (bounded by max_expansions, like
        # MatchPhrasePrefixQuery's MultiPhrasePrefixQuery expansion)
        expansions = 0

        def match(t: str) -> bool:
            nonlocal expansions
            if expansions >= node.max_expansions:
                return False
            if t.startswith(last):
                expansions += 1
                return True
            return False

        prefix_mask = jnp.asarray(self._host_mask_for_terms(node.field, match))
        if result is None:
            return _const_result(prefix_mask & self.dev.live, node.boost, True)
        mask = result.mask & prefix_mask & self.dev.live
        return NodeResult(jnp.where(mask, result.scores, 0.0), mask, True)

    def _exec_MatchBoolPrefixQuery(self, node: q.MatchBoolPrefixQuery) -> NodeResult:
        if node.analyzer:
            terms = self.ctx.mapper_service.analysis.get(node.analyzer).analyze(
                node.query
            )
        else:
            terms = self.ctx.mapper_service.analyze_query_text(node.field, node.query)
        if not terms:
            return _empty(self.dev)
        *body_terms, last = terms

        def term_clause(t: str) -> q.QueryNode:
            if node.fuzziness is not None:
                return q.FuzzyQuery(field=node.field, value=t,
                                    fuzziness=node.fuzziness)
            return q.TermQuery(field=node.field, value=t)

        subs: list[q.QueryNode] = [term_clause(t) for t in body_terms]
        subs.append(q.PrefixQuery(field=node.field, value=last))
        if node.operator == "and":
            return self._exec_BoolQuery(q.BoolQuery(must=subs, boost=node.boost))
        msm = node.minimum_should_match
        if msm is not None:
            try:
                msm = int(str(msm).rstrip("%"))
                if str(node.minimum_should_match).endswith("%"):
                    msm = max(1, (len(subs) * msm) // 100)
            except ValueError:
                msm = None
        return self._exec_BoolQuery(
            q.BoolQuery(should=subs, minimum_should_match=msm, boost=node.boost)
        )

    # -- query-string family ----------------------------------------------

    def _exec_QueryStringQuery(self, node: q.QueryStringQuery) -> NodeResult:
        r = self.execute(self.ctx.rewritten_query_string(node))
        return NodeResult(r.scores * node.boost, r.mask, r.scoring)

    def _exec_SimpleQueryStringQuery(self, node: q.SimpleQueryStringQuery) -> NodeResult:
        r = self.execute(self.ctx.rewritten_query_string(node))
        return NodeResult(r.scores * node.boost, r.mask, r.scoring)

    # -- compound scoring queries ------------------------------------------

    def _exec_BoostingQuery(self, node: q.BoostingQuery) -> NodeResult:
        pos = self.execute(node.positive)
        neg = self.execute(node.negative)
        scores = jnp.where(
            neg.mask, pos.scores * jnp.float32(node.negative_boost), pos.scores
        )
        return NodeResult(scores * node.boost, pos.mask, True)

    def _exec_DisMaxQuery(self, node: q.DisMaxQuery) -> NodeResult:
        if not node.queries:
            return _empty(self.dev)
        subs = [self.execute(sq) for sq in node.queries]
        mask = subs[0].mask
        best = subs[0].scores
        total = subs[0].scores
        for s in subs[1:]:
            mask = mask | s.mask
            best = jnp.maximum(best, s.scores)
            total = total + s.scores
        scores = best + jnp.float32(node.tie_breaker) * (total - best)
        return NodeResult(jnp.where(mask, scores, 0.0) * node.boost, mask, True)

    def _exec_NestedQuery(self, node: q.NestedQuery) -> NodeResult:
        # Flattened semantics: arrays of objects were indexed as multi-valued
        # dotted columns, so the inner query already addresses path.field.
        r = self.execute(node.query)
        return NodeResult(r.scores * node.boost, r.mask, r.scoring)

    def _exec_MoreLikeThisQuery(self, node: q.MoreLikeThisQuery) -> NodeResult:
        return self.execute(self.ctx.mlt_rewrite(node))

    def _seg_index(self) -> int:
        for i, (host, _dev) in enumerate(self.ctx.snapshot.segments):
            if host is self.host:
                return i
        return 0

    def _exec_PercolateQuery(self, node: q.PercolateQuery) -> NodeResult:
        mask_host = self.ctx.percolate_masks(node)[self._seg_index()]
        mask = jnp.asarray(mask_host) & self.dev.live
        return _const_result(mask, node.boost, scoring=True)

    def _exec_HasChildQuery(self, node: q.HasChildQuery) -> NodeResult:
        mask_host = self.ctx.join_masks(node)[self._seg_index()]
        mask = jnp.asarray(mask_host) & self.dev.live
        return _const_result(mask, node.boost, scoring=True)

    def _exec_HasParentQuery(self, node: q.HasParentQuery) -> NodeResult:
        mask_host = self.ctx.join_masks(node)[self._seg_index()]
        mask = jnp.asarray(mask_host) & self.dev.live
        return _const_result(mask, node.boost, scoring=True)

    def _exec_ParentIdQuery(self, node: q.ParentIdQuery) -> NodeResult:
        mask_host = self.ctx.join_masks(node)[self._seg_index()]
        mask = jnp.asarray(mask_host) & self.dev.live
        return _const_result(mask, node.boost, scoring=True)

    def _exec_HybridQuery(self, node: q.HybridQuery) -> NodeResult:
        # Executor-level fallback (no search pipeline): max combination.
        # The service runs sub-queries separately when a normalization
        # pipeline is active (see search/pipeline.py).
        return self._exec_DisMaxQuery(
            q.DisMaxQuery(queries=node.queries, tie_breaker=0.0, boost=node.boost)
        )

    def _exec_FunctionScoreQuery(self, node: q.FunctionScoreQuery) -> NodeResult:
        base = self.execute(node.query)
        n_pad = self.dev.n_pad
        fvals: list[tuple[jnp.ndarray, jnp.ndarray]] = []  # (value, applies-mask)
        for fn in node.functions:
            applies = base.mask
            if fn.filter is not None:
                applies = applies & self.execute(fn.filter).mask
            val = self._function_value(fn)
            if fn.weight is not None:
                val = val * jnp.float32(fn.weight)
            fvals.append((val, applies))

        if not fvals:
            factor = jnp.ones(n_pad, jnp.float32)
        else:
            mode = node.score_mode
            if mode == "first":
                factor = jnp.ones(n_pad, jnp.float32)
                assigned = jnp.zeros(n_pad, bool)
                for val, applies in fvals:
                    take = applies & ~assigned
                    factor = jnp.where(take, val, factor)
                    assigned = assigned | applies
            elif mode in ("sum", "avg"):
                total = jnp.zeros(n_pad, jnp.float32)
                cnt = jnp.zeros(n_pad, jnp.float32)
                for val, applies in fvals:
                    total = total + jnp.where(applies, val, 0.0)
                    cnt = cnt + applies.astype(jnp.float32)
                factor = jnp.where(cnt > 0, total, 1.0)
                if mode == "avg":
                    factor = jnp.where(cnt > 0, total / jnp.maximum(cnt, 1.0), 1.0)
            elif mode in ("max", "min"):
                init = jnp.full(n_pad, -jnp.inf if mode == "max" else jnp.inf, jnp.float32)
                acc = init
                for val, applies in fvals:
                    pick = jnp.maximum if mode == "max" else jnp.minimum
                    acc = jnp.where(applies, pick(acc, val), acc)
                factor = jnp.where(jnp.isfinite(acc), acc, 1.0)
            else:  # multiply (default)
                factor = jnp.ones(n_pad, jnp.float32)
                for val, applies in fvals:
                    factor = factor * jnp.where(applies, val, 1.0)
        if np.isfinite(node.max_boost):
            factor = jnp.minimum(factor, jnp.float32(node.max_boost))

        qs = base.scores
        bm = node.boost_mode
        if bm == "replace":
            scores = factor
        elif bm == "sum":
            scores = qs + factor
        elif bm == "avg":
            scores = (qs + factor) / 2.0
        elif bm == "max":
            scores = jnp.maximum(qs, factor)
        elif bm == "min":
            scores = jnp.minimum(qs, factor)
        else:  # multiply
            scores = qs * factor
        mask = base.mask
        if node.min_score is not None:
            mask = mask & (scores >= jnp.float32(node.min_score))
        scores = jnp.where(mask, scores, 0.0) * node.boost
        return NodeResult(scores, mask, True)

    def _function_value(self, fn: q.ScoreFunction) -> jnp.ndarray:
        n_pad = self.dev.n_pad
        if fn.kind == "weight":
            return jnp.ones(n_pad, jnp.float32)
        if fn.kind == "random_score":
            # deterministic per-doc hash (reference: seeded random_score)
            idx = jnp.arange(n_pad, dtype=jnp.uint32)
            h = (idx * jnp.uint32(2654435761) + jnp.uint32(fn.seed * 40503 + 1)) & jnp.uint32(0x7FFFFFFF)
            return h.astype(jnp.float32) / jnp.float32(0x7FFFFFFF)
        if fn.kind == "field_value_factor":
            vals, present = self._numeric_doc_values(fn.field)
            if fn.missing is not None:
                vals = jnp.where(present, vals, jnp.float32(fn.missing))
            else:
                vals = jnp.where(present, vals, 1.0)
            v = vals * jnp.float32(fn.factor)
            m = fn.modifier
            if m == "log":
                v = jnp.log10(jnp.maximum(v, 1e-9))
            elif m == "log1p":
                v = jnp.log10(v + 1.0)
            elif m == "log2p":
                v = jnp.log10(v + 2.0)
            elif m == "ln":
                v = jnp.log(jnp.maximum(v, 1e-9))
            elif m == "ln1p":
                v = jnp.log1p(v)
            elif m == "ln2p":
                v = jnp.log(v + 2.0)
            elif m == "square":
                v = v * v
            elif m == "sqrt":
                v = jnp.sqrt(jnp.maximum(v, 0.0))
            elif m == "reciprocal":
                v = 1.0 / jnp.maximum(v, 1e-9)
            return v
        if fn.kind == "decay":
            mapper = self.ctx.mapper_service.field_mapper(fn.field)
            is_date = mapper is not None and mapper.type == "date"
            if is_date:
                origin = float(parse_date_millis(fn.origin)) if fn.origin is not None else 0.0
                scale = float(_duration_millis(fn.scale))
                offset = float(_duration_millis(fn.offset)) if fn.offset else 0.0
            else:
                origin = float(fn.origin if fn.origin is not None else 0.0)
                scale = float(fn.scale)
                offset = float(fn.offset or 0.0)
            vals, present = self._numeric_doc_values(fn.field)
            dist = jnp.maximum(jnp.abs(vals - jnp.float32(origin)) - jnp.float32(offset), 0.0)
            if fn.decay_type == "gauss":
                sigma2 = -(scale**2) / (2.0 * np.log(fn.decay))
                out = jnp.exp(-(dist**2) / jnp.float32(2 * sigma2))
            elif fn.decay_type == "exp":
                lam = np.log(fn.decay) / scale
                out = jnp.exp(jnp.float32(lam) * dist)
            else:  # linear
                s = scale / (1.0 - fn.decay)
                out = jnp.maximum(
                    (jnp.float32(s) - dist) / jnp.float32(s), 0.0
                )
            return jnp.where(present, out, 1.0)
        raise IllegalArgumentException(f"unknown score function [{fn.kind}]")

    def _numeric_doc_values(self, field: str) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(float32 values, present) for a numeric/date field on this segment."""
        nf_dev = self.dev.numeric_fields.get(field)
        nf_host = self.host.numeric_fields.get(field)
        if nf_dev is None or nf_host is None:
            z = jnp.zeros(self.dev.n_pad, jnp.float32)
            return z, jnp.zeros(self.dev.n_pad, bool)
        if nf_host.kind == "int":
            vals = np.zeros(self.dev.n_pad, np.float32)
            vals[: self.host.n_docs] = nf_host.values_i64.astype(np.float64)[: self.host.n_docs]
        else:
            vals = np.zeros(self.dev.n_pad, np.float32)
            vals[: self.host.n_docs] = nf_host.values_f64[: self.host.n_docs]
        return jnp.asarray(vals), nf_dev.present


def _wildcard_to_regex(pattern: str, case_insensitive: bool) -> "re.Pattern":
    out = []
    for ch in pattern:
        if ch == "*":
            out.append(".*")
        elif ch == "?":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("".join(out) + r"\Z", re.IGNORECASE if case_insensitive else 0)


def _fuzziness_distance(fuzziness: str, term: str) -> int:
    f = str(fuzziness).upper()
    if f == "AUTO":
        n = len(term)
        return 0 if n < 3 else (1 if n <= 5 else 2)
    try:
        return int(f)
    except ValueError:
        raise IllegalArgumentException(f"invalid fuzziness [{fuzziness}]")


def _edit_distance_at_most(a: str, b: str, max_d: int) -> bool:
    """OSA (Damerau-Levenshtein with adjacent transpositions = 1 edit) with
    early exit — fuzzy queries default to transpositions=true like Lucene's
    LevenshteinAutomata(..., transpositions)."""
    if max_d == 0:
        return a == b
    la, lb = len(a), len(b)
    prev2: list[int] | None = None
    prev = list(range(lb + 1))
    for i in range(1, la + 1):
        cur = [i] + [0] * lb
        row_min = i
        for j in range(1, lb + 1):
            cost = 0 if a[i - 1] == b[j - 1] else 1
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + cost)
            if (prev2 is not None and i > 1 and j > 1
                    and a[i - 1] == b[j - 2] and a[i - 2] == b[j - 1]):
                cur[j] = min(cur[j], prev2[j - 2] + 1)
            row_min = min(row_min, cur[j])
        if row_min > max_d:
            return False
        prev2, prev = prev, cur
    return prev[lb] <= max_d


def _try_ip(value: str):
    import ipaddress

    try:
        return ipaddress.ip_address(value)
    except ValueError:
        return None


def _parse_geo_origin(origin: Any) -> tuple[float, float]:
    """(lat, lon) from the geo_point literal forms."""
    if isinstance(origin, dict) and "lat" in origin and "lon" in origin:
        return float(origin["lat"]), float(origin["lon"])
    if isinstance(origin, list) and len(origin) >= 2:
        return float(origin[1]), float(origin[0])  # [lon, lat]
    if isinstance(origin, str) and "," in origin:
        parts = origin.split(",")
        return float(parts[0]), float(parts[1])
    raise IllegalArgumentException(f"invalid geo origin [{origin!r}]")


def _parse_distance_meters(v: Any) -> float:
    """"5km" / "500m" / "1mi" ... -> meters (DistanceUnit)."""
    if isinstance(v, (int, float)):
        return float(v)
    m = re.fullmatch(
        r"\s*(\d+(?:\.\d+)?)\s*(mm|cm|m|km|mi|miles|yd|ft|in|nmi|NM)\s*",
        str(v),
    )
    if not m:
        raise IllegalArgumentException(f"invalid distance [{v}]")
    mult = {"mm": 0.001, "cm": 0.01, "m": 1.0, "km": 1000.0,
            "mi": 1609.344, "miles": 1609.344, "yd": 0.9144,
            "ft": 0.3048, "in": 0.0254, "nmi": 1852.0, "NM": 1852.0}
    return float(m.group(1)) * mult[m.group(2)]


def _haversine_m(lat1: float, lon1: float, lat2, lon2):
    """Great-circle distance in meters (GeoUtils.arcDistance)."""
    r = 6371008.8
    p1, p2 = np.radians(lat1), np.radians(lat2)
    dp = p2 - p1
    dl = np.radians(lon2) - np.radians(lon1)
    a = np.sin(dp / 2.0) ** 2 + np.cos(p1) * np.cos(p2) * np.sin(dl / 2.0) ** 2
    return 2.0 * r * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))


def _parse_date_or_now(v: Any) -> int:
    """Date literal or date-math anchored at now ("now", "now-7d")."""
    import time as _time

    s = str(v).strip() if not hasattr(v, "isoformat") else v.isoformat()
    if s.startswith("now"):
        base = int(_time.time() * 1000)
        rest = s[3:]
        if not rest:
            return base
        sign = 1 if rest[0] == "+" else -1
        return base + sign * _duration_millis(rest[1:].split("/")[0])
    return parse_date_millis(v)


def _duration_millis(v: Any) -> int:
    """Parse a date-math duration like "10d", "2h", "30m" to milliseconds."""
    if isinstance(v, (int, float)):
        return int(v)
    m = re.fullmatch(
        r"(\d+(?:\.\d+)?)(nanos|micros|ms|s|m|h|d|w)", str(v).strip()
    )
    if not m:
        raise IllegalArgumentException(f"invalid duration [{v}]")
    n = float(m.group(1))
    mult = {"nanos": 1e-6, "micros": 1e-3, "ms": 1, "s": 1000, "m": 60_000,
            "h": 3_600_000, "d": 86_400_000, "w": 604_800_000}[m.group(2)]
    return int(n * mult) if m.group(2) not in ("nanos", "micros") \
        else n * mult


# --------------------------------------------------------------------------
# Shard-level query phase
# --------------------------------------------------------------------------


@dataclass
class ShardHit:
    score: float
    segment: int          # index into snapshot.segments
    doc: int              # local doc id
    sort_values: list = dc_field(default_factory=list)


@dataclass
class ShardQueryResult:
    hits: list[ShardHit]
    total: int
    max_score: float | None
    # per-segment match masks (host bool arrays) for the aggs phase
    masks: list[np.ndarray] = dc_field(default_factory=list)
    # per-segment score arrays (host f32, n_docs) — kept alongside the masks
    # so score-dependent aggregations (top_hits, sampler, scripted_metric)
    # see the query-phase scores
    score_arrays: list[np.ndarray] = dc_field(default_factory=list)


def execute_query_phase(
    snapshot: SearcherSnapshot,
    mapper_service: MapperService,
    query_node: q.QueryNode,
    size: int,
    sort: list[dict] | None = None,
    need_masks: bool = False,
    min_score: float | None = None,
) -> ShardQueryResult:
    ctx = ShardContext(snapshot, mapper_service)
    with _bm25_span(query_node) as scored:
        result = _query_phase(ctx, query_node, size, sort, need_masks,
                              min_score)
        for key, value in ctx.bm25.items():
            scored.set_attribute(key, value)
    return result


def _query_phase(
    ctx: ShardContext,
    query_node: q.QueryNode,
    size: int,
    sort: list[dict] | None,
    need_masks: bool,
    min_score: float | None,
) -> ShardQueryResult:
    snapshot, mapper_service = ctx.snapshot, ctx.mapper_service
    masks: list[np.ndarray] = []
    score_arrays: list[np.ndarray] = []
    total = 0
    max_score: float | None = None
    all_hits: list[ShardHit] = []

    for seg_idx, (host, dev) in enumerate(snapshot.segments):
        ex = SegmentExecutor(ctx, host, dev)
        result = ex.execute(query_node)
        if isinstance(result, HostNodeResult) and not sort:
            # host fast path (bare kNN): the selection is already the
            # shard-level top-k cut, computed against the SNAPSHOT's device
            # live mask: total, hits and max_score come from its <= k
            # winners. Only aggregations (need_masks) index by document
            prof = profile.active()
            t_collect = time.perf_counter_ns()
            with tracing.detail(span_names.SEARCH_COLLECT) as collect:
                docs, scores = result.docs, result.doc_scores
                if min_score is not None:
                    keep = scores >= np.float32(min_score)
                    docs, scores = docs[keep], scores[keep]
                if need_masks:
                    mask_h = result.host_mask
                    if min_score is not None:
                        mask_h = mask_h & (
                            result.host_scores >= np.float32(min_score))
                    masks.append(mask_h[: host.n_docs])
                    score_arrays.append(result.host_scores[: host.n_docs])
                total += len(docs)
                if size > 0:
                    for v, d in zip(scores.tolist(), docs.tolist()):
                        all_hits.append(ShardHit(v, seg_idx, d))
                        if max_score is None or v > max_score:
                            max_score = v
                collect.set_attribute("dense", int(ctx.knn_dense))
            if prof is not None:
                prof.collect_ns += time.perf_counter_ns() - t_collect
            continue
        mask = result.mask & dev.live
        if min_score is not None:
            # min_score excludes docs from hits AND total (reference:
            # QueryPhase applies MinScoreCollectorContext before counting)
            mask = mask & (result.scores >= jnp.float32(min_score))
        mask_host = np.asarray(mask)[: host.n_docs]
        if need_masks:
            masks.append(mask_host)
            score_arrays.append(np.asarray(result.scores)[: host.n_docs])
        total += int(mask_host.sum())
        prof = profile.active()
        t_collect = time.perf_counter_ns()
        if size > 0:
            if not sort:
                k = min(size, dev.n_pad)
                masked = jnp.where(mask, result.scores, -jnp.inf)
                from opensearch_tpu.ops.topk import segment_top_k

                vals, ids = segment_top_k(masked, k)
                vals_h, ids_h = np.asarray(vals), np.asarray(ids)
                for v, d in zip(vals_h, ids_h):
                    if np.isfinite(v):
                        all_hits.append(ShardHit(float(v), seg_idx, int(d)))
                        if max_score is None or v > max_score:
                            max_score = float(v)
            else:
                scores_h = np.asarray(result.scores)[: host.n_docs]
                all_hits.extend(
                    _sorted_segment_hits(
                        host, mask_host, scores_h, sort, size, seg_idx, mapper_service
                    )
                )
        if prof is not None:
            # the top-k cut / field sort is this engine's collector
            prof.collect_ns += time.perf_counter_ns() - t_collect

    if ctx._knn_cache and not ctx.knn_dense:
        count_metric("knn.collect.sparse")
    t_final = time.perf_counter_ns()
    if not sort:
        all_hits.sort(key=lambda h: (-h.score, h.segment, h.doc))
        all_hits = all_hits[:size]
    else:
        all_hits.sort(key=_sort_key_fn(sort))
        all_hits = all_hits[:size]
    final_prof = profile.active()
    if final_prof is not None:
        final_prof.collect_ns += time.perf_counter_ns() - t_final
    return ShardQueryResult(
        hits=all_hits, total=total, max_score=max_score, masks=masks,
        score_arrays=score_arrays,
    )


def _field_sort_values(
    host: HostSegment, field: str, docs: np.ndarray,
    mapper_service: MapperService, mode: str | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """(values float64/int64, present bool) for the requested docs. A field
    absent from this whole segment means every doc's value is missing (the
    reference sorts those by the `missing` policy rather than erroring).
    `mode` picks the multi-value reduction (SortedNumericSortField's
    min/max/sum/avg/median; default min asc / max desc chosen by caller)."""
    nf = host.numeric_fields.get(field)
    if nf is not None:
        mapper = mapper_service.field_mapper(field)
        unsigned = mapper is not None and \
            getattr(mapper, "original_type", None) == "unsigned_long"
        vals = nf.values_i64 if nf.kind == "int" else nf.values_f64
        if unsigned:
            # unbias in exact python-int space (np int64 would overflow)
            def _avg_exact(vv):
                # unsigned_long reduces in BigInteger space: exact
                # half-up rounding (the reference's unsigned sort values)
                s_ = sum(vv)
                n_ = len(vv)
                return (2 * s_ + n_) // (2 * n_)

            def _median_exact(vv):
                sv = sorted(vv)
                n_ = len(sv)
                if n_ % 2:
                    return sv[n_ // 2]
                return (sv[n_ // 2 - 1] + sv[n_ // 2] + 1) // 2

            def _sum_wrap(vv):
                # unsigned sums wrap at 2^64
                return sum(vv) % 2**64

            red = {"min": min, "max": max, "sum": _sum_wrap,
                   "avg": _avg_exact,
                   "median": _median_exact,
                   }.get(mode or "min", min)
            out = np.empty(len(docs), dtype=object)
            for i, d in enumerate(docs):
                if nf.present[d]:
                    vv = [int(x) + 2**63 for x in nf.doc_values(int(d))]
                    out[i] = red(vv) if vv else 0
                else:
                    out[i] = 0
            return out, nf.present[docs]
        if mode and nf.mv_offsets is not None:
            is_int = nf.kind == "int"

            def _sum(a):
                if not is_int:
                    return np.sum(a)
                return sum(int(x) for x in a)  # exact python-int sum

            def _avg(a):
                if not is_int:
                    return float(np.mean(a))
                # long avg: exact sum -> double -> truncate back to long
                # (the reference's double cast)
                return int(float(_sum(a)) / len(a))

            def _median(a):
                sa = np.sort(a)
                n_ = len(sa)
                if n_ % 2:
                    return sa[n_ // 2]
                lo_, hi_ = sa[n_ // 2 - 1], sa[n_ // 2]
                if not is_int:
                    return (float(lo_) + float(hi_)) / 2.0
                return int(float(int(lo_) + int(hi_)) / 2.0)

            red = {"min": np.min, "max": np.max, "sum": _sum,
                   "avg": _avg, "median": _median}.get(mode, np.min)
            out = np.array([
                red(nf.doc_values(int(d))) if nf.present[d] else 0
                for d in docs
            ])
            return out, nf.present[docs]
        return vals[docs], nf.present[docs]
    kf = host.keyword_fields.get(field)
    if kf is not None:
        # ordinal sort within a segment is NOT globally consistent across
        # segments; use the string values for cross-segment correctness
        ords = kf.first_ord[docs]
        return ords, ords >= 0
    return np.zeros(len(docs)), np.zeros(len(docs), bool)


def _sorted_segment_hits(
    host: HostSegment,
    mask: np.ndarray,
    scores: np.ndarray,
    sort: list[dict],
    size: int,
    seg_idx: int,
    mapper_service: MapperService,
) -> list[ShardHit]:
    docs = np.nonzero(mask)[0]
    if len(docs) == 0:
        return []
    hits = []
    sort_cols = []
    for spec in sort:
        fname, order, _missing = _sort_spec(spec)
        if fname == "_score":
            sort_cols.append((scores[docs], np.ones(len(docs), bool), order, None))
        elif fname in ("_doc", "_shard_doc"):
            sort_cols.append((docs.astype(np.float64), np.ones(len(docs), bool), order, None))
        else:
            spec_conf = spec if isinstance(spec, dict) else {}
            conf = spec_conf.get(fname) if isinstance(spec_conf.get(fname), dict) else {}
            mode = conf.get("mode") or ("max" if order == "desc" else "min")
            vals, present = _field_sort_values(host, fname, docs,
                                               mapper_service, mode=mode)
            kf = host.keyword_fields.get(fname)
            sort_cols.append((vals, present, order, kf.ord_values if kf is not None else None))
    for i, d in enumerate(docs):
        sv = []
        for col_i, (vals, present, order, ord_values) in enumerate(sort_cols):
            if not present[i]:
                sv.append(None)
            elif ord_values is not None:
                sv.append(ord_values[int(vals[i])])
            else:
                v = vals[i]
                out_v = (int(v) if isinstance(v, (np.integer, int))
                         else float(v))
                sv.append(out_v)
        hits.append(ShardHit(float(scores[d]), seg_idx, int(d), sort_values=sv))
    keys = _sort_key_fn(sort)
    hits.sort(key=keys)
    return hits[:size]


def _sort_spec(spec: dict | str) -> tuple[str, str, Any]:
    if isinstance(spec, str):
        return spec, ("desc" if spec == "_score" else "asc"), None
    if len(spec) != 1:
        raise ParsingException("each sort entry must have a single field")
    fname, conf = next(iter(spec.items()))
    if isinstance(conf, str):
        return fname, conf, None
    return fname, conf.get("order", "desc" if fname == "_score" else "asc"), conf.get("missing")


def _sort_key_fn(sort: list[dict]):
    specs = [_sort_spec(s) for s in sort]

    def key(hit: ShardHit):
        parts = []
        for i, (fname, order, missing) in enumerate(specs):
            if fname == "_score":
                v = hit.score
                parts.append(-v if order == "desc" else v)
                continue
            if fname == "_doc":
                parts.append((hit.segment, hit.doc) if order == "asc" else (-hit.segment, -hit.doc))
                continue
            v = hit.sort_values[i] if i < len(hit.sort_values) else None
            if v is None and missing not in (None, "_last", "_first"):
                v = missing  # substitute the user-provided missing value
            if v is None:
                # _last (default): sorts after every real value in either
                # order; _first: before
                parts.append((-1, 0) if missing == "_first" else (1, 0))
            elif isinstance(v, str):
                # desc string order via a reflected-comparison wrapper
                parts.append((0, _StrKey(v, order == "desc")))
            else:
                parts.append((0, -v if order == "desc" else v))
        parts.append((hit.segment, hit.doc))
        return tuple(parts)

    return key


class _StrKey:
    __slots__ = ("v", "desc")

    def __init__(self, v: str, desc: bool):
        self.v = v
        self.desc = desc

    def __lt__(self, other: "_StrKey") -> bool:
        return (self.v > other.v) if self.desc else (self.v < other.v)

    def __eq__(self, other: object) -> bool:
        return isinstance(other, _StrKey) and self.v == other.v
