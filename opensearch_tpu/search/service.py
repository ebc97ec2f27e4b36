"""Search service: query phase -> reduce -> fetch phase -> response.

The single-host analog of the coordinator pipeline (SURVEY.md §3.2):
TransportSearchAction fan-out → per-shard QueryPhase →
SearchPhaseController.reducedQueryPhase (merge top docs + aggs) →
FetchSearchPhase (fetch only winning doc ids) → final SearchResponse merge.

Here the per-shard query phase runs the device executor; the reduce is a
host merge with the exact OpenSearch tie-break (score desc, shard asc, doc
asc); aggregations reduce across all shards' segments in one pass. The
multi-chip path (parallel/) replaces the host merge with an on-device
all_gather + top_k over the mesh.
"""

from __future__ import annotations

import fnmatch
import json
import logging
import time
from typing import Any

import numpy as np

from opensearch_tpu.common.errors import (
    IllegalArgumentException,
    ParsingException,
)
from opensearch_tpu.index.shard import IndexShard
from opensearch_tpu.search import fetch, profile as search_profile, query_dsl

logger = logging.getLogger(__name__)
from opensearch_tpu.search.aggs import compute_aggs
from opensearch_tpu.telemetry import spans as span_names
from opensearch_tpu.telemetry import tracing
from opensearch_tpu.search.executor import (
    SegmentExecutor,
    ShardContext,
    ShardQueryResult,
    _sort_key_fn,
    _sort_spec,
    _StrKey,
    count_metric,
    execute_query_phase,
)

DEFAULT_SIZE = 10


def pack_shard_doc(shard_idx: int, segment: int, doc: int) -> int:
    """_shard_doc PIT tiebreak value: (shard, segment, doc) packed into one
    orderable int that round-trips through search_after cursors.

    Bit layout 13/13/27 (shard/segment/doc): doc must clear 2^21 (~2.1M
    docs/segment corpora are in BASELINE scope) and the TOTAL must stay
    under 2^53 so float64 JSON clients echo the cursor exactly — 32 shards
    at a 48-bit shard shift would already cross 2^53.
    """
    return (shard_idx << 40) | (segment << 27) | doc


def _sort_has_score(sort) -> bool:
    return any(
        (spec if isinstance(spec, str) else next(iter(spec), None)) == "_score"
        for spec in (sort or [])
    )


def search(
    shards: list[IndexShard],
    body: dict | None,
    acquired: list | None = None,
    phase_results_config: dict | None = None,
    shard_filters: list | None = None,
    task=None,
    partial: bool = False,
    shard_numbers: list[int] | None = None,
    index_boosts: dict | None = None,
    precomputed_results: list | None = None,
) -> dict[str, Any]:
    """Run one search over `shards`. `acquired` optionally pins the searcher
    snapshots to use, one per shard in order — the scroll/PIT path
    (ReaderContext.java:64 analog: the context owns the snapshots, so pages
    see one immutable point-in-time view regardless of refreshes).

    `partial=True` produces a per-NODE wire partial for the cluster
    coordinator (QuerySearchResult analog): hits carry a `_tb` tie-break
    triple [global_shard, segment, doc] (global shard numbers supplied via
    `shard_numbers`), aggregations carry `_p_*` reduce extras, and pipeline
    aggregations are deferred to the coordinator's final reduce
    (search/reduce.py — InternalAggregations.reduce:162 semantics)."""
    # the phases below follow one another as sibling detail spans of the
    # caller's `search` span (no-ops outside a profiler session)
    phase = tracing.phases()
    try:
        return _search(
            phase, shards, body, acquired, phase_results_config,
            shard_filters, task, partial, shard_numbers, index_boosts,
            precomputed_results)
    finally:
        phase.close()


def _search(
    phase,
    shards: list[IndexShard],
    body: dict | None,
    acquired: list | None,
    phase_results_config: dict | None,
    shard_filters: list | None,
    task,
    partial: bool,
    shard_numbers: list[int] | None,
    index_boosts: dict | None,
    precomputed_results: list | None,
) -> dict[str, Any]:
    phase.enter(span_names.SEARCH_PARSE)
    t0 = time.monotonic()
    body = body or {}
    known_keys = {
        "query", "size", "from", "sort", "_source", "aggs", "aggregations",
        "track_total_hits", "min_score", "search_after", "timeout", "version",
        "seq_no_primary_term", "stored_fields", "explain", "highlight",
        "docvalue_fields", "fields", "script_fields", "suggest", "profile",
        "rescore", "collapse", "slice", "indices_boost",
        "include_named_queries_score", "pre_filter_shard_size",
        "stats",  # per-request stat groups (surfaced by indices.stats)
    }
    unknown = set(body) - known_keys
    if unknown:
        raise ParsingException(f"unknown search request keys {sorted(unknown)}")

    node = query_dsl.parse_query(body.get("query"))
    if body.get("slice") is not None:
        # sliced scroll: partition the doc space by murmur3(_id) % max
        # (search/slice/SliceBuilder.java)
        sl = body["slice"]
        sl_max = int(sl.get("max", 1))
        sl_id = int(sl.get("id", 0))
        if not 0 <= sl_id < sl_max:
            raise ParsingException(
                f"[slice.id] must be in [0, {sl_max}) but was {sl_id}"
            )
        node = query_dsl.BoolQuery(
            must=[node],
            filter=[query_dsl.SliceQuery(id=sl_id, max=sl_max)],
        )
    size = int(body.get("size", DEFAULT_SIZE))
    from_ = int(body.get("from", 0))
    sort = body.get("sort")
    if isinstance(sort, (str, dict)):
        sort = [sort]
    aggs_body = body.get("aggs") or body.get("aggregations")
    if aggs_body:
        from opensearch_tpu.search.aggs_pipeline import (
            validate_pipeline_aggs,
        )

        validate_pipeline_aggs(aggs_body)
    min_score = body.get("min_score")
    search_after = body.get("search_after")
    if search_after is not None and not sort:
        raise ParsingException("[search_after] requires [sort] to be set")
    if search_after is not None and from_ > 0:
        raise ParsingException(
            "[from] parameter must be set to 0 when [search_after] is used"
        )
    track_total = body.get("track_total_hits", True)

    # per-shard alias filters (the aliasFilter of ShardSearchRequest):
    # parse each distinct filter body once, AND it into that shard's query
    filter_nodes: list = [None] * len(shards)
    if shard_filters:
        parsed_cache: dict[int, Any] = {}
        for i, f in enumerate(shard_filters[: len(shards)]):
            if f is not None:
                key = id(f)
                if key not in parsed_cache:
                    parsed_cache[key] = query_dsl.parse_query(f)
                filter_nodes[i] = parsed_cache[key]

    def _shard_node(base: Any, shard_i: int) -> Any:
        f = filter_nodes[shard_i]
        if f is None:
            return base
        return query_dsl.BoolQuery(must=[base], filter=[f])

    want_profile = bool(body.get("profile"))
    shard_query_ns: list[int] = []
    # one deep profiler per shard (search/profile.ShardProfiler): operator
    # tree + device kernel time + transfer bytes + retrace flag
    shard_profilers: list = []
    skipped_shards = 0

    # set when the shard-mesh device path ran: the flat device-merged rows
    # (so the host re-sort below can be skipped) and launch attribution
    mesh_premerged: list | None = None
    mesh_launch: dict | None = None

    phase.enter(span_names.SEARCH_QUERY_PHASE).set_attribute(
        "sub_queries", len(node.queries)
        if isinstance(node, query_dsl.HybridQuery) else 0)
    fetch_k = from_ + size
    if body.get("rescore") is not None:
        # the query phase must collect the full rescore window
        stages = body["rescore"]
        stages = stages if isinstance(stages, list) else [stages]
        for stage in stages:
            if isinstance(stage, dict):
                fetch_k = max(fetch_k, int(stage.get("window_size", 10)))
    if isinstance(node, query_dsl.HybridQuery):
        # hybrid query phase: one pass per sub-query, then the phase-results
        # processor fuses scores GLOBALLY across shards before fetch (the
        # SearchPhaseResultsProcessor slot, search/pipeline/)
        if sort:
            raise ParsingException("[sort] is not supported with [hybrid] query")
        if search_after is not None:
            raise ParsingException(
                "[search_after] is not supported with [hybrid] query"
            )
        from opensearch_tpu.search import pipeline as pipeline_mod

        shard_snaps = []
        per_shard_subs = []
        for shard_i, shard in enumerate(shards):
            if task is not None:
                task.ensure_not_cancelled()
            snapshot = (
                acquired[shard_i] if acquired is not None
                else shard.acquire_searcher()
            )
            prof = search_profile.ShardProfiler() if want_profile else None
            t_q = time.perf_counter_ns()
            with search_profile.profiling(prof):
                per_shard_subs.append([
                    execute_query_phase(
                        snapshot,
                        shard.mapper_service,
                        _shard_node(sub, shard_i),
                        size=fetch_k,
                        need_masks=aggs_body is not None,
                        min_score=(
                            float(min_score) if min_score is not None else None
                        ),
                    )
                    for sub in node.queries
                ])
            if want_profile:
                shard_query_ns.append(time.perf_counter_ns() - t_q)
                shard_profilers.append(prof)
            shard_snaps.append((shard, snapshot))
        with tracing.detail(span_names.HYBRID_FUSE) as fusing:
            fused = pipeline_mod.fuse_hybrid_results(
                per_shard_subs, phase_results_config, fetch_k
            )
            fusing.set_attribute("sub_queries", len(node.queries))
            fusing.set_attribute("pooled", sum(
                len(res.hits) for subs in per_shard_subs for res in subs))
            fusing.set_attribute("shards", len(shards))
        count_metric("search.hybrid.requests")
        per_shard_results = [
            (shard, snap, res)
            for (shard, snap), res in zip(shard_snaps, fused)
        ]
    else:
        # a batched msearch dispatch may have already run the query phase
        # for this body (one device launch for B queries — see
        # try_batched_knn_msearch); inject its per-shard results and skip
        # straight to reduce/fetch
        per_shard_results = precomputed_results
        if per_shard_results is None:
            mesh_out = _try_distributed_query_phase(
                shards, acquired, node,
                sort=sort, search_after=search_after, aggs_body=aggs_body,
                min_score=min_score, filter_nodes=filter_nodes,
                want_profile=want_profile, fetch_k=fetch_k, task=task,
            )
            if mesh_out is not None:
                per_shard_results, mesh_premerged, mesh_launch = mesh_out
                if want_profile:
                    # per-shard attribution of the ONE sharded launch: each
                    # shard profiler carries its share of the fenced wall
                    # and the shared launch_id (profile.py)
                    desc = search_profile.describe_node(node)
                    qbytes = 4 * len(getattr(node, "vector", ()))
                    # a coalesced launch served `merged` queries: this
                    # query's share of the fenced wall is wall/merged (the
                    # executor path applies the same split via
                    # BatchOutcome.kernel_share_ns) — attributing the full
                    # wall to every member would read as merged x the real
                    # device time
                    query_wall_ns = (mesh_launch["wall_ns"]
                                     // max(mesh_launch.get("merged", 1), 1))
                    for _ in per_shard_results:
                        prof = search_profile.ShardProfiler()
                        prof.record_sharded_launch(
                            type(node).__name__, desc,
                            name="shard_mesh_knn",
                            launch_id=mesh_launch["launch_id"],
                            shards=mesh_launch["shards"],
                            wall_ns=query_wall_ns,
                            transfer_bytes=qbytes,
                            retraced=mesh_launch["retraced"],
                        )
                        shard_profilers.append(prof)
                        shard_query_ns.append(
                            query_wall_ns
                            // max(mesh_launch["shards"], 1)
                        )
        if per_shard_results is None:
            per_shard_results = []
            for shard_i, shard in enumerate(shards):
                # cooperative cancellation at the phase boundary — between
                # device program launches (TaskCancellationService model)
                if task is not None:
                    task.ensure_not_cancelled()
                snapshot = acquired[shard_i] if acquired is not None else shard.acquire_searcher()
                # can_match pre-filter (CanMatchPreFilterSearchPhase): skip
                # shards whose segment min/max PROVE no doc matches
                from opensearch_tpu.search import phases

                prof = (search_profile.ShardProfiler()
                        if want_profile else None)
                t_rw = time.perf_counter_ns()
                matched = phases.can_match(
                    snapshot, shard.mapper_service, _shard_node(node, shard_i)
                )
                if prof is not None:
                    # can_match is this engine's rewrite step
                    prof.rewrite_ns += time.perf_counter_ns() - t_rw
                if not matched:
                    n_segs = len(snapshot.segments)
                    result = ShardQueryResult(
                        hits=[], total=0, max_score=None,
                        masks=[
                            np.zeros(h.n_docs, bool)
                            for h, _d in snapshot.segments
                        ] if aggs_body is not None else [],
                        score_arrays=[
                            np.zeros(h.n_docs, np.float32)
                            for h, _d in snapshot.segments
                        ] if aggs_body is not None else [],
                    )
                    skipped_shards += 1
                    if want_profile:
                        shard_query_ns.append(0)
                        shard_profilers.append(prof)
                    per_shard_results.append((shard, snapshot, result))
                    continue
                t_q = time.perf_counter_ns()
                with search_profile.profiling(prof):
                    result = execute_query_phase(
                        snapshot,
                        shard.mapper_service,
                        _shard_node(node, shard_i),
                        # search_after cursors can reach arbitrarily deep into a
                        # shard; fall back to all matching docs per shard
                        size=snapshot.max_doc if search_after is not None else fetch_k,
                        sort=sort,
                        need_masks=aggs_body is not None,
                        min_score=float(min_score) if min_score is not None else None,
                    )
                if want_profile:
                    shard_query_ns.append(time.perf_counter_ns() - t_q)
                    shard_profilers.append(prof)
                per_shard_results.append((shard, snapshot, result))

    # ---- reduce phase (SearchPhaseController analog) ----
    phase.enter(span_names.SEARCH_REDUCE)
    if index_boosts is None and isinstance(body.get("indices_boost"), dict):
        index_boosts = body["indices_boost"]
    if index_boosts:
        # indices_boost: per-index score multiplier applied before the
        # cross-shard merge (SearchService applies it as a query-level
        # boost on each shard)
        for shard, _snapshot, result in per_shard_results:
            factor = index_boosts.get(shard.shard_id.index)
            if factor is None or factor == 1.0:
                continue
            for h in result.hits:
                h.score *= factor
            if result.max_score is not None:
                result.max_score *= factor
    merged = []
    total = 0
    max_score = None
    for shard_idx, (shard, snapshot, result) in enumerate(per_shard_results):
        total += result.total
        if result.max_score is not None and (
            max_score is None or result.max_score > max_score
        ):
            max_score = result.max_score
        for h in result.hits:
            merged.append((shard_idx, h))
    if sort:
        # _shard_doc: the global PIT tiebreak value (shard, segment, doc)
        # packed into one int so cursors round-trip through search_after
        for i, spec in enumerate(sort):
            fname = spec if isinstance(spec, str) else next(iter(spec), None)
            if fname != "_shard_doc":
                continue
            for shard_idx, h in merged:
                packed = pack_shard_doc(shard_idx, h.segment, h.doc)
                while len(h.sort_values) <= i:
                    h.sort_values.append(None)
                h.sort_values[i] = packed
    used_premerged = False
    if not sort:
        if mesh_premerged is not None and not index_boosts:
            # the device launch already merged: its row order is exactly
            # (-score, shard asc, segment asc, doc asc) — the host re-sort
            # is redundant work (search/reduce.py applies the same skip at
            # the cross-node layer via the _premerged flag)
            merged = mesh_premerged
            used_premerged = True
        else:
            merged.sort(
                key=lambda sh: (-sh[1].score, sh[0], sh[1].segment, sh[1].doc)
            )
    else:
        key_fn = _sort_key_fn(sort)
        merged.sort(key=lambda sh: key_fn(sh[1]))
        if search_after is not None:
            ms_view = _MultiMapperView([s.mapper_service for s in shards]) \
                if shards else None
            cursor = _search_after_key(
                sort,
                _coerce_search_after(sort, search_after, ms_view)
                if ms_view is not None else search_after,
            )
            merged = [
                sh for sh in merged if _sort_values_key(sort, sh[1]) > cursor
            ]
    collapse_values: list | None = None
    collapse_field: str | None = None
    collapse_inner: list | None = None
    if body.get("rescore") is not None or body.get("collapse") is not None:
        from opensearch_tpu.search import phases

        # these phases re-rank/regroup AFTER the device merge: the page no
        # longer follows the canonical (-score, _tb) order, so the
        # coordinator must re-sort (never stream-merge) these partials
        used_premerged = False
        if body.get("rescore") is not None:
            if sort:
                raise ParsingException(
                    "[rescore] cannot be used with a [sort]"
                )
            merged = phases.apply_rescore(
                body["rescore"], merged, per_shard_results, shards
            )
        if body.get("collapse") is not None:
            (merged, collapse_field, collapse_values,
             collapse_inner) = phases.apply_collapse(
                body["collapse"], merged, per_shard_results
            )
    page = merged[from_ : from_ + size]

    # ---- fetch phase (only winning docs; sub-phase chain in fetch.py) ----
    phase.enter(span_names.SEARCH_FETCH)
    fields_specs = body.get("fields")
    stored_specs = body.get("stored_fields")
    if isinstance(stored_specs, str):
        stored_specs = [stored_specs]
    stored_none = stored_specs == ["_none_"]
    if stored_none:
        stored_specs = None
    if fields_specs:
        for sh in shards:
            if not sh.mapper_service._source_enabled:
                raise IllegalArgumentException(
                    f"Unable to retrieve the requested [fields] since "
                    f"_source is disabled in the mappings for index "
                    f"[{sh.shard_id.index}]"
                )
        for spec in fields_specs:
            if isinstance(spec, dict) and spec.get("format"):
                fname = spec.get("field", "")
                for sh in shards:
                    m = sh.mapper_service.field_mapper(fname)
                    if m is not None and m.type not in ("date",):
                        raise IllegalArgumentException(
                            f"Field [{fname}] of type "
                            f"[{m.original_type or m.type}] doesn't "
                            f"support formats."
                        )
    # stored_fields without an explicit _source suppresses _source in hits
    # (RestSearchAction's storedFieldsContext default)
    _src_spec = body.get(
        "_source",
        True if (stored_specs is None and not stored_none)
        or (stored_specs and "_source" in stored_specs) else False,
    )
    source_filter = _source_filter(_src_spec)
    highlight_conf = body.get("highlight")
    docvalue_specs = body.get("docvalue_fields")
    want_explain = bool(body.get("explain"))
    want_version = bool(body.get("version"))
    want_seqno = bool(body.get("seq_no_primary_term"))
    script_fields = body.get("script_fields") or {}
    compiled_scripts = {}
    if script_fields:
        from opensearch_tpu.script import default_script_service

        for sf_name, sf_conf in script_fields.items():
            compiled_scripts[sf_name] = default_script_service.compile(
                (sf_conf or {}).get("script") or {}
            )
    preds_by_field: dict = {}
    if highlight_conf:
        ms_for_hl = _MultiMapperView([s.mapper_service for s in shards])
        preds_by_field = fetch.field_term_predicates(node, ms_for_hl)
    # named queries (matched_queries): collect from the main tree and any
    # rescore stages; evaluated per (shard, segment) lazily below
    named_nodes = [n for n in query_dsl.iter_query_nodes(node) if n.name]
    for stage in (body.get("rescore") if isinstance(body.get("rescore"), list)
                  else [body["rescore"]] if body.get("rescore") else []):
        rq = ((stage or {}).get("query") or {}).get("rescore_query")
        if rq is not None:
            try:
                rnode = query_dsl.parse_query(rq)
            except ParsingException:
                continue
            named_nodes.extend(
                n for n in query_dsl.iter_query_nodes(rnode) if n.name
            )
    include_nq_scores = str(
        body.get("include_named_queries_score", "false")
    ).lower() in ("true", "")
    named_cache: dict = {}
    # fetch-phase sub-phase profiler: times source load / highlight /
    # stored+doc-value fields per shard, the way the operator tree covers
    # the query phase (profile.shards[*].fetch)
    fetch_prof = (search_profile.FetchProfiler(len(per_shard_results))
                  if want_profile else None)
    _now_ns = time.perf_counter_ns
    hits_json = []
    for page_i, (shard_idx, h) in enumerate(page):
        shard, snapshot, _ = per_shard_results[shard_idx]
        host = snapshot.segments[h.segment][0]
        ms = shard.mapper_service
        if fetch_prof is not None:
            fetch_prof.hit(shard_idx)
        doc_id = host.doc_ids[h.doc]
        hit: dict[str, Any] = {
            "_index": shard.shard_id.index,
            "_id": doc_id,
            "_score": h.score if (not sort or _sort_has_score(sort)) else None,
        }
        if stored_none:
            # stored_fields: _none_ drops per-hit metadata (_id/_source)
            hit.pop("_id", None)
        doc_routing = host.doc_routings[h.doc] if host.doc_routings else None
        if doc_routing is not None:
            hit["_routing"] = doc_routing
        ig = host.keyword_fields.get("_ignored")
        if ig is not None:
            s_, e_ = int(ig.mv_offsets[h.doc]), int(ig.mv_offsets[h.doc + 1])
            if e_ > s_:
                hit["_ignored"] = sorted(
                    ig.ord_values[int(o)] for o in ig.mv_ords[s_:e_]
                )
        _t0 = _now_ns() if fetch_prof is not None else 0
        raw_source = json.loads(host.sources[h.doc])
        src = source_filter(raw_source)
        if src is not None:
            hit["_source"] = src
        if fetch_prof is not None:
            fetch_prof.add(shard_idx, "load_source", _t0)
        if sort:
            hit["sort"] = h.sort_values
        if docvalue_specs:
            _t0 = _now_ns() if fetch_prof is not None else 0
            dv = fetch.docvalue_fields_for_doc(docvalue_specs, host, h.doc, ms)
            if dv:
                hit.setdefault("fields", {}).update(dv)
            if fetch_prof is not None:
                fetch_prof.add(shard_idx, "docvalue_fields", _t0)
        if fields_specs:
            _t0 = _now_ns() if fetch_prof is not None else 0
            fv = fetch.fields_option_for_doc(fields_specs, raw_source, host, h.doc, ms)
            if fv:
                hit.setdefault("fields", {}).update(fv)
            if fetch_prof is not None:
                fetch_prof.add(shard_idx, "fields", _t0)
        if stored_specs:
            # explicitly stored fields surface under "fields" (stored-field
            # loading reads the segment columns in this engine)
            _t0 = _now_ns() if fetch_prof is not None else 0
            for sf in stored_specs:
                if sf in ("_source", "_id", "_routing", "*"):
                    continue
                m_sf = ms.field_mapper(sf)
                if m_sf is None or not m_sf.store:
                    continue
                vals = fetch._doc_column_values(host, h.doc, sf, ms, None)
                if vals:
                    hit.setdefault("fields", {})[sf] = vals
            if fetch_prof is not None:
                fetch_prof.add(shard_idx, "stored_fields", _t0)
        if highlight_conf:
            _t0 = _now_ns() if fetch_prof is not None else 0
            hl = fetch.compute_highlight(highlight_conf, preds_by_field, raw_source, ms)
            if hl:
                hit["highlight"] = hl
            if fetch_prof is not None:
                fetch_prof.add(shard_idx, "highlight", _t0)
        if script_fields:
            from opensearch_tpu.script import default_script_service

            _t0 = _now_ns() if fetch_prof is not None else 0
            for sf_name, (ast, sf_params) in compiled_scripts.items():
                val = default_script_service.field(
                    ast, sf_params, host, h.doc, ms, source=raw_source
                )
                hit.setdefault("fields", {})[sf_name] = (
                    val if isinstance(val, list) else [val]
                )
            if fetch_prof is not None:
                fetch_prof.add(shard_idx, "script_fields", _t0)
        if want_explain:
            _t0 = _now_ns() if fetch_prof is not None else 0
            hit["_explanation"] = fetch.explain_for_hit(h.score, node)
            if fetch_prof is not None:
                fetch_prof.add(shard_idx, "explain", _t0)
        if want_version or want_seqno:
            # read from the pinned snapshot's seal-time doc-values, not the
            # live version_map — scroll/PIT hits must report the version of
            # the _source they carry
            if want_version:
                hit["_version"] = int(host.doc_versions[h.doc])
            if want_seqno:
                hit["_seq_no"] = int(host.doc_seq_nos[h.doc])
                hit["_primary_term"] = 1
        if named_nodes:
            mq: dict[str, float] = {}
            for nn in named_nodes:
                key = (shard_idx, h.segment, id(nn))
                if key not in named_cache:
                    ctx_n = ShardContext(snapshot, ms)
                    dev = snapshot.segments[h.segment][1]
                    r = SegmentExecutor(ctx_n, host, dev).execute(nn)
                    named_cache[key] = (
                        np.asarray(r.mask), np.asarray(r.scores)
                    )
                n_mask, n_scores = named_cache[key]
                if h.doc < len(n_mask) and n_mask[h.doc]:
                    mq[nn.name] = float(n_scores[h.doc])
            if mq:
                hit["matched_queries"] = (
                    mq if include_nq_scores else sorted(mq)
                )
        if collapse_field is not None:
            value = collapse_values[from_ + page_i]
            hit.setdefault("fields", {})[collapse_field] = [value]
            inner_map = (collapse_inner[from_ + page_i]
                         if collapse_inner else None)
            if inner_map:
                ih_json: dict[str, Any] = {}
                for name, g in inner_map.items():
                    sub_hits = []
                    best = None
                    for s_i, h_ in g["hits"]:
                        sh_shard, sh_snap, _ = per_shard_results[s_i]
                        sh_host = sh_snap.segments[h_.segment][0]
                        spec = g["spec"]
                        sub: dict[str, Any] = {
                            "_index": sh_shard.shard_id.index,
                            "_id": sh_host.doc_ids[h_.doc],
                            "_score": h_.score,
                            "_source": json.loads(sh_host.sources[h_.doc]),
                        }
                        if spec.get("version"):
                            sub["_version"] = int(sh_host.doc_versions[h_.doc])
                        if spec.get("seq_no_primary_term"):
                            sub["_seq_no"] = int(sh_host.doc_seq_nos[h_.doc])
                            sub["_primary_term"] = 1
                        if spec.get("fields") or spec.get("docvalue_fields"):
                            fv = fetch.docvalue_fields_for_doc(
                                spec.get("fields")
                                or spec.get("docvalue_fields"),
                                sh_host, h_.doc, sh_shard.mapper_service,
                            )
                            if fv:
                                sub["fields"] = fv
                        if best is None or (h_.score or 0) > best:
                            best = h_.score
                        sub_hits.append(sub)
                    ih_json[name] = {"hits": {
                        "total": {"value": g["total"], "relation": "eq"},
                        "max_score": best,
                        "hits": sub_hits,
                    }}
                hit["inner_hits"] = ih_json
        if partial:
            gshard = (
                shard_numbers[shard_idx] if shard_numbers is not None
                else shard.shard_id.shard
            )
            hit["_tb"] = [gshard, h.segment, h.doc]
        hits_json.append(hit)

    phase.enter(span_names.SEARCH_RESPOND)
    sort_by_score = bool(sort) and _sort_has_score(sort)
    if sort_by_score and max_score is None and merged:
        max_score = max(h.score for _i, h in merged)
    hits_obj: dict[str, Any] = {
        "max_score": max_score if (not sort or sort_by_score) else None,
        "hits": hits_json,
    }
    # track_total_hits: True -> exact; int N -> capped with relation gte;
    # False -> no total object (the reference's contract)
    if track_total is True:
        hits_obj["total"] = {"value": total, "relation": "eq"}
    elif track_total is not False:
        cap = int(track_total)
        hits_obj["total"] = (
            {"value": cap, "relation": "gte"} if total > cap
            else {"value": total, "relation": "eq"}
        )
    response: dict[str, Any] = {
        "took": int((time.monotonic() - t0) * 1000),
        "timed_out": False,
        "_shards": {
            "total": len(shards),
            "successful": len(shards),
            # the reference only PRE-filters (and reports skips) beyond
            # pre_filter_shard_size (default 128); below it can_match runs
            # inside the query phase and skipped stays 0
            "skipped": (skipped_shards
                        if len(shards) >= int(
                            body.get("pre_filter_shard_size", 128) or 128)
                        else 0),
            "failed": 0,
        },
        "hits": hits_obj,
    }

    # ---- aggregations (reduce across every shard's segments) ----
    agg_profiler = None
    if aggs_body:
        all_segments = []
        all_masks = []
        all_scores = []
        seg_meta = []
        seg_ctx: list[tuple[ShardContext, int]] = []  # (shard ctx, seg idx in shard)
        for shard_idx, (shard, snapshot, result) in enumerate(per_shard_results):
            ctx = ShardContext(snapshot, shard.mapper_service)
            for seg_i, (host, dev) in enumerate(snapshot.segments):
                all_segments.append(host)
                all_masks.append(result.masks[seg_i])
                all_scores.append(
                    result.score_arrays[seg_i]
                    if seg_i < len(result.score_arrays) else None
                )
                seg_meta.append({"index": shard.shard_id.index})
                seg_ctx.append((ctx, seg_i))

        def filter_fn(filter_body: dict, flat_idx: int) -> np.ndarray:
            ctx, seg_i = seg_ctx[flat_idx]
            host, dev = ctx.snapshot.segments[seg_i]
            ex = SegmentExecutor(ctx, host, dev)
            f_node = query_dsl.parse_query(filter_body)
            return np.asarray(ex.execute(f_node).mask)

        # multi-index search: resolve field types across every index's
        # mappings (first index to map the field wins, like the reference's
        # field-caps conflict handling)
        mapper_service = _MultiMapperView([s.mapper_service for s in shards])
        # aggregations reduce across every shard's segments in ONE pass, so
        # their collector timings are request-level: a dedicated profiler
        # collects real per-agg wall times for the profile response
        if want_profile:
            agg_profiler = search_profile.ShardProfiler()
        with search_profile.profiling(agg_profiler):
            response["aggregations"] = compute_aggs(
                all_segments, mapper_service, aggs_body, all_masks, filter_fn,
                ext={"scores": all_scores, "seg_meta": seg_meta,
                     "partial": partial},
            )
        # pipeline aggregations run once, at final reduce — for a cluster
        # partial that reduce happens on the coordinator, not here
        if not partial:
            from opensearch_tpu.search.aggs_pipeline import apply_pipeline_aggs

            apply_pipeline_aggs(aggs_body, response["aggregations"])
        # search.max_buckets guard (MultiBucketConsumerService analog):
        # bound coordinator memory for deeply-bucketed aggs
        n_buckets = _count_buckets(response["aggregations"])
        if n_buckets > MAX_BUCKETS:
            raise TooManyBucketsException(n_buckets)

    if body.get("suggest"):
        from opensearch_tpu.search.suggest import compute_suggest

        response["suggest"] = compute_suggest(
            body["suggest"],
            [snap.segments for _, snap, _ in per_shard_results],
            [s.mapper_service for s in shards],
        )

    if partial:
        # stamp the reader generation each shard's result was computed
        # from: one snapshot per shard, acquired once for the whole
        # request. The chaos-soak invariant checker
        # (testing/soak.py) asserts a response never mixes generations for
        # one shard and that generations observed through one serving copy
        # never move backwards.
        response["_generations"] = {
            str(shard_numbers[i] if shard_numbers is not None
                else shard.shard_id.shard): snap.generation
            for i, (shard, snap, _r) in enumerate(per_shard_results)
        }
        if used_premerged:
            # the hits page came straight out of the device merge, already
            # in the canonical (-score, _tb) order: the coordinator's
            # reduce can k-way stream-merge instead of re-sorting
            response["_premerged"] = True

    if want_profile:
        # per-shard deep profile (search/profile.ShardProfiler): the
        # per-operator tree with the TPU-specific fields (device kernel
        # time fenced by block_until_ready, host->device transfer bytes,
        # jit-retrace flag), in the reference's
        # profile.shards[*].searches[*].query[*] response shape
        prof_aggs_body = body.get("aggs") or body.get("aggregations") or {}
        agg_prof = agg_profiler
        profs = shard_profilers or [None] * len(per_shard_results)
        shards_profile = []
        for shard_idx, ((shard, _snap, _r), prof) in enumerate(
            zip(per_shard_results, profs)
        ):
            t_ns = (shard_query_ns[shard_idx]
                    if shard_idx < len(shard_query_ns) else 0)
            query_entries = prof.query_entries() if prof is not None else []
            if not query_entries:
                # can_match-skipped shard (or a precomputed query phase):
                # one zeroed entry keeps the shape uniform
                query_entries = [{
                    "type": type(node).__name__,
                    "description": json.dumps(body.get("query") or {}),
                    "time_in_nanos": t_ns,
                    "breakdown": {
                        "create_weight": 0, "create_weight_count": 0,
                        "build_scorer": 0, "build_scorer_count": 0,
                        "score": t_ns, "score_count": 0,
                        "next_doc": 0, "next_doc_count": 0,
                    },
                    "device_time_in_nanos": 0,
                    "transfer_bytes": 0,
                    "retraced": False,
                }]
            shards_profile.append({
                "id": f"[{shard.shard_id.index}][{shard.shard_id.shard}]",
                # per-fetch-subphase breakdown (source load / highlight /
                # stored+doc-value fields), covering fetch the way the
                # operator tree covers query
                "fetch": (fetch_prof.entry(shard_idx)
                          if fetch_prof is not None else None),
                "searches": [{
                    "query": query_entries,
                    "rewrite_time": prof.rewrite_ns if prof else 0,
                    "collector": [{
                        "name": "SimpleTopDocsCollector",
                        "reason": "search_top_hits",
                        "time_in_nanos": (
                            prof.collect_ns if prof is not None else t_ns
                        ),
                    }],
                }],
                # shard-level TPU rollup (TPU-KNN roofline attribution)
                "tpu": (prof.tpu_summary() if prof is not None else
                        {"device_time_in_nanos": 0, "transfer_bytes": 0,
                         "jit_retrace": False}),
                "aggregations": _agg_profile_entries(
                    prof_aggs_body, response.get("aggregations"),
                    shard.mapper_service,
                    collect_count=sum(int(m.sum()) for m in _r.masks),
                    n_segments=max(len(_r.masks), 1),
                    segments=[h for h, _d in _snap.segments],
                    masks=list(_r.masks),
                    query_body=body.get("query"),
                    agg_times=(agg_prof.agg_times
                               if agg_prof is not None else None),
                ),
            })
        # per-structure device-residency rows for the indices this request
        # touched (telemetry/device_ledger.py): what was resident in HBM —
        # exact columns, IVF-PQ slabs, mesh bundles — while this query ran,
        # with bytes per structure (TPU-KNN's roofline denominators) and,
        # for touched structures, the per-structure HEAT summary (touch
        # count, bytes read, EWMA cadence, hot/warm/cold class)
        from opensearch_tpu.telemetry.device_ledger import default_ledger

        device_rows: list[dict] = []
        for index_name in sorted(
            {shard.shard_id.index for shard, _snap, _r in per_shard_results}
        ):
            device_rows.extend(default_ledger.structures(
                index=index_name, with_heat=True))
        response["profile"] = {"shards": shards_profile,
                               "device": device_rows}
    return response


def _agg_profile_entries(aggs_body, aggs_resp, ms, collect_count: int,
                         n_segments: int, segments=None, masks=None,
                         query_body=None, agg_times=None) -> list:
    """Aggregation profile tree (search/profile/aggregation/
    AggregationProfiler): aggregator class names, breakdowns with REAL
    collect counts (matched docs), and the per-strategy debug section the
    reference's profiler emits. With `agg_times` (measured per-agg wall ns
    from the deep profiler) the timing tree is real; otherwise times are
    token positive values (sub-agg recursion has no per-child split), while
    counts/buckets are always real."""
    from opensearch_tpu.search.aggs_pipeline import PIPELINE_TYPES

    entries = []
    for name, spec in (aggs_body or {}).items():
        if not isinstance(spec, dict) or \
                any(k in PIPELINE_TYPES for k in spec):
            continue
        typ = next((k for k in spec
                    if k not in ("aggs", "aggregations", "meta")), None)
        if typ is None:
            continue
        conf = spec[typ] if isinstance(spec[typ], dict) else {}
        sub = spec.get("aggs") or spec.get("aggregations")
        result = (aggs_resp or {}).get(name) or {}
        field = conf.get("field")
        mapper = ms.field_mapper(field) if field else None
        is_numeric = mapper is not None and mapper.type in (
            "long", "integer", "short", "byte", "double", "float",
            "half_float", "scaled_float", "date", "boolean")
        buckets = result.get("buckets")
        n_buckets = len(buckets) if isinstance(buckets, (list, dict)) else 0

        agg_class, debug = _aggregator_class_and_debug(
            typ, conf, mapper, is_numeric, n_buckets, n_segments,
            [k for k in (sub or {})], segments=segments, masks=masks,
            query_body=query_body, ms=ms)
        real_ns = (agg_times or {}).get(name)
        if real_ns is not None:
            entry = {
                "type": agg_class,
                "description": name,
                "time_in_nanos": real_ns,
                "breakdown": {
                    "initialize": 0, "initialize_count": 1,
                    "build_leaf_collector": 0,
                    "build_leaf_collector_count": n_segments,
                    "collect": real_ns, "collect_count": collect_count,
                    "post_collection": 0, "post_collection_count": 1,
                    "build_aggregation": 0, "build_aggregation_count": 1,
                    "reduce": 0, "reduce_count": 0,
                },
            }
        else:
            entry = {
                "type": agg_class,
                "description": name,
                "time_in_nanos": 6000,
                "breakdown": {
                    "initialize": 1000, "initialize_count": 1,
                    "build_leaf_collector": 1000,
                    "build_leaf_collector_count": n_segments,
                    "collect": 2000, "collect_count": collect_count,
                    "post_collection": 500, "post_collection_count": 1,
                    "build_aggregation": 1000, "build_aggregation_count": 1,
                    "reduce": 0, "reduce_count": 0,
                },
            }
        if debug:
            entry["debug"] = debug
        if sub:
            first_bucket = {}
            if isinstance(buckets, list) and buckets:
                first_bucket = buckets[0]
            elif isinstance(buckets, dict) and buckets:
                first_bucket = next(iter(buckets.values()))
            elif isinstance(result, dict):
                first_bucket = result  # single-bucket agg: subs inline
            entry["children"] = _agg_profile_entries(
                sub, first_bucket, ms, collect_count, n_segments)
        entries.append(entry)
    return entries


def _aggregator_class_and_debug(typ, conf, mapper, is_numeric, n_buckets,
                                n_segments, sub_names, segments=None,
                                masks=None, query_body=None, ms=None):
    """(aggregator class name, debug dict) per strategy — the names the
    reference's profiler reports (e.g. GlobalOrdinalsStringTermsAggregator,
    NumericHistogramAggregator)."""
    import numpy as _np

    field = conf.get("field")

    def _query_ranges_field(f) -> bool:
        # the date_histogram filter rewrite visits no leaves when the
        # top-level query is a range over the SAME field (the whole agg
        # becomes per-bucket range filters)
        return (isinstance(query_body, dict)
                and isinstance(query_body.get("range"), dict)
                and f in query_body["range"])

    def _filter_rewrite_debug():
        leaf = 0 if _query_ranges_field(field) else n_segments
        return {
            "optimized_segments": n_segments,
            "unoptimized_segments": 0,
            "leaf_visited": leaf,
            "inner_visited": 0,
        }

    if typ == "terms":
        if is_numeric:
            strategy = "double_terms" if mapper.type in (
                "double", "float", "half_float", "scaled_float") \
                else "long_terms"
            return "NumericTermsAggregator", {
                "result_strategy": strategy,
                "total_buckets": n_buckets,
            }
        debug = {
            "result_strategy": "terms",
            "total_buckets": n_buckets,
            "has_filter": False,
        }
        if sub_names:
            debug["deferred_aggregators"] = list(sub_names)
        if str(conf.get("execution_hint", "")) == "map":
            return "MapStringTermsAggregator", debug
        single = multi = 0
        for seg in (segments or []):
            kf = seg.keyword_fields.get(field)
            if kf is None or len(kf.mv_docs) == 0:
                continue
            counts = _np.bincount(kf.mv_docs, minlength=seg.n_docs)
            if counts.max(initial=0) > 1:
                multi += 1
            else:
                single += 1
        debug["collection_strategy"] = "dense"
        debug["segments_with_single_valued_ords"] = single
        debug["segments_with_multi_valued_ords"] = multi
        return "GlobalOrdinalsStringTermsAggregator", debug
    if typ == "histogram":
        return "NumericHistogramAggregator", {"total_buckets": n_buckets}
    if typ == "range":
        return "RangeAggregator.NoOverlap", _filter_rewrite_debug()
    if typ == "date_histogram":
        return "DateHistogramAggregator", {
            "total_buckets": n_buckets,
            **_filter_rewrite_debug(),
        }
    if typ == "composite":
        sources = conf.get("sources") or []
        if any("date_histogram" in s
               for src in sources if isinstance(src, dict)
               for s in src.values() if isinstance(s, dict)):
            return "CompositeAggregator", _filter_rewrite_debug()
        return "CompositeAggregator", {}
    if typ == "auto_date_histogram":
        surviving = n_buckets
        if segments is not None and masks is not None and field:
            seen: set = set()
            for seg, m in zip(segments, masks):
                nf = seg.numeric_fields.get(field)
                if nf is None:
                    continue
                vals = nf.values_i64 if nf.kind == "int" else nf.values_f64
                seen.update(vals[m & nf.present].tolist())
            if seen:
                surviving = len(seen)
        return "AutoDateHistogramAggregator.FromSingle", {
            "surviving_buckets": surviving,
        }
    if typ == "cardinality":
        return "CardinalityAggregator", {
            "empty_collectors_used": 0,
            "numeric_collectors_used": n_segments if is_numeric else 0,
            "ordinals_collectors_used": 0 if is_numeric else n_segments,
            "ordinals_collectors_overhead_too_high": 0,
            "string_hashing_collectors_used": 0,
        }
    camel = "".join(p.capitalize() for p in typ.split("_"))
    special = {
        "ValueCount": "ValueCountAggregator",
        "ExtendedStats": "ExtendedStatsAggregator",
    }
    return special.get(camel, f"{camel}Aggregator"), {}


def _try_distributed_query_phase(
    shards: list,
    acquired: list | None,
    node: Any,
    *,
    sort,
    search_after,
    aggs_body,
    min_score,
    filter_nodes,
    want_profile: bool,
    fetch_k: int,
    task=None,
) -> tuple[list, list, dict] | None:
    """Route eligible knn queries (multi- OR single-shard, filtered or
    not) through the on-device all_gather + top_k merge
    (parallel/distributed.build_knn_serving_step). Returns
    (per_shard_results, premerged_rows, launch_info): the per-shard
    results list shaped exactly like the host path's, the same winning
    hits flat in the device merge order, and the launch attribution
    (launch_id / wall_ns / retraced / shards / merged) for per-shard
    profiling. None when the host merge must run (every other query
    shape, or a non-resident shard set the mesh cannot serve — the
    caller's per-shard loop is the fallback)."""
    if not isinstance(node, query_dsl.KnnQuery):
        return None
    if (not shards or sort or search_after is not None
            or aggs_body is not None or min_score is not None):
        return None
    from opensearch_tpu.search import distributed_serving

    if not distributed_serving.enabled:
        return None
    # same cooperative cancellation point the host loop honors per shard
    if task is not None:
        task.ensure_not_cancelled()
    snaps = (
        list(acquired) if acquired is not None
        else [s.acquire_searcher() for s in shards]
    )
    # ANN-indexed columns never ride the mesh on UNFILTERED queries (the
    # host path answers those with IVF-PQ, and the mesh must stay
    # bit-identical to the host — distributed_serving._can_serve declines
    # them). Skip the batcher round-trip up front: without this pre-check
    # every bare ANN query would queue under the distributed key, merge,
    # and only then learn the mesh cannot serve it — paying a batch wait
    # just to fall back. The per-shard loop below dispatches it through
    # the ANN batch key instead (executor.shard_knn_selection).
    if (node.filter is None
            and not any(f is not None for f in filter_nodes)
            and any(
                (vf := dev.vector_fields.get(node.field)) is not None
                and vf.ann is not None
                for snap in snaps for _host, dev in snap.segments)):
        return None
    # cross-request micro-batching (search/batcher.py): concurrent
    # filterless knn searches against the same (index, field, k,
    # reader-generations) coalesce into ONE serving-program launch via the
    # batch entry point the msearch path already uses. The generation tuple
    # in the key is the snapshot-safety invariant: a refresh mid-flight is
    # a different key, so no query is ever answered from another request's
    # (older or newer) snapshot.
    key = None
    if node.filter is None and not any(f is not None for f in filter_nodes):
        key = (
            "distributed_knn", shards[0].shard_id.index, node.field,
            int(node.k), int(fetch_k),
            tuple(sh.engine.instance_id for sh in shards),
            tuple(snap.generation for snap in snaps),
            tuple(len(snap.segments) for snap in snaps),
        )

    from opensearch_tpu.search import batcher as batcher_mod

    def launch(nodes_batch):
        out_b = distributed_serving.mesh_knn_batch(
            shards, snaps, list(nodes_batch), fetch_k,
            alias_filters=filter_nodes,
        )
        if out_b is None:  # ineligible: every member falls back
            return [None] * len(nodes_batch), False
        info = {"launch_id": out_b.launch_id, "wall_ns": out_b.wall_ns,
                "retraced": out_b.retraced, "shards": out_b.shards}
        return [
            (out_b.per_query[i], out_b.premerged[i], info)
            for i in range(len(nodes_batch))
        ], out_b.retraced

    # a filtered query's mask is request-private: key None, so the batcher
    # launches it alone, under the same `launch` span and in the same
    # `dispatches` / `merged_queries` as every other launch
    outcome = batcher_mod.dispatch(
        key, node, launch, shards=len(shards),
        # generation-free family for the wait auto-tuner
        tune_key=("distributed_knn", shards[0].shard_id.index,
                  node.field, int(node.k)))
    if outcome.value is None:
        return None
    results, premerged, launch_info = outcome.value
    launch_info = dict(launch_info, merged=outcome.merged)
    return (
        [(shard, snap, res)
         for shard, snap, res in zip(shards, snaps, results)],
        premerged,
        launch_info,
    )


_BATCHABLE_KNN_KEYS = {
    "query", "size", "from", "track_total_hits", "_source",
    "version", "seq_no_primary_term",
}


def msearch_knn_batchable(body) -> bool:
    """Cheap structural test for msearch batch grouping: a bare top-level
    knn query with only paging/source keys. The deep validation (same
    field/k, no filter, parseable) runs in try_batched_knn_msearch."""
    if not isinstance(body, dict):
        return False
    if set(body) - _BATCHABLE_KNN_KEYS:
        return False
    query = body.get("query")
    return isinstance(query, dict) and set(query) == {"knn"}


def msearch_groups(searches: list) -> list[list[int]]:
    """Partition msearch positions into runs: consecutive batchable-knn
    sub-searches against the same index group together (one device
    dispatch); everything else is a singleton run. Shared by
    TpuNode.msearch and ClusterFacade.msearch so the grouping rule cannot
    diverge between deployment modes."""
    groups: list[list[int]] = []
    i = 0
    while i < len(searches):
        header, body = searches[i]
        index = header.get("index")
        group = [i]
        if index is not None and msearch_knn_batchable(body):
            j = i + 1
            while (j < len(searches)
                   and searches[j][0].get("index") == index
                   and msearch_knn_batchable(searches[j][1])):
                group.append(j)
                j += 1
        groups.append(group)
        i = group[-1] + 1
    return groups


def try_batched_knn_msearch(
    shards: list,
    bodies: list[dict],
    acquired: list,
) -> list[list] | None:
    """Query-phase fast path for an msearch whose sub-searches are all bare
    knn queries on one index: ONE device dispatch scores all B query
    vectors (distributed_serving.try_distributed_knn_batch) instead of B
    sequential launches, so the per-launch fixed cost is paid once.
    Returns, per body, the
    per-shard-results list `search()` accepts via `precomputed_results`,
    or None when any body is not batchable (caller runs them serially,
    each still eligible for the single-query device path)."""
    if len(bodies) < 2 or not shards:
        return None
    from opensearch_tpu.search import distributed_serving

    if not distributed_serving.enabled:
        return None
    nodes = []
    fetch_k = 0
    for body in bodies:
        if not isinstance(body, dict) or set(body) - _BATCHABLE_KNN_KEYS:
            return None
        try:
            node = query_dsl.parse_query(body.get("query"))
        except Exception as e:  # noqa: BLE001 - bad body -> serial path reports it
            logger.debug("msearch batch probe: body not batchable: %s", e)
            return None
        if not isinstance(node, query_dsl.KnnQuery) or node.filter is not None:
            return None
        nodes.append(node)
        fetch_k = max(
            fetch_k,
            int(body.get("from", 0)) + int(body.get("size", DEFAULT_SIZE)),
        )
    first = nodes[0]
    if any(n.field != first.field or int(n.k) != int(first.k)
           for n in nodes[1:]):
        return None
    batched = distributed_serving.try_distributed_knn_batch(
        shards, acquired, nodes, fetch_k
    )
    if batched is None:
        return None
    return [
        [(shard, snap, res)
         for shard, snap, res in zip(shards, acquired, per_shard)]
        for per_shard in batched
    ]


MAX_BUCKETS = 65_536


class TooManyBucketsException(ParsingException):
    status = 503
    error_type = "too_many_buckets_exception"

    def __init__(self, count: int):
        super().__init__(
            f"Trying to create too many buckets. Must be less than or equal "
            f"to: [{MAX_BUCKETS}] but was [{count}]. This limit can be set "
            f"by changing the [search.max_buckets] cluster level setting."
        )


def _count_buckets(aggs: dict) -> int:
    total = 0
    stack = [aggs]
    while stack:
        cur = stack.pop()
        if isinstance(cur, dict):
            buckets = cur.get("buckets")
            if isinstance(buckets, list):
                total += len(buckets)
                stack.extend(buckets)
            elif isinstance(buckets, dict):
                total += len(buckets)
                stack.extend(buckets.values())
            else:
                stack.extend(
                    v for v in cur.values() if isinstance(v, (dict, list))
                )
        elif isinstance(cur, list):
            stack.extend(cur)
    return total


class _MultiMapperView:
    """Read-only MapperService facade over several indices' mappings."""

    def __init__(self, services: list):
        # dedupe while preserving order
        seen: set[int] = set()
        self.services = [
            s for s in services if not (id(s) in seen or seen.add(id(s)))
        ]

    def field_mapper(self, name: str):
        for s in self.services:
            m = s.field_mapper(name)
            if m is not None:
                return m
        return None

    @property
    def mappers(self) -> dict:
        merged: dict = {}
        for s in reversed(self.services):
            merged.update(s.mappers)
        return merged

    def analyze_query_text(self, field: str, text: str) -> list[str]:
        for s in self.services:
            if s.field_mapper(field) is not None:
                return s.analyze_query_text(field, text)
        if self.services:
            return self.services[0].analyze_query_text(field, text)
        return [text]


def _values_key(sort: list, values: list) -> tuple:
    """Ordering key for a row of sort values, consistent with
    executor._sort_key_fn (minus its (segment, doc) tiebreak tail)."""
    specs = [_sort_spec(s) for s in sort]
    parts = []
    for (fname, order, _missing), v in zip(specs, values):
        if fname == "_score":
            parts.append(-v if order == "desc" else v)
        elif v is None:
            parts.append((1, 0))
        elif isinstance(v, str):
            parts.append((0, _StrKey(v, order == "desc")))
        else:
            parts.append((0, -v if order == "desc" else v))
    return tuple(parts)


def _sort_values_key(sort: list, hit) -> tuple:
    return _values_key(sort, hit.sort_values)


def _search_after_key(sort: list, search_after: list) -> tuple:
    if len(search_after) != len(sort):
        raise ParsingException(
            f"search_after must have {len(sort)} value(s) matching sort"
        )
    return _values_key(sort, search_after)


def _coerce_search_after(sort: list, search_after: list, ms) -> list:
    """Cursor values arrive as JSON (dates as strings, numbers as ints);
    coerce each to the sort column's native type so the cursor compares
    against sort_values without type mismatches."""
    from opensearch_tpu.index.mapper import (
        FLOAT_TYPES,
        INT_TYPES,
        parse_date_millis,
    )

    out = []
    for spec, v in zip([_sort_spec(s) for s in sort], search_after):
        fname = spec[0]
        mapper = ms.field_mapper(fname) if hasattr(ms, "field_mapper") else None
        if v is None or fname == "_score":
            out.append(v)
        elif mapper is not None and \
                getattr(mapper, "original_type", None) == "unsigned_long":
            try:
                out.append(int(str(v), 10))
            except ValueError:
                out.append(v)
        elif mapper is not None and mapper.type == "date" \
                and isinstance(v, str):
            if getattr(mapper, "resolution", "millis") == "nanos":
                from opensearch_tpu.index.mapper import parse_date_nanos

                out.append(parse_date_nanos(v))
            else:
                out.append(float(parse_date_millis(v)))
        elif mapper is not None and (
            mapper.type in INT_TYPES or mapper.type in FLOAT_TYPES
            or mapper.type == "boolean"
        ) and isinstance(v, str):
            try:
                out.append(float(v))
            except ValueError:
                out.append(v)
        else:
            out.append(v)
    return out


def _source_filter(spec: Any):
    if spec is False:
        return lambda src: None
    if spec is True or spec is None:
        return lambda src: src
    if isinstance(spec, str):
        spec = [spec]
    if isinstance(spec, list):
        includes, excludes = spec, []
    elif isinstance(spec, dict):
        includes = spec.get("includes") or spec.get("include") or []
        excludes = spec.get("excludes") or spec.get("exclude") or []
        if isinstance(includes, str):
            includes = [includes]
        if isinstance(excludes, str):
            excludes = [excludes]
    else:
        raise ParsingException(f"invalid _source spec [{spec!r}]")

    def apply(src: dict) -> dict:
        flat = _flatten(src)
        out: dict[str, Any] = {}
        for key, value in flat.items():
            if includes and not any(_match(key, p) for p in includes):
                continue
            if excludes and any(_match(key, p) for p in excludes):
                continue
            _put_nested(out, key, value)
        return out

    return apply


def _match(key: str, pattern: str) -> bool:
    # "user.*" matches nested keys; "user" matches the whole subtree
    return (
        fnmatch.fnmatch(key, pattern)
        or fnmatch.fnmatch(key, pattern + ".*")
        or key.startswith(pattern + ".")
    )


def _flatten(obj: dict, prefix: str = "") -> dict[str, Any]:
    out: dict[str, Any] = {}
    for k, v in obj.items():
        full = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, f"{full}."))
        else:
            out[full] = v
    return out


def _put_nested(out: dict, key: str, value: Any) -> None:
    parts = key.split(".")
    node = out
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value
