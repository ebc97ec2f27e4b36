"""Distributed exact-kNN serving: the on-device cross-shard merge in _search.

This wires parallel/distributed.build_knn_serving_step into the serving
path: a multi-shard knn query executes ONE
shard_map program over the device mesh — per-shard scoring + top-k on each
device, then all_gather + top_k over ICI — replacing the host-side k-way
merge of the reference's SearchPhaseController.mergeTopDocs
(server/src/main/java/org/opensearch/action/search/SearchPhaseController.java:224)
and its per-shard fan-out (AbstractSearchAsyncAction.java:281).

Layout: at first use after a refresh, each shard's segment vector columns
are flattened into one [n_flat, d] slab (segment-ascending, doc-ascending —
the host merge's tie-break order) and laid out as [S, n_flat, d] with the
shard axis over the mesh's data axis: every device is handed its own
shards' slabs from host memory (`_place`), so no chip ever holds another
shard's rows. The slabs are cached per (index, field, per-shard segment
generations); a refresh invalidates only that index's entry.

Fallback contract: any shape this path cannot serve identically to the host
merge (ANN-indexed segments on unfiltered queries, mixed similarities)
returns None and the caller keeps the host path — the can-serve gate
mirrors how the reference keeps BKD/points fast paths behind eligibility
checks.

Widening: the gates that restricted this path to
unfiltered multi-shard queries, one vector per dispatch, are lifted:
 - FILTERED kNN: the filter (knn-level and per-shard alias filters) is
   evaluated per segment by the same SegmentExecutor the per-shard path
   uses (a keyword clause: a host mask from the posting lists of the
   ordinals it names, uploaded; numeric and text clauses and the bool
   composition: device programs over the segment's columns), each
   segment's mask copied to the host, flattened to a [S, n_flat] mask,
   uploaded, ANDed with the bundle's valid mask, and the SAME device
   program runs — pre-filter semantics identical to the per-shard path
   (executor.ShardContext.shard_knn_selection, which ANDs the filter's
   mask into `valid` before its launch). All of that is the DETAIL span
   `filter.mask` (`rows`, `eligible`, `clauses`, `postings`,
   `upload_bytes`), the launch says `filtered` 1, and the node's counters
   `knn.filter.requests` / `knn.filter.mask_bytes` count it
   (executor.count_knn_filter; this module's `stats["filtered"]` is fed at
   the same place); `knn.filter.postings_builds` counts a keyword field's
   ordinal-major view being built, once a segment and field. A filtered
   query's mask is request-private, so it shares no launch
   (search/service.py hands it to the batcher with key None). Because the
   per-shard path falls back to an exact scan whenever a filter is
   present, ANN-indexed segments are also eligible when filtered.
 - SINGLE-SHARD: s == 1 runs the same program on a 1-device mesh (the
   all_gather degenerates); the per-shard executor path is bypassed in
   favor of the resident bundle.
 - BATCHED multi-query: try_distributed_knn_batch dispatches B query
   vectors in ONE program launch ([B, d] padded to a power of two), so
   the per-launch fixed cost is paid once per batch; facade.msearch
   groups eligible consecutive knn searches into one such dispatch.
"""

from __future__ import annotations

import threading
import time
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opensearch_tpu.cluster.shard_mesh import default_registry as registry
from opensearch_tpu.parallel.distributed import build_knn_serving_step, unpack
from opensearch_tpu.parallel.mesh import DATA_AXIS, serving_devices
from opensearch_tpu.search.executor import (
    ShardHit,
    ShardQueryResult,
    count_knn_filter,
    filter_clauses,
)
from opensearch_tpu.telemetry import spans as span_names
from opensearch_tpu.telemetry import tracing

# observability: tests and the multichip dryrun assert the serving path
# ran. Increment via _count(): searches run on a parallel pool, and a bare
# `dict[k] += 1` drops counts under concurrent read-modify-write.
stats = {
    "distributed_searches": 0,
    "fallbacks": 0,
    "filtered": 0,          # dispatches that carried a filter mask; counted
                            # where `knn.filter.requests` is (the tests' view)
    "single_shard": 0,      # dispatches with s == 1
    "batched_queries": 0,   # total query vectors sent in B>1 dispatches
}
_STATS_LOCK = threading.Lock()


def _count(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        stats[key] += n

# kill switch (tests compare against the host merge; ops can disable)
enabled = True

_PROGRAM_CACHE: dict[tuple, Any] = {}
_MESH_CACHE: dict[int, Mesh] = {}
# searches run on a parallel pool since the kNN batcher PR: concurrent
# cache misses must not race program-cache insertion (bundle residency has
# its own lock inside the ShardMeshRegistry)
_CACHE_LOCK = threading.Lock()


class MeshLaunchOutcome:
    """What ONE sharded launch produced, for every query it served.

    `per_query[q]` is the per-shard ShardQueryResult list shaped exactly
    like the host path's; `premerged[q]` is the same winning hits as a flat
    [(shard_idx, ShardHit)] list in the DEVICE merge order — which equals
    the host merge's (-score, shard, segment, doc) ordering exactly, so the
    caller can skip its host-side re-sort. `launch_id`/`wall_ns`/`retraced`
    feed per-shard profile attribution (one launch record shared by every
    shard the program covered)."""

    __slots__ = ("per_query", "premerged", "launch_id", "wall_ns",
                 "retraced", "shards")

    def __init__(self, per_query, premerged, launch_id, wall_ns, retraced,
                 shards):
        self.per_query = per_query
        self.premerged = premerged
        self.launch_id = launch_id
        self.wall_ns = wall_ns
        self.retraced = retraced
        self.shards = shards


class _IndexBundle:
    """[S, n_flat, d] mesh-sharded slabs + host-side flat->segment maps.

    A multi-vector field's bundle (`parent` set) holds the shards' ROWS:
    `parent` [S, n_flat] is each row's document in its shard's flat
    document space (segment-ascending, doc-ascending; -1 on padding), and
    the launch's ids are documents, `id_stride` apart from shard to shard
    (the flat bundle's ids are rows, `n_flat` apart)."""

    def __init__(self, vectors, norms_sq, valid, n_flat: int,
                 seg_offsets: list[list[tuple[int, int, int]]],
                 allocation=None, parent=None, id_stride: int = 0):
        self.vectors = vectors          # jnp [S, n_flat, d] on mesh
        self.norms_sq = norms_sq        # jnp [S, n_flat]
        self.valid = valid              # jnp [S, n_flat]
        self.n_flat = n_flat
        # per shard: [(flat_start, seg_idx, n_docs)] in segment order
        self.seg_offsets = seg_offsets
        # device-residency ledger handle; the ShardMeshRegistry frees it
        # on eviction/invalidation (and on a lost duplicate-build race)
        self.allocation = allocation
        self.parent = parent            # jnp [S, n_flat] int32, or None
        self.id_stride = id_stride or n_flat

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in
                   (self.vectors, self.norms_sq, self.valid, self.parent)
                   if a is not None)

    def locate(self, shard_idx: int, flat: int) -> tuple[int, int]:
        for start, seg_idx, n_docs in self.seg_offsets[shard_idx]:
            if start <= flat < start + n_docs:
                return seg_idx, flat - start
        raise IndexError(f"flat doc {flat} out of range for shard {shard_idx}")


def _serving_mesh(n_shards: int) -> Mesh:
    """The mesh an index of `n_shards` shards is served on: its data axis
    over `serving_devices`, the device order `shard_device` places each
    shard's segment columns by."""
    devices = serving_devices(n_shards)
    mesh = _MESH_CACHE.get(len(devices))
    if mesh is None:
        mesh = Mesh(np.asarray(devices), (DATA_AXIS,))
        _MESH_CACHE[len(devices)] = mesh
    return mesh


def _place(slabs: list[np.ndarray], mesh: Mesh, spec: P):
    """[S, ...] over the mesh's data axis from one host slab a shard. Each
    device is handed its own shards' rows straight from host memory:
    nothing is staged on another device, nothing is stacked whole on the
    host (a device that holds several shards gets their stack alone)."""
    def block(index: tuple) -> np.ndarray:
        mine = slabs[index[0]]
        return mine[0][None] if len(mine) == 1 else np.stack(mine)

    return jax.make_array_from_callback(
        (len(slabs), *slabs[0].shape), NamedSharding(mesh, spec), block)


def _peaks(mesh: Mesh) -> list[tuple[int, int]] | None:
    """(peak, in use) bytes of each mesh device by the backend's own
    count; None where it keeps none (the CPU's)."""
    stats = [dev.memory_stats() or {} for dev in mesh.devices.flat]
    if not all("peak_bytes_in_use" in st and "bytes_in_use" in st
               for st in stats):
        return None
    return [(int(st["peak_bytes_in_use"]), int(st["bytes_in_use"]))
            for st in stats]


def _can_serve(snaps: list, field: str, *, filtered: bool = False,
               nested: bool = False) -> tuple[str, int] | None:
    """Returns (similarity, dims) if every shard can be served exactly,
    else None. ANN-indexed segments fall back on UNFILTERED queries: the
    host path would answer those with IVF-PQ, and this path must stay
    bit-identical to the host. With a filter, the host path itself runs an
    exact scan (executor.shard_knn_selection gates ANN on filter is None),
    so ANN segments are eligible here too. `nested`: the field is a
    multi-vector field (always exact)."""
    from opensearch_tpu.ops.knn import canonical_similarity

    similarity = None
    dims = None
    any_field = False
    for snap in snaps:
        for host, dev in snap.segments:
            vf = (dev.nested_vector_fields or {}).get(field) if nested \
                else dev.vector_fields.get(field)
            if vf is None:
                continue
            any_field = True
            if getattr(vf, "ann", None) is not None and not filtered:
                return None
            sim = canonical_similarity(vf.similarity)
            if similarity is None:
                similarity, dims = sim, vf.dims
            elif sim != similarity or vf.dims != dims:
                return None
    if not any_field:
        return None
    return similarity, dims


def _nested_slab(host, dev, field: str, dims: int, doc_start: int):
    """One segment's rows of a multi-vector field for a nested bundle:
    (vectors, norms, valid, parent in the shard's flat document space)."""
    hvf = host.nested_vector_fields.get(field)
    if hvf is None:
        return (np.zeros((0, dims), np.float32), np.zeros(0, np.float32),
                np.zeros(0, bool), np.zeros(0, np.int32))
    v = np.asarray(hvf.vectors, np.float32)
    # dev.live, as the flat slab's: the PUBLISHED live bitmap
    return (v, (v.astype(np.float64) ** 2).sum(axis=1).astype(np.float32),
            np.asarray(dev.live)[hvf.parent],
            (hvf.parent + doc_start).astype(np.int32))


def _build_bundle(snaps: list, field: str, dims: int, mesh: Mesh,
                  index_name: str = "_unknown",
                  generations: tuple = (),
                  nested: bool = False) -> _IndexBundle:
    per_shard_vecs: list[np.ndarray] = []
    per_shard_norms: list[np.ndarray] = []
    per_shard_valid: list[np.ndarray] = []
    per_shard_parent: list[np.ndarray] = []
    seg_offsets: list[list[tuple[int, int, int]]] = []
    for snap in snaps:
        chunks_v, chunks_n, chunks_ok, chunks_p = [], [], [], []
        offsets: list[tuple[int, int, int]] = []
        pos = 0
        for seg_idx, (host, dev) in enumerate(snap.segments):
            n = host.n_docs
            hvf = host.vector_fields.get(field)
            if nested:
                for out, part in zip(
                        (chunks_v, chunks_n, chunks_ok, chunks_p),
                        _nested_slab(host, dev, field, dims, pos)):
                    out.append(part)
            elif hvf is None:
                chunks_v.append(np.zeros((n, dims), np.float32))
                chunks_n.append(np.zeros(n, np.float32))
                chunks_ok.append(np.zeros(n, bool))
            else:
                v = np.asarray(hvf.vectors[:n], np.float32)
                chunks_v.append(v)
                # identical norm formula to index/device.to_device so scores
                # match the host path bit-for-bit
                chunks_n.append(
                    (v.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)
                )
                # dev.live, not host.live: deletes flip host.live in place
                # before refresh, but the host query path masks with the
                # PUBLISHED live bitmap (executor.py uses dev.live) — the
                # bundle must see exactly what the host path sees
                chunks_ok.append(
                    np.asarray(hvf.present[:n], bool)
                    & np.asarray(dev.live)[:n]
                )
            offsets.append((pos, seg_idx, n))
            pos += n
        seg_offsets.append(offsets)
        per_shard_vecs.append(
            np.concatenate(chunks_v) if chunks_v else np.zeros((0, dims), np.float32)
        )
        per_shard_norms.append(
            np.concatenate(chunks_n) if chunks_n else np.zeros(0, np.float32)
        )
        per_shard_valid.append(
            np.concatenate(chunks_ok) if chunks_ok else np.zeros(0, bool)
        )
        per_shard_parent.append(
            np.concatenate(chunks_p) if chunks_p else np.zeros(0, np.int32)
        )

    max_docs = max((v.shape[0] for v in per_shard_vecs), default=1)
    # bucket to the next power of two: keeps the compiled program stable
    # across refreshes that grow a shard slightly (query-shape cache,
    # SURVEY.md §7 hard part #3)
    n_flat = 1 << max(int(max_docs - 1).bit_length(), 3)
    # a nested bundle's winners are documents: their ids' shard stride
    doc_stride = 1 << max(int(max(
        (sum(n for _s, _i, n in offs) for offs in seg_offsets),
        default=1) - 1).bit_length(), 3)

    def pad(a: np.ndarray, fill=0) -> np.ndarray:
        out = np.full((n_flat, *a.shape[1:]), fill, dtype=a.dtype)
        out[: a.shape[0]] = a
        return out

    # HBM residency: the slab stays device-resident until the registry
    # evicts it (superseded generation, byte budget, invalidation)
    from opensearch_tpu.telemetry.device_ledger import (
        KIND_MESH_BUNDLE,
        default_ledger,
        device_bytes,
    )

    before = _peaks(mesh)
    with tracing.span(span_names.MESH_BUNDLE_BUILD) as span:
        bundle = _IndexBundle(
            vectors=_place([pad(v) for v in per_shard_vecs], mesh,
                           P(DATA_AXIS, None, None)),
            norms_sq=_place([pad(n) for n in per_shard_norms], mesh,
                            P(DATA_AXIS)),
            valid=_place([pad(v, fill=False) for v in per_shard_valid], mesh,
                         P(DATA_AXIS)),
            n_flat=n_flat,
            seg_offsets=seg_offsets,
            parent=(_place([pad(p, fill=-1) for p in per_shard_parent],
                           mesh, P(DATA_AXIS)) if nested else None),
            id_stride=doc_stride if nested else n_flat,
        )
        by_device = device_bytes(*(a for a in (
            bundle.vectors, bundle.norms_sq, bundle.valid, bundle.parent)
            if a is not None))
        after = _peaks(mesh)
        span.set_attribute("devices", len(by_device))
        span.set_attribute("shards", len(snaps))
        span.set_attribute("bytes_per_device", max(by_device.values()))
        # how far the build pushed any chip's peak above what that chip
        # holds once it is done (and above its peak before): a copy staged
        # on one chip on its way to the others shows here. A backend that
        # keeps no peaks reads 0: `_place` puts nothing but each device's
        # own shards, and `register` below refuses a split that holds more
        span.set_attribute("staging_bytes", max(
            max(0, peak - max(peak0, in_use))
            for (peak, in_use), (peak0, _) in zip(after, before))
            if before and after else 0)
    bundle.allocation = default_ledger.register(
        KIND_MESH_BUNDLE, bundle.nbytes, index=index_name, field=field,
        generation=tuple(generations),
        device=f"mesh[{len(by_device)}]", by_device=by_device,
    )
    return bundle


def _filter_valid_mask(
    shards: list,
    snaps: list,
    knn_filter,
    alias_filters: list | None,
    n_flat: int,
) -> tuple[np.ndarray, int]:
    """[S, n_flat] bool: per-query-eligible docs under the knn-level filter
    and each shard's alias filter, laid out exactly like the bundle slabs
    (segment-ascending, doc-ascending, zero-padded); and the posting entries
    the filters' keyword clauses scattered to make it. Runs the SAME
    SegmentExecutor the host path uses for the filter
    (executor.shard_knn_selection), so pre-filter semantics match."""
    from opensearch_tpu.search.executor import SegmentExecutor, ShardContext

    out = np.zeros((len(snaps), n_flat), bool)
    postings = 0
    for si, (shard, snap) in enumerate(zip(shards, snaps)):
        fnodes = [f for f in (
            knn_filter, alias_filters[si] if alias_filters else None
        ) if f is not None]
        ctx = ShardContext(snap, shard.mapper_service)
        pos = 0
        for host, dev in snap.segments:
            n = host.n_docs
            m = np.ones(n, bool)
            for fnode in fnodes:
                ex = SegmentExecutor(ctx, host, dev)
                m &= np.asarray(ex.execute(fnode).mask)[:n]
                postings += ex.postings
            out[si, pos:pos + n] = m
            pos += n
    return out, postings


def try_distributed_knn_batch(
    shards: list,
    snaps: list,
    nodes: list,
    fetch_k: int,
    alias_filters: list | None = None,
) -> list[list[ShardQueryResult]] | None:
    """Compatibility wrapper over :func:`mesh_knn_batch` returning only the
    per-query per-shard results (the msearch batching path)."""
    out = mesh_knn_batch(
        shards, snaps, nodes, fetch_k, alias_filters=alias_filters
    )
    return None if out is None else out.per_query


def mesh_knn_batch(
    shards: list,
    snaps: list,
    nodes: list,
    fetch_k: int,
    alias_filters: list | None = None,
    nested: bool = False,
) -> MeshLaunchOutcome | None:
    """Execute B KnnQuery nodes (same field/k/filter) in ONE device
    dispatch. Returns a MeshLaunchOutcome (per-query per-shard results,
    device-merged row order, launch attribution), or None when this path
    cannot reproduce the host result. `nested`: the nodes are the `knn`s
    of `nested` queries over a multi-vector field (no filter): the winners
    are documents, each scored by its best row."""
    # the batcher's (or the service's) `launch` detail span, if this request
    # is detailed: it learns the launch's shape below
    launch_span = tracing.active_tracer().current_span()
    with tracing.detail(span_names.LAUNCH_HOST_PRE):
        if not shards or len(shards) != len(snaps) or not nodes:
            return None
        s = len(shards)
        first = nodes[0]
        # batch members must share the device program and the filter mask;
        # filters are compared by identity (msearch groups by equal body JSON,
        # the single-query path always has B == 1)
        for node in nodes:
            if (node.field != first.field or int(node.k) != int(first.k)
                    or node.filter is not first.filter):
                return None
        has_filter = first.filter is not None or (
            alias_filters is not None and any(f is not None for f in alias_filters)
        )
        if nested and has_filter:
            return None
        served = _can_serve(snaps, first.field, filtered=has_filter,
                            nested=nested)
        if served is None:
            _count("fallbacks")
            return None
        similarity, dims = served
        if any(len(node.vector) != dims for node in nodes):
            return None

        mesh = _serving_mesh(s)
        n_devices = mesh.devices.size

        index_name = shards[0].shard_id.index
        # generation-pinned residency key (ShardMeshRegistry.residency_key):
        # a refresh mid-flight is a different key, so no query is ever merged
        # against another snapshot's slab
        cache_key = registry.residency_key(index_name, first.field, shards, snaps)
        bundle = registry.get(cache_key)
        if bundle is None:
            # build OUTSIDE the registry lock: the device upload can take
            # seconds for a large index and must not stall warm-path queries of
            # other indexes. A same-key race (two cold misses) wastes one
            # duplicate upload at worst — registry.put keeps the cache itself
            # consistent, returns the winning bundle, and frees the loser's
            # ledger allocation.
            bundle = registry.put(
                cache_key,
                _build_bundle(snaps, first.field, dims, mesh,
                              index_name=index_name,
                              generations=cache_key[4], nested=nested),
            )

        valid = bundle.valid
        if has_filter:
            with tracing.detail(span_names.FILTER_MASK) as masked:
                fmask, postings = _filter_valid_mask(
                    shards, snaps, first.filter, alias_filters, bundle.n_flat
                )
                # per-request upload, consumed by this launch: transient in
                # the residency ledger (allocated and freed in one step)
                from opensearch_tpu.telemetry.device_ledger import (
                    KIND_QUERY_BATCH,
                    default_ledger,
                )

                default_ledger.record_transient(KIND_QUERY_BATCH, fmask.nbytes)
                # from host memory to each device its own shards' rows: no
                # copy of the whole mask staged on one chip
                valid = valid & jax.device_put(
                    fmask, NamedSharding(mesh, P(DATA_AXIS))
                )
                if masked.detail is not None:
                    masked.set_attribute("rows", int(fmask.size))
                    masked.set_attribute(
                        "eligible", int(np.count_nonzero(fmask)))
                    masked.set_attribute("clauses", sum(
                        filter_clauses(f)
                        for f in (first.filter, *(alias_filters or ()))))
                    masked.set_attribute("postings", postings)
                    masked.set_attribute("upload_bytes", int(fmask.nbytes))
            # the one count of launches that carried a filter mask: the
            # node's `knn.filter.*` counters and this module's dict
            count_knn_filter(len(nodes), int(fmask.nbytes))
            _count("filtered")

        b = len(nodes)
        # pad B to a power of two: B is a static shape under jit, so raw batch
        # sizes would compile one program per msearch width (query-shape cache,
        # SURVEY.md §7 hard part #3); padding queries are zero vectors whose
        # results are sliced off
        b_pad = 1 << (b - 1).bit_length()
        if launch_span is not None and launch_span.name == span_names.LAUNCH:
            launch_span.set_attribute("devices", n_devices)
            launch_span.set_attribute("shards", s)
            launch_span.set_attribute("b_pad", b_pad)
            # device -> host transfers this launch makes: the packed output
            launch_span.set_attribute("host_copies", 1)
            launch_span.set_attribute("filtered", int(has_filter))
            launch_span.set_attribute("nested", int(nested))
        q_host = np.zeros((b_pad, dims), np.float32)
        for i, node in enumerate(nodes):
            q_host[i] = np.asarray(node.vector, np.float32)

        k_shard = max(1, min(int(first.k), bundle.n_flat))
        k_final = min(max(k_shard, int(fetch_k)), s * k_shard)
        # EXACT-path kernel policy (search.knn.kernel / score_precision): what
        # the one rule (ops/pallas_knn.fused_impl) RESOLVES it to for this
        # k, and the precision, are part of the program key, so a live flip
        # compiles a fresh mesh program and never re-ranks a batch formed
        # under the old policy.
        from opensearch_tpu.ops.pallas_knn import fused_impl, fused_pool_width
        from opensearch_tpu.search.ann import default_config as ann_config

        impl, interpret = fused_impl(ann_config.exact_kernel, k_shard)
        # a nested scan is fp32 whatever the setting (ops/pallas_knn.
        # knn_nested)
        score_precision = "fp32" if nested else ann_config.score_precision
        doc_stride = bundle.id_stride if nested else None
        prog_key = (n_devices, s, bundle.n_flat, dims, k_shard, k_final,
                    similarity, b_pad, impl, interpret, score_precision)
        if nested:
            prog_key += (doc_stride,)
        with _CACHE_LOCK:
            program = _PROGRAM_CACHE.get(prog_key)
            retraced = program is None
            if program is None:
                program = build_knn_serving_step(
                    mesh, k_shard=k_shard, k_final=k_final,
                    similarity=similarity, kernel=impl,
                    score_precision=score_precision, interpret=interpret,
                    doc_stride=doc_stride,
                )
                _PROGRAM_CACHE[prog_key] = program

        queries = jnp.asarray(q_host)
        operands = (bundle.vectors, bundle.norms_sq, valid,
                    *((bundle.parent,) if nested else ()), queries)
    t0 = time.perf_counter_ns()
    with tracing.detail(span_names.LAUNCH_DEVICE) as span:
        span.set_attribute("retraced", retraced)
        with mesh:
            packed = program(*operands)
        # host materialization is the fence for this launch and its only
        # transfer: the host needs these rows anyway, so the copy doubles
        # as the wait (asking for it at dispatch, `copy_to_host_async`,
        # brought it no sooner on the chip: PERF.md §6, PR 30)
        vals, gids, counts = unpack(np.asarray(packed)[:b], k_final, s)
    wall_ns = time.perf_counter_ns() - t0
    with tracing.detail(span_names.LAUNCH_HOST_POST):
        launch_id = registry.next_launch_id()
        registry.record_launch_wall(wall_ns)
        registry.record_launch_kernel(impl, score_precision)
        # roofline accounting: ONE sharded launch against the mesh cost model
        # (per-slot scan + on-device all_gather/top_k merge)
        from opensearch_tpu.telemetry import roofline

        launch_params = dict(b=b_pad, s=s, n_flat=bundle.n_flat, d=dims,
                             k_shard=k_shard, devices=n_devices,
                             precision=score_precision,
                             r=fused_pool_width(k_shard, score_precision),
                             kernel=impl)
        family = "mesh_knn_nested" if nested else "mesh_knn_fused"
        roofline.record_launch(
            f"{family}[{score_precision}]", wall_ns, **launch_params)
        from opensearch_tpu.telemetry.device_ledger import (
            KIND_QUERY_BATCH,
            default_ledger,
        )

        default_ledger.record_transient(KIND_QUERY_BATCH, q_host.nbytes)
        # heat touch against the mesh bundle this launch scanned, bytes from
        # the same cost model the roofline fold used (telemetry/device_ledger)
        default_ledger.touch([getattr(bundle, "allocation", None)],
                             family=family, params=launch_params)
        if retraced:
            # program-cache miss == fresh jit entry for the mesh kernel family;
            # the first launch wall includes the compile
            default_ledger.record_compile(family, wall_ns)
        _count("distributed_searches")
        if s == 1:
            _count("single_shard")
        if b > 1:
            _count("batched_queries", b)

        out: list[list[ShardQueryResult]] = []
        premerged: list[list[tuple[int, ShardHit]]] = []
        for qi, node in enumerate(nodes):
            boost = np.float32(getattr(node, "boost", 1.0))
            per_shard_hits: list[list[ShardHit]] = [[] for _ in range(s)]
            # device row order IS the final merged order: (-score, shard asc,
            # segment asc, doc asc) — see build_knn_serving_step's tie-break
            rows: list[tuple[int, ShardHit]] = []
            for v, g in zip(vals[qi], gids[qi]):
                if not np.isfinite(v):
                    continue
                shard_idx, flat = divmod(int(g), bundle.id_stride)
                seg_idx, doc = bundle.locate(shard_idx, flat)
                hit = ShardHit(float(np.float32(v) * boost), seg_idx, doc)
                per_shard_hits[shard_idx].append(hit)
                rows.append((shard_idx, hit))
            results = []
            for shard_idx in range(s):
                hits = per_shard_hits[shard_idx]
                results.append(ShardQueryResult(
                    hits=hits,
                    total=int(counts[shard_idx, qi]),
                    max_score=max((h.score for h in hits), default=None),
                ))
            out.append(results)
            premerged.append(rows)
        return MeshLaunchOutcome(out, premerged, launch_id, wall_ns, retraced, s)


def clear_caches() -> None:
    registry.clear()
    _PROGRAM_CACHE.clear()
    _MESH_CACHE.clear()
