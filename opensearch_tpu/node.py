"""TpuNode: single-node engine facade (IndicesService + NodeClient analog).

The single-process composition root, mirroring the reference's Node wiring
(server/src/main/java/org/opensearch/node/Node.java:494 constructs
IndicesService:979, SearchService:1515, ActionModule:1165): owns the index
registry, routes documents to shards (OperationRouting: murmur3 % shards),
executes the document/bulk/search APIs with OpenSearch response shapes.

The multi-node story (cluster/ package: coordination, allocation,
replication fan-out) layers on top of this same class — a TpuNode hosts the
shards the cluster state assigns to it.
"""

from __future__ import annotations

import contextlib
import json
import logging
import re
import time
import uuid
from pathlib import Path
from typing import Any

from opensearch_tpu.common.errors import (
    DocumentMissingException,
    IllegalArgumentException,
    InputCoercionException,
    IndexClosedException,
    IndexNotFoundException,
    OpenSearchTpuException,
    ResourceAlreadyExistsException,
    ResourceNotFoundException,
    SearchContextMissingException,
    VersionConflictException,
)
from opensearch_tpu.common.timeutil import (
    now_millis as _now_ms,
    parse_time_value_millis,
)
from opensearch_tpu.common.hashing import shard_id_for_routing
from opensearch_tpu.common.settings import (
    Settings,
    setting_str,
    settings_section,
)
from opensearch_tpu.index.analysis import AnalysisRegistry
from opensearch_tpu.index.mapper import MapperService
from opensearch_tpu.index.shard import IndexShard, ShardId, translog_durability
from opensearch_tpu.search import service as search_service

logger = logging.getLogger(__name__)

# index names: anything except the reserved characters, no uppercase
# ASCII, not starting with _ - + (MetadataCreateIndexService.validateIndexName
# — non-ASCII like CJK is legal)
_INVALID_INDEX_CHARS = set(' "*\\<>|,/?#:')


def _valid_index_name(name: str) -> bool:
    if not name or name in (".", ".."):
        return False
    if any(c in _INVALID_INDEX_CHARS for c in name):
        return False
    if any("A" <= c <= "Z" for c in name):
        return False
    return not name.startswith(("_", "-", "+"))


def _flatten_source_fields(obj: dict, prefix: str = "") -> dict:
    out: dict = {}
    for k, v in obj.items():
        full = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten_source_fields(v, f"{full}."))
        else:
            out[full] = v
    return out


def fnmatch_one(name: str, pattern: str) -> bool:
    import fnmatch

    return fnmatch.fnmatch(name, pattern.strip())


def simple_match(name: str, pattern: str) -> bool:
    """`*`-only wildcard match (the reference's Regex.simpleMatch) — unlike
    fnmatch, `?` and `[...]` are literal characters, so an alias named
    `logs-[old]` can be addressed exactly."""
    parts = pattern.split("*")
    if len(parts) == 1:
        return name == pattern
    if not name.startswith(parts[0]) or not name.endswith(parts[-1]):
        return False
    pos = len(parts[0])
    for mid in parts[1:-1]:
        i = name.find(mid, pos, len(name) - len(parts[-1]) if parts[-1] else None)
        if i < 0:
            return False
        pos = i + len(mid)
    return pos + len(parts[-1]) <= len(name)


# defaults surfaced by ?include_defaults (IndexScopedSettings defaults)
INDEX_SETTING_DEFAULTS = {
    "index.refresh_interval": "1s",
    "index.max_result_window": "10000",
    "index.max_inner_result_window": "100",
    "index.max_rescore_window": "10000",
    "index.max_docvalue_fields_search": "100",
    "index.max_script_fields": "32",
    "index.max_ngram_diff": "1",
    "index.max_shingle_diff": "3",
    "index.max_terms_count": "65536",
    "index.requests.cache.enable": "true",
    "index.translog.durability": "REQUEST",
    "index.translog.flush_threshold_size": "512mb",
}


def index_settings_entry(raw_settings: dict, *, num_shards: int,
                         num_replicas: int, name: str | None = None,
                         flat: bool = False, include_defaults: bool = False,
                         extra: dict | None = None) -> dict:
    """One index's GET _settings entry — the shared shaping (stringify,
    `name` filter by flat dotted key, flat vs nested, defaults section)
    used by both TpuNode.get_settings and ClusterFacade.get_settings."""
    import fnmatch as _fn

    patterns = None
    if name and name not in ("_all", "*"):
        patterns = [p.strip() for p in str(name).split(",") if p.strip()]

    def select(flat_map: dict) -> dict:
        if patterns is None:
            return flat_map
        return {k: v for k, v in flat_map.items()
                if any(_fn.fnmatch(k, p) for p in patterns)}

    norm: dict[str, Any] = {}
    for k, v in Settings.from_nested(raw_settings or {}).as_dict().items():
        key = k if k.startswith("index.") else f"index.{k}"
        norm[key] = setting_str(v)
    norm["index.number_of_shards"] = str(num_shards)
    norm["index.number_of_replicas"] = str(num_replicas)
    norm.update(extra or {})
    entry = {"settings": settings_section(select(norm), flat)}
    if include_defaults:
        defaults = {k: v for k, v in INDEX_SETTING_DEFAULTS.items()
                    if k not in norm}
        entry["defaults"] = settings_section(select(defaults), flat)
    return entry


def _deep_merge(base: dict, overlay: dict) -> dict:
    """Recursive dict merge, overlay wins (template composition order)."""
    out = dict(base)
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _object_parents(ms) -> dict[str, str]:
    """Object/nested paths implied by dotted leaf names: any proper prefix
    of a mapper name that is not itself a mapper (multi-field parents ARE
    mappers and are excluded). The reference's ObjectMapper tree, recovered
    from the flattened registry."""
    parents: dict[str, str] = {}
    for fname in ms.mappers:
        parts = fname.split(".")
        for i in range(1, len(parts)):
            prefix = ".".join(parts[:i])
            if prefix in ms.mappers:
                continue
            parents[prefix] = (
                "nested" if prefix in getattr(ms, "nested_paths", set())
                else "object"
            )
    return parents


def build_field_caps(names: list, mapper_for, patterns: list,
                     include_unmapped: bool = False) -> dict:
    """Merge per-index field capabilities into the FieldCapabilities wire
    shape (FieldCapabilities.java): per (field, type) the `indices` list
    appears when the field is not single-typed across all queried indices
    (include_unmapped's pseudo-type "unmapped" counts), mixed
    searchability/aggregatability surfaces as `non_searchable_indices` /
    `non_aggregatable_indices`, and mapping `meta` merges into
    key -> sorted list of distinct values. Shared by TpuNode and
    ClusterFacade."""
    import fnmatch

    # field -> type -> {"indices": [...], "searchable": {idx: bool},
    #                   "aggregatable": {idx: bool}, "meta": [dict, ...]}
    by_field: dict[str, dict[str, dict]] = {}

    def slot_for(fname: str, ftype: str) -> dict:
        return by_field.setdefault(fname, {}).setdefault(
            ftype, {"indices": [], "searchable": {}, "aggregatable": {},
                    "meta": []},
        )

    for name in names:
        ms = mapper_for(name)
        for fname, mapper in ms.mappers.items():
            if not any(fnmatch.fnmatch(fname, p) for p in patterns):
                continue
            if mapper.type == "alias":
                # aliases report the TARGET's capabilities under the
                # queried name (QueryShardContext alias resolution)
                resolved = ms.field_mapper(fname)
                if resolved is None or resolved.type == "alias":
                    continue
                mapper = resolved
            ftype = mapper.original_type or mapper.type
            slot = slot_for(fname, ftype)
            slot["indices"].append(name)
            slot["searchable"][name] = bool(mapper.index)
            slot["aggregatable"][name] = bool(
                mapper.doc_values and mapper.type != "text"
            )
            if mapper.meta:
                slot["meta"].append(mapper.meta)
        for pname, ptype in _object_parents(ms).items():
            if not any(fnmatch.fnmatch(pname, p) for p in patterns):
                continue
            slot = slot_for(pname, ptype)
            slot["indices"].append(name)
            slot["searchable"][name] = False
            slot["aggregatable"][name] = False

    if include_unmapped:
        for fname, types in by_field.items():
            mapped: set = set()
            for slot in types.values():
                mapped.update(slot["indices"])
            missing = [n for n in names if n not in mapped]
            if missing:
                un = slot_for(fname, "unmapped")
                for n in missing:
                    un["indices"].append(n)
                    un["searchable"][n] = False
                    un["aggregatable"][n] = False

    caps: dict[str, dict[str, dict]] = {}
    for fname, types in sorted(by_field.items()):
        conflicted = len(types) > 1
        caps[fname] = {}
        for ftype, slot in types.items():
            s_vals = list(slot["searchable"].values())
            a_vals = list(slot["aggregatable"].values())
            entry: dict[str, Any] = {
                "type": ftype,
                "searchable": bool(s_vals) and all(s_vals),
                "aggregatable": bool(a_vals) and all(a_vals),
            }
            if conflicted:
                # every type of a multi-typed field lists its members
                entry["indices"] = sorted(slot["indices"])
            if any(s_vals) and not all(s_vals):
                entry["non_searchable_indices"] = sorted(
                    n for n, v in slot["searchable"].items() if not v
                )
            if any(a_vals) and not all(a_vals):
                entry["non_aggregatable_indices"] = sorted(
                    n for n, v in slot["aggregatable"].items() if not v
                )
            merged_meta: dict[str, set] = {}
            for m in slot["meta"]:
                for k, v in m.items():
                    merged_meta.setdefault(k, set()).add(str(v))
            if merged_meta:
                entry["meta"] = {
                    k: sorted(vs) for k, vs in sorted(merged_meta.items())
                }
            caps[fname][ftype] = entry
    return {"indices": names, "fields": caps}


class IndexService:
    """Per-index container (index module + its shards)."""

    def __init__(self, name: str, path: Path, settings: dict, mappings: dict | None):
        self.name = name
        self.path = path
        self.settings = settings
        analysis = AnalysisRegistry.from_index_settings(
            (settings.get("analysis") if isinstance(settings.get("analysis"), dict) else None)
        )
        self.mapper_service = MapperService(mappings, analysis)
        self.mapper_service.ignore_malformed_default = str(
            self.setting("mapping.ignore_malformed", False)
        ).lower() == "true"
        self.num_shards = int(settings.get("number_of_shards", 1))
        self.num_replicas = int(settings.get("number_of_replicas", 1))
        self.creation_date = int(time.time() * 1000)
        # index UUID (IndexMetadata.INDEX_UUID): 22-char url-safe base64
        import base64 as _b64
        import os as _os

        self.uuid = _b64.urlsafe_b64encode(_os.urandom(16)).decode()[:22]
        # alias name -> config ({"filter":..., "routing":...,
        # "is_write_index":...}); the per-index slice of AliasMetadata
        self.aliases: dict[str, dict] = {}
        self.closed = False
        self.shards: dict[int, IndexShard] = {}
        durability = translog_durability(settings)
        from opensearch_tpu.parallel.mesh import shard_device

        for s in range(self.num_shards):
            # each shard's columns on the chip that holds its slice of the
            # mesh bundle, and on no other
            self.shards[s] = IndexShard(
                ShardId(name, s), path / str(s), self.mapper_service,
                durability=durability,
                device=shard_device(s, self.num_shards),
            )

    def setting(self, key: str, default=None):
        """Look up an index setting by dotted key regardless of storage
        shape. `self.settings` holds the NESTED form (create_index re-nests),
        so a plain .get("mapping.nested_objects.limit") always misses;
        flatten first and accept both bare and "index."-prefixed keys
        (IndexSettings.getValue analog). The flat view is cached — this
        sits on the per-document and per-search hot paths — and
        invalidated by put_index_settings via settings_changed()."""
        flat = getattr(self, "_flat_settings", None)
        if flat is None:
            flat = self._flat_settings = \
                Settings.from_nested(self.settings or {}).as_dict()
        if key in flat:
            return flat[key]
        return flat.get(f"index.{key}", default)

    def settings_changed(self) -> None:
        """Drop the cached flat-settings view after a settings update."""
        self._flat_settings = None

    def shard_for(self, doc_id: str, routing: str | None) -> IndexShard:
        sid = shard_id_for_routing(routing or doc_id, self.num_shards)
        return self.shards[sid]

    def close(self) -> None:
        for shard in self.shards.values():
            shard.close()


class TpuNode:
    def __init__(self, data_path: str | Path, node_name: str = "node-0"):
        self.data_path = Path(data_path)
        self.node_name = node_name
        self.indices: dict[str, IndexService] = {}
        # scroll/PIT reader contexts (SearchService's ReaderContext registry)
        self._reader_contexts: dict[str, dict] = {}
        self._state_file = self.data_path / "indices.json"
        self._recover_indices()
        from opensearch_tpu.ingest import IngestService

        self.ingest = IngestService(self.data_path / "ingest_pipelines.json")
        from opensearch_tpu.snapshots import SnapshotsService

        self.snapshots = SnapshotsService(self)
        from opensearch_tpu.search.pipeline import SearchPipelineService

        self.search_pipelines = SearchPipelineService(
            self.data_path / "search_pipelines.json"
        )
        from opensearch_tpu.common.breaker import HierarchyBreakerService
        from opensearch_tpu.index.pressure import IndexingPressure
        from opensearch_tpu.tasks import TaskManager

        self.task_manager = TaskManager(node_name)
        self.breakers = HierarchyBreakerService()
        self.indexing_pressure = IndexingPressure()
        self._pressure_depth = 0
        # (index, shard_id) of the most recent write, set by the inner write
        # path AFTER pipeline rerouting — see _write_pressure docstring
        self._last_write_shard: tuple[str, int] | None = None
        # shards with translog appends not yet fsynced this request
        self._dirty_translog_shards: set = set()
        from opensearch_tpu.search.backpressure import SearchBackpressureService

        self.search_backpressure = SearchBackpressureService(self.task_manager)
        from opensearch_tpu.telemetry.slowlog import SlowLog

        from opensearch_tpu.telemetry.tracing import Telemetry

        self.telemetry = Telemetry()  # per-node: metrics must not leak
        from opensearch_tpu.common.monitor import MonitorService

        self.monitor = MonitorService(self.data_path)
        from opensearch_tpu.wlm import QueryGroupService

        self.query_groups = QueryGroupService(
            self.data_path / "query_groups.json"
        )
        from opensearch_tpu.index.request_cache import RequestCache

        self.request_cache = RequestCache()
        # kNN dispatch batcher (search/batcher.py): the scheduler is
        # process-wide (one process == one device), the node adopts it for
        # settings + stats + metrics wiring. Last-constructed node owns the
        # metrics sink, matching the one-real-node-per-process deployment.
        from opensearch_tpu.search import batcher as _batcher_mod

        self.knn_batcher = _batcher_mod.default_batcher
        self.knn_batcher.metrics = self.telemetry.metrics
        # request-detail captures (telemetry/tracing.py): written under the
        # data path when a profiler session ends, with these counters
        # snapshotted at its open and close
        from opensearch_tpu.telemetry.device_ledger import (
            backend_memory,
            default_ledger,
        )

        self.telemetry.tracer.capture_dir = self.data_path / "telemetry"
        # how a kNN selection reached the hits (search/executor.py):
        # registered here so that `_nodes/stats` shows a 0 as a 0
        knn_collect = {
            "dense": self.telemetry.metrics.counter("knn.collect.dense"),
            "sparse": self.telemetry.metrics.counter("knn.collect.sparse"),
        }
        # filtered kNN queries served and the bytes of the eligibility
        # masks built for them (search/executor.py count_knn_filter), and
        # the keyword fields' ordinal-major views built, once a segment and
        # field on its first keyword clause (executor._keyword_postings)
        knn_filter = {
            "requests": self.telemetry.metrics.counter("knn.filter.requests"),
            "mask_bytes": self.telemetry.metrics.counter(
                "knn.filter.mask_bytes"),
            "postings_builds": self.telemetry.metrics.counter(
                "knn.filter.postings_builds"),
        }
        # hybrid searches served, and what their (and any other query's)
        # BM25 scoring launched: device launches and the posting entries
        # of the terms they looked up (search/executor.py `_bm25`)
        lexical = {
            "hybrid_requests": self.telemetry.metrics.counter(
                "search.hybrid.requests"),
            "bm25_launches": self.telemetry.metrics.counter(
                "search.bm25.launches"),
            "bm25_postings": self.telemetry.metrics.counter(
                "search.bm25.postings"),
        }
        self.telemetry.tracer.capture_counters = lambda: {
            "knn_batch": dict(self.knn_batcher.stats),
            "knn_collect": {k: c.value for k, c in knn_collect.items()},
            "knn_filter": {k: c.value for k, c in knn_filter.items()},
            "lexical": {k: c.value for k, c in lexical.items()},
            "device_resident_bytes": default_ledger.resident_bytes(),
            "device_resident_by_device": default_ledger.device_totals(),
            "device_backend_memory": backend_memory(),
        }
        # roofline recorder (telemetry/roofline.py): process-wide like the
        # batcher; this node is its fallback metrics sink (active_metrics()
        # still attributes per executing request scope). Peaks calibrate
        # HERE, at boot (cached per platform; a stub installed earlier
        # wins) — never lazily inside a stats poll or Prometheus scrape,
        # where the one-shot microbenchmark would block the monitoring
        # path and measure a contended ceiling.
        from opensearch_tpu.telemetry import roofline as _roofline_mod

        _roofline_mod.default_recorder.metrics = self.telemetry.metrics
        _roofline_mod.ensure_peaks()
        # priority-lane bookkeeping (search/lanes.py): the HTTP server
        # submits/sheds against this tracker so the `tail` stats section
        # (and the bench) can read lane depths off the node handle
        from opensearch_tpu.search import lanes as _lanes_mod

        self.lane_tracker = _lanes_mod.LaneTracker()
        from opensearch_tpu.index.remote_store import RemoteStoreService

        self.remote_store = RemoteStoreService(self)
        from opensearch_tpu.persistent import PersistentTasksService

        self.persistent_tasks = PersistentTasksService(
            self.data_path / "persistent_tasks.json"
        )
        self.persistent_tasks.resume_incomplete()
        self.search_slowlog = SlowLog("search")
        self.indexing_slowlog = SlowLog("indexing")
        self._configure_slowlogs()
        # cluster-coordination metadata surfaced by /_cluster/state
        # (CoordinationMetadata.VotingConfigExclusion)
        self._voting_config_exclusions: list[dict] = []
        self.cluster_uuid = uuid.uuid4().hex[:22]
        self._state_version = 1
        # persisted dynamic settings re-apply on boot (batcher config,
        # request-cache budget survive restart like persistent settings do)
        self.get_cluster_settings()
        self._apply_dynamic_node_settings()

    def _configure_slowlogs(self) -> None:
        """Pick up index.search.slowlog.threshold.query.* /
        index.indexing.slowlog.threshold.index.* from any index's settings
        (node-wide loggers; the reference scopes per index). Thresholds
        reset first so deleted/changed indices don't leave stale levels."""
        from opensearch_tpu.telemetry.slowlog import LEVELS

        for sl in (self.search_slowlog, self.indexing_slowlog):
            sl.thresholds = {lvl: -1 for lvl in LEVELS}
        for svc in self.indices.values():
            s = svc.settings
            q = (((s.get("search") or {}).get("slowlog") or {})
                 .get("threshold") or {}).get("query") or {}
            if q:
                self.search_slowlog.configure(q)
            i = (((s.get("indexing") or {}).get("slowlog") or {})
                 .get("threshold") or {}).get("index") or {}
            if i:
                self.indexing_slowlog.configure(i)

    # -- index lifecycle ---------------------------------------------------

    def _index_path(self, name: str) -> Path:
        return self.data_path / "indices" / name

    def _persist_index_registry(self) -> None:
        self.data_path.mkdir(parents=True, exist_ok=True)
        registry = {
            name: {
                "settings": svc.settings,
                "mappings": svc.mapper_service.to_dict(),
                "aliases": svc.aliases,
                "closed": svc.closed,
                "restored_from_snapshot": getattr(
                    svc, "restored_from_snapshot", None),
            }
            for name, svc in self.indices.items()
        }
        self._state_file.write_text(json.dumps(registry))

    def _recover_indices(self) -> None:
        if not self._state_file.exists():
            return
        registry = json.loads(self._state_file.read_text())
        for name, meta in registry.items():
            svc = IndexService(
                name, self._index_path(name), meta["settings"], meta["mappings"]
            )
            svc.aliases = meta.get("aliases", {})
            svc.closed = meta.get("closed", False)
            if meta.get("restored_from_snapshot"):
                svc.restored_from_snapshot = meta["restored_from_snapshot"]
            self.indices[name] = svc

    def create_index(self, name: str, body: dict | None = None) -> dict:
        if not _valid_index_name(name):
            raise IllegalArgumentException(f"invalid index name [{name}]")
        if name in self.indices:
            raise ResourceAlreadyExistsException(f"index [{name}] already exists")
        body = body or {}
        settings = body.get("settings") or {}
        mappings = body.get("mappings")
        aliases = dict(body.get("aliases") or {})
        # composable index templates: template layers under the request body
        tmpl = self._template_for_index(name)
        if tmpl is not None:
            settings = _deep_merge(tmpl["settings"], settings)
            mappings = _deep_merge(tmpl["mappings"], mappings or {}) or None
            aliases = {**tmpl["aliases"], **aliases}
        # accept both flat ("index.number_of_shards") and nested forms
        flat = Settings.from_nested(settings).as_dict()
        norm = {}
        for k, v in flat.items():
            norm[k[len("index."):] if k.startswith("index.") else k] = v
        # analysis config must stay nested
        nested = Settings.from_flat(norm).as_nested()
        svc = IndexService(
            name, self._index_path(name), nested, mappings
        )
        for alias, conf in aliases.items():
            if alias in self.indices:
                raise IllegalArgumentException(
                    f"alias [{alias}] clashes with an index name"
                )
            svc.aliases[alias] = dict(conf or {})
        self.indices[name] = svc
        self._persist_index_registry()
        self._configure_slowlogs()
        return {"acknowledged": True, "shards_acknowledged": True, "index": name}

    def attach_index(self, name: str, settings: dict, mappings: dict | None) -> "IndexService":
        """Register an index whose shard files already exist on disk (the
        restore path: RestoreService writes files, then the shards recover
        from their commit points)."""
        if name in self.indices:
            raise ResourceAlreadyExistsException(f"index [{name}] already exists")
        self.indices[name] = IndexService(
            name, self._index_path(name), settings, mappings
        )
        self._persist_index_registry()
        self._configure_slowlogs()
        return self.indices[name]

    def delete_index(self, expr: str, *, ignore_unavailable: bool = False,
                     allow_no_indices: bool = True) -> dict:
        """DELETE /{index}. Wildcards expand over concrete indices only;
        explicit alias names are rejected (TransportDeleteIndexAction uses
        strict concrete-index resolution) unless ignore_unavailable."""
        import fnmatch

        alias_map = self._alias_map()
        targets: list[str] = []
        matched_any = False
        for part in expr.split(","):
            part = part.strip()
            if part in ("_all", "*"):
                # list() snapshots: wildcard resolution runs on the
                # parallel search pool concurrently with index creation
                targets.extend(list(self.indices))
                matched_any = True
            elif "*" in part or "?" in part:
                hits = [n for n in list(self.indices)
                        if fnmatch.fnmatch(n, part)]
                targets.extend(hits)
                matched_any = matched_any or bool(hits)
                if not hits and not allow_no_indices:
                    # per-expression: an empty wildcard fails fast
                    raise IndexNotFoundException(part)
            elif part in alias_map:
                if ignore_unavailable:
                    continue
                raise IllegalArgumentException(
                    f"The provided expression [{part}] matches an alias, "
                    f"specify the corresponding concrete indices instead."
                )
            elif part in self.indices:
                targets.append(part)
                matched_any = True
            elif not ignore_unavailable:
                raise IndexNotFoundException(part)
        if not matched_any and not allow_no_indices:
            raise IndexNotFoundException(expr)
        import shutil

        for name in dict.fromkeys(targets):
            svc = self._get_index(name)
            svc.close()
            del self.indices[name]
            # release the index's device-resident mesh bundles promptly
            # (the cluster path does this at state application; without it
            # a deleted index's slab sat in HBM until LRU/budget pressure —
            # a leak the residency ledger made visible)
            from opensearch_tpu.cluster.shard_mesh import default_registry

            default_registry.invalidate_index(name)
            shutil.rmtree(self._index_path(name), ignore_errors=True)
        self._persist_index_registry()
        self._configure_slowlogs()
        return {"acknowledged": True}

    def _get_index(self, name: str) -> IndexService:
        svc = self.indices.get(name)
        if svc is None:
            raise IndexNotFoundException(name)
        return svc

    def _get_or_autocreate(self, name: str) -> IndexService:
        if name not in self.indices:
            self.create_index(name, {})
        return self.indices[name]

    @staticmethod
    def _resolve_date_math_name(name: str) -> str:
        """"<logstash-{now/M}>" -> "logstash-2026.07.01"
        (IndexNameExpressionResolver.DateMathExpressionResolver; default
        format uuuu.MM.dd, rounding per the date-math unit)."""
        if not (name.startswith("<") and name.endswith(">")):
            return name
        import datetime as _dt
        import re as _re

        inner = name[1:-1]

        def repl(m):
            expr = m.group(1)
            fmt = "%Y.%m.%d"
            if "{" in expr:  # custom format {now/M{yyyy.MM}}
                expr, _, f = expr.partition("{")
                f = f.rstrip("}")
                fmt = (f.replace("yyyy", "%Y").replace("uuuu", "%Y")
                        .replace("MM", "%m").replace("dd", "%d"))
            now = _dt.datetime.now(_dt.timezone.utc)
            rest = expr[3:] if expr.startswith("now") else ""
            while rest:
                m2 = _re.match(r"([+-]\d+[yMwdhHms]|/[yMwdhHms])", rest)
                if not m2:
                    break
                op = m2.group(1)
                rest = rest[len(op):]
                if op.startswith("/"):
                    unit = op[1:]
                    if unit == "M":
                        now = now.replace(day=1, hour=0, minute=0,
                                          second=0, microsecond=0)
                    elif unit in ("d",):
                        now = now.replace(hour=0, minute=0, second=0,
                                          microsecond=0)
                    elif unit == "y":
                        now = now.replace(month=1, day=1, hour=0,
                                          minute=0, second=0,
                                          microsecond=0)
                else:
                    sign = 1 if op[0] == "+" else -1
                    n_, unit = int(op[1:-1]), op[-1]
                    delta = {"d": _dt.timedelta(days=n_),
                             "w": _dt.timedelta(weeks=n_),
                             "h": _dt.timedelta(hours=n_),
                             "H": _dt.timedelta(hours=n_),
                             "m": _dt.timedelta(minutes=n_),
                             "s": _dt.timedelta(seconds=n_)}.get(
                        unit, _dt.timedelta())
                    now = now + sign * delta
            return now.strftime(fmt)

        return _re.sub(r"\{([^}]*(?:\{[^}]*\})?)\}", repl, inner)

    def resolve_indices(self, expr: str, *, ignore_unavailable: bool = False,
                        allow_no_indices: bool = True,
                        expand_wildcards: str = "open") -> list[str]:
        """Index name/pattern/alias resolution (comma lists, wildcards,
        _all). Wildcards match concrete index names AND alias names, like
        the reference's IndexNameExpressionResolver; aliases expand to
        their member indices. `ignore_unavailable` drops missing concrete
        names instead of 404ing; `expand_wildcards=none` disables pattern
        expansion; empty expansion 404s when `allow_no_indices` is false
        (IndicesOptions semantics)."""
        alias_map = self._alias_map()
        expand = {w.strip() for w in str(expand_wildcards).split(",")}
        wildcards_on = "none" not in expand
        if "all" in expand:
            expand |= {"open", "closed"}

        def state_ok(name: str) -> bool:
            # wildcard expansion honors open/closed selection
            # (IndicesOptions.expandWildcards*)
            if self.indices[name].closed:
                return "closed" in expand
            return "open" in expand or not (expand & {"open", "closed"})

        if expr in ("_all", "*", ""):
            names = ([n for n in sorted(self.indices) if state_ok(n)]
                     if wildcards_on else [])
            if not names and not allow_no_indices:
                raise IndexNotFoundException(expr or "_all")
            return names
        names: list[str] = []
        import fnmatch

        candidates = sorted(set(self.indices) | set(alias_map))
        for part in expr.split(","):
            part = self._resolve_date_math_name(part.strip())
            if "*" in part or "?" in part:
                if not wildcards_on:
                    continue
                matched = False
                for n in candidates:
                    if fnmatch.fnmatch(n, part):
                        expanded = [
                            m for m in alias_map.get(n, [n]) if state_ok(m)
                        ]
                        names.extend(expanded)
                        matched = True
                if not matched and not allow_no_indices:
                    raise IndexNotFoundException(part)
            elif part in alias_map:
                names.extend(alias_map[part])
            else:
                if part not in self.indices:
                    if ignore_unavailable:
                        continue
                    raise IndexNotFoundException(part)
                names.append(part)
        if not names and not allow_no_indices:
            raise IndexNotFoundException(expr)
        seen = set()
        return [n for n in names if not (n in seen or seen.add(n))]

    # -- aliases (cluster/metadata/AliasMetadata + TransportIndicesAliasesAction
    # analog) ---------------------------------------------------------------

    def _alias_map(self) -> dict[str, list[str]]:
        """alias name -> sorted member index names. Iterates a list()
        snapshot: searches resolve aliases on the parallel pool while the
        serial data worker may be inserting/deleting indices."""
        out: dict[str, list[str]] = {}
        for name, svc in list(self.indices.items()):
            for alias in list(svc.aliases):
                out.setdefault(alias, []).append(name)
        return {a: sorted(ns) for a, ns in out.items()}

    def update_aliases(self, body: dict) -> dict:
        actions = (body or {}).get("actions")
        if not isinstance(actions, list) or not actions:
            raise IllegalArgumentException("[aliases] requires [actions]")
        # validate + stage first: the reference applies the action list
        # atomically in one cluster-state update
        staged: list[tuple[str, str, str, dict | None]] = []
        # indices removed by THIS request: an added alias may take a name
        # a remove_index in the same atomic batch is freeing
        removing_indices: set[str] = set()
        for action in actions:
            if isinstance(action, dict) and "remove_index" in action:
                conf0 = action["remove_index"]
                if isinstance(conf0, dict):
                    for iexpr in (conf0.get("indices")
                                  or ([conf0["index"]]
                                      if conf0.get("index") else [])):
                        try:
                            removing_indices.update(self.resolve_indices(
                                iexpr, expand_wildcards="all"))
                        except OpenSearchTpuException:
                            pass
        for action in actions:
            if not isinstance(action, dict) or len(action) != 1:
                raise IllegalArgumentException(
                    "each alias action must be a single-key object"
                )
            kind, conf = next(iter(action.items()))
            if kind not in ("add", "remove", "remove_index"):
                raise IllegalArgumentException(f"unknown alias action [{kind}]")
            if not isinstance(conf, dict):
                raise IllegalArgumentException(
                    f"[aliases] action [{kind}] requires an object body"
                )
            indices = conf.get("indices") or (
                [conf["index"]] if conf.get("index") else []
            )
            aliases = conf.get("aliases") or (
                [conf["alias"]] if conf.get("alias") else []
            )
            resolved: list[str] = []
            for iexpr in indices:
                resolved.extend(self.resolve_indices(
                    iexpr, expand_wildcards="all"))
            if not resolved:
                raise IllegalArgumentException(
                    f"[aliases] action [{kind}] requires an index"
                )
            if kind == "remove_index":
                staged.extend((kind, name, "", None) for name in resolved)
                continue
            if not aliases:
                if "aliases" in conf:
                    raise IllegalArgumentException("[aliases] can't be empty")
                raise IllegalArgumentException(
                    f"[aliases] action [{kind}] requires an alias"
                )
            for name in resolved:
                for alias in aliases:
                    if kind == "add" and alias in self.indices \
                            and alias not in removing_indices:
                        raise IllegalArgumentException(
                            f"alias [{alias}] clashes with an index name"
                        )
                    staged.append((kind, name, alias, conf))
        # removes must name an alias that actually exists somewhere in the
        # action's scope — the reference fails the whole request with
        # aliases_not_found (404) before mutating anything (must_exist=false
        # opts out). Validated pre-apply to keep the update atomic.
        remove_matched: dict[str, bool] = {}
        remove_opt_out: set[str] = set()
        for kind, name, alias, conf in staged:
            if kind != "remove":
                continue
            if (conf or {}).get("must_exist") is False:
                remove_opt_out.add(alias)
            svc = self._get_index(name)
            hit = alias in svc.aliases or any(
                simple_match(a, alias) for a in svc.aliases
            )
            remove_matched[alias] = remove_matched.get(alias, False) or hit
        missing = sorted(
            a for a, hit in remove_matched.items()
            if not hit and a not in remove_opt_out
        )
        if missing:
            raise ResourceNotFoundException(
                f"aliases [{','.join(missing)}] missing"
            )
        # alias mutations first, index deletions last: a remove_index in
        # the middle of the list must not invalidate later staged actions
        to_delete = [n for k, n, _, _ in staged if k == "remove_index"]
        for kind, name, alias, conf in staged:
            if kind == "remove_index":
                continue
            svc = self._get_index(name)
            if kind == "add":
                entry: dict = {}
                for key in ("filter", "routing", "index_routing",
                            "search_routing", "is_write_index", "is_hidden"):
                    if conf.get(key) is not None:
                        entry[key] = conf[key]
                svc.aliases[alias] = entry
            else:
                for a in list(svc.aliases):
                    if a == alias or simple_match(a, alias):
                        del svc.aliases[a]
        import shutil

        for name in to_delete:
            # delete by CONCRETE name: an add action in this same batch may
            # have just taken the name as an alias, which would trip
            # delete_index's alias-ambiguity check
            svc = self.indices.pop(name, None)
            if svc is not None:
                svc.close()
                shutil.rmtree(self._index_path(name), ignore_errors=True)
        if to_delete:
            self._configure_slowlogs()
        self._persist_index_registry()
        return {"acknowledged": True}

    def put_alias(self, index_expr: str, alias: str, body: dict | None = None) -> dict:
        conf = dict(body or {})
        conf["alias"] = alias
        conf["indices"] = self.resolve_indices(index_expr,
                                               expand_wildcards="all")
        return self.update_aliases({"actions": [{"add": conf}]})

    def delete_alias(self, index_expr: str, alias_expr: str) -> dict:
        import fnmatch

        # alias ops reach closed indices too (IndicesAliasesRequest
        # expands open and closed)
        names = self.resolve_indices(index_expr, expand_wildcards="all")
        removed = False
        for name in names:
            svc = self._get_index(name)
            for alias in list(svc.aliases):
                if alias_expr in ("_all", "*") or fnmatch.fnmatch(alias, alias_expr):
                    del svc.aliases[alias]
                    removed = True
        if not removed:
            raise ResourceNotFoundException(
                f"aliases [{alias_expr}] missing on indices {names}"
            )
        self._persist_index_registry()
        return {"acknowledged": True}

    def get_alias(self, index_expr: str | None = None,
                  alias_expr: str | None = None,
                  expand_wildcards: str = "all") -> dict:
        """GET [/{index}]/_alias[/{name}] (TransportGetAliasesAction):
        `name` takes comma lists, wildcards, and "-pattern" exclusions
        applied in order; a CONCRETE requested alias that resolves to
        nothing makes the whole response a 404 that still carries the
        found entries (the handler reads the `status`/`error` riders)."""
        import fnmatch

        names = (
            self.resolve_indices(index_expr,
                                 expand_wildcards=expand_wildcards)
            if index_expr else sorted(
                n for n in self.indices
                if "closed" in expand_wildcards or "all" in expand_wildcards
                or not self.indices[n].closed
            )
        )

        def echo(conf: dict) -> dict:
            # "routing" renders as index_routing + search_routing
            # (AliasMetadata's response shape); routing values are strings
            conf = dict(conf or {})
            if "routing" in conf:
                conf.setdefault("index_routing", str(conf["routing"]))
                conf.setdefault("search_routing", str(conf["routing"]))
                del conf["routing"]
            for k in ("index_routing", "search_routing"):
                if k in conf:
                    conf[k] = str(conf[k])
            return conf

        all_alias_names = {
            a for name in names for a in self._get_index(name).aliases
        }
        if alias_expr in ("_all", "*"):
            alias_expr = "*"  # explicit catch-all: alias-less indices drop
        parts = ([p.strip() for p in str(alias_expr).split(",") if p.strip()]
                 if alias_expr not in (None, "") else None)
        missing: list[str] = []
        selected: set | None = None
        if parts is not None:
            selected = set()
            # a leading "-name" with nothing selected yet is a LITERAL
            # alias request (dash included) and 404s; once any wildcard or
            # plain part appeared, "-x" is a plain exclusion
            active = False
            for part in parts:
                wildcard = "*" in part or "?" in part
                if part.startswith("-"):
                    pat = part[1:]
                    hits = {a for a in selected if fnmatch.fnmatch(a, pat)}
                    if hits:
                        selected -= hits
                    elif not wildcard and not active:
                        missing.append(part)
                    if wildcard:
                        active = True
                elif wildcard:
                    selected |= {a for a in all_alias_names
                                 if fnmatch.fnmatch(a, part)}
                    active = True
                else:
                    active = True
                    if part in all_alias_names:
                        selected.add(part)
                    else:
                        missing.append(part)

        out: dict[str, Any] = {}
        for name in names:
            svc = self._get_index(name)
            matched = {
                a: echo(c) for a, c in svc.aliases.items()
                if selected is None or a in selected
            }
            if matched or parts is None:
                out[name] = {"aliases": matched}
        if missing:
            missing.sort()
            label = "aliases" if len(missing) > 1 else "alias"
            out["error"] = f"{label} [{','.join(missing)}] missing"
            out["status"] = 404
        return out

    def resolve_write_target(self, name: str, for_write: bool = True) -> str:
        """Alias -> its write index (TransportBulkAction's write-alias
        resolution); concrete names pass through (may autocreate later).
        Reads (`for_write=False`) ignore write-index designations."""
        targets = self._alias_targets(name)
        if not targets:
            return name
        if len(targets) == 1:
            if for_write and targets[0][1].get("is_write_index") is False:
                raise IllegalArgumentException(
                    f"no write index is defined for alias [{name}]. The "
                    f"write index may be explicitly disabled using "
                    f"is_write_index=false or the alias points to multiple "
                    f"indices without one being designated as a write index"
                )
            return targets[0][0]
        writes = [n for n, c in targets if c.get("is_write_index")]
        if not for_write and len(writes) != 1:
            names_l = ", ".join(sorted(n for n, _c in targets))
            raise IllegalArgumentException(
                f"alias [{name}] has more than one index associated with "
                f"it [{names_l}], can't execute a single index op"
            )
        if len(writes) != 1:
            raise IllegalArgumentException(
                f"no write index is defined for alias [{name}]. The write "
                f"index may be explicitly disabled using is_write_index="
                f"false or the alias points to multiple indices without one "
                f"being designated as a write index"
            )
        return writes[0]

    def _resolve_write_alias(
        self, index: str, routing: str | None, for_write: bool = True,
        check_blocks: bool | None = None,
    ) -> tuple[str, str | None]:
        """(concrete index, effective routing) for a write/read-by-id op:
        alias write-index resolution + alias-level routing defaulting."""
        concrete = self.resolve_write_target(index, for_write=for_write)
        if concrete != index and routing is None:
            conf = self.indices[concrete].aliases.get(index) or {}
            routing = conf.get("index_routing", conf.get("routing"))
        if concrete in self.indices and self.indices[concrete].closed:
            raise IndexClosedException(concrete)
        if check_blocks is None:
            check_blocks = for_write
        if check_blocks and concrete in self.indices:
            # index-level write blocks (IndexMetadata.INDEX_WRITE_BLOCK /
            # READ_ONLY_BLOCK enforced at the TransportWriteAction gate);
            # read APIs that resolve with for_write=True only for alias
            # write-index semantics pass check_blocks=False
            svc = self.indices[concrete]
            for setting in ("blocks.write", "blocks.read_only"):
                bid, desc, _levels = self._INDEX_BLOCKS[setting]
                if str(svc.setting(setting, "false")).lower() == "true":
                    from opensearch_tpu.common.errors import (
                        ClusterBlockException,
                    )

                    raise ClusterBlockException(
                        f"index [{concrete}] blocked by: "
                        f"[FORBIDDEN/{bid}/{desc}];")
        return concrete, routing

    def _alias_targets(self, alias: str) -> list[tuple[str, dict]]:
        return [
            (name, svc.aliases[alias])
            for name, svc in sorted(self.indices.items())
            if alias in svc.aliases
        ]

    def resolve_search_shards(self, expr: str,
                              ignore_unavailable: bool = False) -> tuple[list, list]:
        """(shards, per-shard alias filter bodies, index names) for a
        search expression.
        Filtered aliases contribute their filter to exactly their member
        shards (the per-shard aliasFilter of ShardSearchRequest); closed
        indices are skipped by wildcards but rejected by explicit names."""
        alias_map = self._alias_map()
        import fnmatch

        per_index_filters: dict[str, list] = {}
        names: list[str] = []

        def add_index(name: str, filt: dict | None, explicit: bool) -> None:
            svc = self._get_index(name)
            if svc.closed:
                if explicit:
                    raise IndexClosedException(name)
                return
            if name not in per_index_filters:
                names.append(name)
                per_index_filters[name] = []
            if filt is not None:
                per_index_filters[name].append(filt)
            else:
                # unfiltered route to this index: filters don't restrict
                per_index_filters[name] = [None]

        def add_alias(alias: str, explicit: bool) -> None:
            for name, conf in self._alias_targets(alias):
                add_index(name, conf.get("filter"), explicit=False)
                if self._get_index(name).closed and explicit:
                    raise IndexClosedException(name)

        if expr in ("_all", "*", ""):
            for name in sorted(self.indices):
                add_index(name, None, explicit=False)
        else:
            candidates = sorted(set(self.indices) | set(alias_map))
            for part in expr.split(","):
                part = self._resolve_date_math_name(part.strip())
                if "*" in part or "?" in part:
                    for n in candidates:
                        if fnmatch.fnmatch(n, part):
                            if n in alias_map:
                                add_alias(n, explicit=False)
                            else:
                                add_index(n, None, explicit=False)
                elif part in alias_map:
                    add_alias(part, explicit=True)
                elif part in self.indices:
                    add_index(part, None, explicit=True)
                elif ignore_unavailable:
                    continue
                else:
                    raise IndexNotFoundException(part)

        shards: list = []
        filters: list = []
        for name in names:
            flist = per_index_filters[name]
            if None in flist or not flist:
                filt = None
            elif len(flist) == 1:
                filt = flist[0]
            else:
                filt = {"bool": {"should": flist, "minimum_should_match": 1}}
            for shard in self._get_index(name).shards.values():
                shards.append(shard)
                filters.append(filt)
        return shards, filters, names

    # -- index templates (MetadataIndexTemplateService analog: composable
    # V2 templates + component templates) ----------------------------------

    def _templates_file(self) -> Path:
        return self.data_path / "templates.json"

    # -- stored scripts (cluster state scripts; StoredScriptSource) --------

    def _scripts_file(self):
        return self.data_path / "stored_scripts.json"

    def _load_scripts(self) -> dict:
        if self._scripts_file().exists():
            return json.loads(self._scripts_file().read_text())
        return {}

    def put_stored_script(self, script_id: str, body: dict) -> dict:
        script = (body or {}).get("script")
        if not isinstance(script, dict) or "source" not in script:
            raise IllegalArgumentException(
                "stored script requires [script] with [source]"
            )
        data = self._load_scripts()
        data[script_id] = {
            "lang": script.get("lang", "painless"),
            "source": script["source"],
            **({"options": script["options"]} if "options" in script else {}),
        }
        self.data_path.mkdir(parents=True, exist_ok=True)
        self._scripts_file().write_text(json.dumps(data))
        return {"acknowledged": True}

    def get_stored_script(self, script_id: str) -> dict:
        data = self._load_scripts()
        if script_id not in data:
            return {"_id": script_id, "found": False}
        return {"_id": script_id, "found": True, "script": data[script_id]}

    def delete_stored_script(self, script_id: str) -> dict:
        data = self._load_scripts()
        if script_id not in data:
            raise ResourceNotFoundException(
                f"stored script [{script_id}] does not exist"
            )
        del data[script_id]
        self._scripts_file().write_text(json.dumps(data))
        return {"acknowledged": True}

    def render_search_template(self, body: dict,
                               template_id: str | None = None) -> dict:
        """Template (inline source or stored id) + params -> search body."""
        from opensearch_tpu.script.mustache import render_search_template

        body = body or {}
        source = body.get("source")
        sid = template_id or body.get("id")
        if source is None and sid is not None:
            stored = self.get_stored_script(str(sid))
            if not stored.get("found"):
                raise ResourceNotFoundException(
                    f"search template [{sid}] does not exist"
                )
            source = stored["script"]["source"]
        if source is None:
            raise IllegalArgumentException(
                "search template requires [source] or [id]"
            )
        return render_search_template(source, body.get("params"))

    def search_template(self, index: str | None, body: dict,
                        template_id: str | None = None, **kwargs) -> dict:
        rendered = self.render_search_template(body, template_id)
        return self.search(index, rendered, **kwargs)

    def _load_templates(self) -> dict:
        if self._templates_file().exists():
            return json.loads(self._templates_file().read_text())
        return {"index_templates": {}, "component_templates": {}}

    def _save_templates(self, data: dict) -> None:
        self.data_path.mkdir(parents=True, exist_ok=True)
        self._templates_file().write_text(json.dumps(data))

    def put_index_template(self, name: str, body: dict) -> dict:
        body = body or {}
        patterns = body.get("index_patterns")
        if not isinstance(patterns, list) or not patterns:
            raise IllegalArgumentException(
                "index template requires [index_patterns]"
            )
        data = self._load_templates()
        for comp in body.get("composed_of") or []:
            if comp not in data["component_templates"]:
                raise IllegalArgumentException(
                    f"component template [{comp}] not found"
                )
        data["index_templates"][name] = body
        self._save_templates(data)
        return {"acknowledged": True}

    def get_index_template(self, name: str | None = None) -> dict:
        data = self._load_templates()
        if name is None:
            items = data["index_templates"]
        else:
            import fnmatch

            items = {
                n: t for n, t in data["index_templates"].items()
                if fnmatch.fnmatch(n, name)
            }
            if not items and "*" not in name:
                raise ResourceNotFoundException(
                    f"index template matching [{name}] not found"
                )
        return {"index_templates": [
            {"name": n, "index_template": t} for n, t in sorted(items.items())
        ]}

    def delete_index_template(self, name: str) -> dict:
        data = self._load_templates()
        if name not in data["index_templates"]:
            raise ResourceNotFoundException(
                f"index template matching [{name}] not found"
            )
        del data["index_templates"][name]
        self._save_templates(data)
        return {"acknowledged": True}

    def put_component_template(self, name: str, body: dict) -> dict:
        if not isinstance((body or {}).get("template"), dict):
            raise IllegalArgumentException(
                "component template requires [template]"
            )
        data = self._load_templates()
        data["component_templates"][name] = body
        self._save_templates(data)
        return {"acknowledged": True}

    def get_component_template(self, name: str | None = None) -> dict:
        data = self._load_templates()
        items = data["component_templates"]
        if name is not None:
            if name not in items:
                raise ResourceNotFoundException(
                    f"component template matching [{name}] not found"
                )
            items = {name: items[name]}
        return {"component_templates": [
            {"name": n, "component_template": t} for n, t in sorted(items.items())
        ]}

    def delete_component_template(self, name: str) -> dict:
        data = self._load_templates()
        if name not in data["component_templates"]:
            raise ResourceNotFoundException(
                f"component template matching [{name}] not found"
            )
        del data["component_templates"][name]
        self._save_templates(data)
        return {"acknowledged": True}

    # -- legacy (v1) templates: /_template (MetadataIndexTemplateService
    # legacy API; composable /_index_template templates shadow these) ------

    def put_legacy_template(self, name: str, body: dict,
                            create: bool = False) -> dict:
        body = body or {}
        patterns = body.get("index_patterns")
        if isinstance(patterns, str):
            patterns = [patterns]
        if not patterns:
            raise IllegalArgumentException(
                f"index_template [{name}] index patterns are missing"
            )
        data = self._load_templates()
        legacy = data.setdefault("legacy_templates", {})
        if create and name in legacy:
            raise IllegalArgumentException(
                f"index_template [{name}] already exists"
            )
        # settings persist FLAT with the index. prefix and string values
        # (IndexTemplateMetadata stores Settings; GET re-nests by default)
        flat_settings = {}
        for k, v in Settings.from_nested(
                body.get("settings") or {}).as_dict().items():
            if not k.startswith("index."):
                k = f"index.{k}"
            flat_settings[k] = str(v) if not isinstance(v, (dict, list)) \
                else v
        aliases = {}
        for aname, conf in (body.get("aliases") or {}).items():
            conf = dict(conf or {})
            routing = conf.pop("routing", None)
            if routing is not None:
                conf.setdefault("index_routing", str(routing))
                conf.setdefault("search_routing", str(routing))
            aliases[aname] = conf
        entry: dict[str, Any] = {
            "order": int(body.get("order", 0)),
            "index_patterns": list(patterns),
            "settings": flat_settings,
            "mappings": body.get("mappings") or {},
            "aliases": aliases,
        }
        if body.get("version") is not None:
            entry["version"] = int(body["version"])
        legacy[name] = entry
        self._save_templates(data)
        return {"acknowledged": True}

    def get_legacy_templates(self, name: str | None = None) -> dict:
        import fnmatch

        legacy = self._load_templates().get("legacy_templates", {})
        if name is None:
            return dict(sorted(legacy.items()))
        out = {}
        for pat in str(name).split(","):
            for n, t in legacy.items():
                if fnmatch.fnmatch(n, pat):
                    out[n] = t
        if not out and not any(c in str(name) for c in "*,?"):
            raise ResourceNotFoundException(
                f"index_template [{name}] missing"
            )
        return dict(sorted(out.items()))

    def delete_legacy_template(self, name: str) -> dict:
        import fnmatch

        data = self._load_templates()
        legacy = data.setdefault("legacy_templates", {})
        victims = [n for n in legacy if fnmatch.fnmatch(n, name)]
        if not victims and not any(c in name for c in "*?"):
            raise ResourceNotFoundException(
                f"index_template [{name}] missing"
            )
        for n in victims:
            del legacy[n]
        self._save_templates(data)
        return {"acknowledged": True}

    def _legacy_template_for_index(self, name: str) -> dict | None:
        """Merged {settings, mappings, aliases} of matching v1 templates,
        ascending order (higher order overrides)."""
        import fnmatch

        legacy = self._load_templates().get("legacy_templates", {})
        matching = sorted(
            (t for t in legacy.values()
             if any(fnmatch.fnmatch(name, p) for p in t["index_patterns"])),
            key=lambda t: int(t.get("order", 0)),
        )
        if not matching:
            return None
        merged: dict = {"settings": {}, "mappings": {}, "aliases": {}}
        for t in matching:
            merged["settings"] = _deep_merge(
                merged["settings"], t.get("settings") or {})
            merged["mappings"] = _deep_merge(
                merged["mappings"], t.get("mappings") or {})
            merged["aliases"].update(t.get("aliases") or {})
        return merged

    def _template_for_index(self, name: str) -> dict | None:
        """Composed {settings, mappings, aliases} of the highest-priority
        matching template (components first, template's own last).
        Composable templates shadow legacy /_template ones entirely."""
        import fnmatch

        data = self._load_templates()
        best = None
        best_prio = -1
        for tmpl in data["index_templates"].values():
            if any(fnmatch.fnmatch(name, p) for p in tmpl["index_patterns"]):
                prio = int(tmpl.get("priority", 0))
                if prio > best_prio:
                    best, best_prio = tmpl, prio
        if best is None:
            return self._legacy_template_for_index(name)
        merged: dict = {"settings": {}, "mappings": {}, "aliases": {}}
        layers = [
            data["component_templates"].get(c, {}).get("template", {})
            for c in best.get("composed_of") or []
        ]
        layers.append(best.get("template") or {})
        for layer in layers:
            merged["settings"] = _deep_merge(
                merged["settings"], layer.get("settings") or {}
            )
            merged["mappings"] = _deep_merge(
                merged["mappings"], layer.get("mappings") or {}
            )
            merged["aliases"].update(layer.get("aliases") or {})
        return merged

    # -- rollover / open / close (MetadataRolloverService,
    # TransportCloseIndexAction analogs) -----------------------------------

    def rollover(self, alias: str, body: dict | None = None) -> dict:
        body = body or {}
        old_index = self.resolve_write_target(alias)
        if old_index == alias:
            raise IllegalArgumentException(
                f"rollover target [{alias}] is not an alias"
            )
        new_index = body.get("new_index")
        if not new_index:
            m = re.match(r"^(.*?)-?(\d+)$", old_index)
            if not m:
                raise IllegalArgumentException(
                    f"index name [{old_index}] does not end with a number; "
                    "specify [new_index] explicitly"
                )
            new_index = f"{m.group(1)}-{int(m.group(2)) + 1:06d}"
        conditions = body.get("conditions") or {}
        svc = self._get_index(old_index)
        doc_count = sum(s.num_docs for s in svc.shards.values())
        age_ms = int(time.time() * 1000) - svc.creation_date
        met: dict[str, bool] = {}
        if "max_docs" in conditions:
            met[f"[max_docs: {conditions['max_docs']}]"] = (
                doc_count >= int(conditions["max_docs"])
            )
        if "max_age" in conditions:
            max_age_ms = parse_time_value_millis(
                conditions["max_age"], "max_age"
            )
            met[f"[max_age: {conditions['max_age']}]"] = age_ms >= max_age_ms
        rolled = (not conditions) or any(met.values())
        dry_run = bool(body.get("dry_run"))
        if rolled and not dry_run:
            create_body = {k: v for k, v in body.items()
                           if k in ("settings", "mappings", "aliases")}
            self.create_index(new_index, create_body)
            old_svc = self._get_index(old_index)
            alias_conf = dict(old_svc.aliases.get(alias) or {})
            if alias_conf.get("is_write_index"):
                # explicit write alias: stays on the old index for reads,
                # write flag moves (MetadataRolloverService semantics)
                old_svc.aliases[alias] = {**alias_conf, "is_write_index": False}
            else:
                del old_svc.aliases[alias]
            self._get_index(new_index).aliases[alias] = {
                **alias_conf, "is_write_index": True,
            }
            self._persist_index_registry()
        return {
            "acknowledged": rolled and not dry_run,
            "shards_acknowledged": rolled and not dry_run,
            "old_index": old_index,
            "new_index": new_index,
            "rolled_over": rolled and not dry_run,
            "dry_run": dry_run,
            "conditions": met,
        }

    def close_index(self, expr: str) -> dict:
        # open/close expand BOTH states (Open/CloseIndexRequest default
        # to strictExpandOpen*AndClosed* indices options)
        for name in self.resolve_indices(expr, expand_wildcards="all"):
            svc = self._get_index(name)
            # closing FLUSHES (the reference's close commits so the shard
            # recovers from its store on reopen)
            for shard in svc.shards.values():
                shard.flush()
            svc.closed = True
        self._persist_index_registry()
        return {"acknowledged": True, "shards_acknowledged": True}

    def open_index(self, expr: str) -> dict:
        for name in self.resolve_indices(expr, expand_wildcards="all"):
            self._get_index(name).closed = False
        self._persist_index_registry()
        return {"acknowledged": True, "shards_acknowledged": True}

    def _get_open_index(self, name: str) -> IndexService:
        svc = self._get_index(name)
        if svc.closed:
            raise IndexClosedException(name)
        return svc

    # -- analyze API (TransportAnalyzeAction analog) -----------------------

    @staticmethod
    def _analyze_stages(tokenizer_fn, filters, texts) -> list[list[dict]]:
        """Token stream after the tokenizer and after each filter, with
        character offsets (AnalyzeAction's detail pipeline). Filters apply
        per token so offsets/positions survive drops (stopwords leave
        position gaps, like posInc)."""
        from opensearch_tpu.index.analysis import _SPAN_TOKENIZERS

        stages: list[list[dict]] = [[] for _ in range(len(filters) + 1)]
        pos_base = 0
        char_base = 0
        for t in texts:
            t = str(t)
            span_fn = _SPAN_TOKENIZERS.get(tokenizer_fn)
            raw = (span_fn(t) if span_fn
                   else [(tok, 0, 0) for tok in tokenizer_fn(t)])
            text_final: list[dict] = []
            for pos, (tok, s, e) in enumerate(raw):
                def entry(term):
                    return {
                        "token": term,
                        "start_offset": char_base + s,
                        "end_offset": char_base + e,
                        "type": "<ALPHANUM>",
                        "position": pos_base + pos,
                    }
                stages[0].append(entry(tok))
                cur = [tok]
                for fi, f in enumerate(filters):
                    cur = f(cur)
                    if not cur:
                        break
                    target = (text_final if fi == len(filters) - 1
                              else stages[fi + 1])
                    target.append(entry(cur[0]))
            if not filters:
                text_final = []
            # reconcile the FINAL stage against full-stream application so
            # stream-stateful filters (unique) drop here too
            toks = [tok for tok, _s, _e in raw]
            for f in filters:
                toks = f(toks)
            j = 0
            for d in text_final:
                if j < len(toks) and toks[j] == d["token"]:
                    stages[-1].append(d)
                    j += 1
            pos_base += len(raw) + 100
            char_base += len(t) + 1
        return stages

    def analyze(self, index: str | None, body: dict) -> dict:
        from opensearch_tpu.index.analysis import (
            TOKENIZERS,
            build_token_filter,
        )

        body = body or {}
        text = body.get("text")
        if text is None:
            raise IllegalArgumentException("[_analyze] requires [text]")
        texts = text if isinstance(text, list) else [text]
        explain = bool(body.get("explain"))
        max_tokens = None
        registry = AnalysisRegistry.from_index_settings(None)
        if index is not None:
            svc = self._get_index(index)
            registry = svc.mapper_service.analysis
            max_tokens = int(svc.setting("analyze.max_token_count", 10_000))

        custom = (body.get("tokenizer") is not None
                  or body.get("filter") is not None)
        if custom:
            tok_name = body.get("tokenizer", "standard")
            tokenizer_fn = TOKENIZERS.get(str(tok_name))
            if tokenizer_fn is None:
                raise IllegalArgumentException(
                    f"unknown tokenizer [{tok_name}]")
            filters = []
            filter_names = []
            for f in body.get("filter") or []:
                if isinstance(f, dict):
                    ftype = f.get("type")
                    if ftype is None:
                        raise IllegalArgumentException(
                            "token filter entry must have a type")
                    filters.append(build_token_filter(str(ftype), f))
                    filter_names.append(f"__anonymous__{ftype}")
                else:
                    filters.append(build_token_filter(str(f)))
                    filter_names.append(str(f))
            analyzer_name = None
        else:
            field = body.get("field")
            if index is not None and field and not body.get("analyzer"):
                mapper = self._get_index(index).mapper_service.field_mapper(
                    field)
                analyzer_name = (
                    mapper.analyzer if mapper is not None
                    and mapper.type == "text" else "keyword"
                )
            else:
                analyzer_name = body.get("analyzer", "standard")
            analyzer = registry.get(str(analyzer_name))
            tokenizer_fn = analyzer.tokenizer
            filters = list(analyzer.filters)
            filter_names = []

        stages = self._analyze_stages(tokenizer_fn, filters, texts)
        final = stages[-1]
        if max_tokens is not None and len(final) > max_tokens:
            raise IllegalArgumentException(
                f"The number of tokens produced by calling _analyze has "
                f"exceeded the allowed maximum of [{max_tokens}]. This "
                f"limit can be set by changing the "
                f"[index.analyze.max_token_count] index level setting."
            )
        if not explain:
            return {"tokens": final}
        if custom:
            return {"detail": {
                "custom_analyzer": True,
                "tokenizer": {"name": str(body.get("tokenizer", "standard")),
                              "tokens": stages[0]},
                "tokenfilters": [
                    {"name": fname, "tokens": stages[i + 1]}
                    for i, fname in enumerate(filter_names)
                ],
            }}
        return {"detail": {
            "custom_analyzer": False,
            "analyzer": {"name": str(analyzer_name), "tokens": final},
        }}

    def put_mapping(self, index: str, body: dict) -> dict:
        # mapping updates reach closed indices too (PutMappingRequest
        # expands open and closed)
        for name in self.resolve_indices(index, expand_wildcards="all"):
            self._get_index(name).mapper_service.merge(body)
        self._persist_index_registry()
        return {"acknowledged": True}

    def get_mapping(self, index: str, *, ignore_unavailable: bool = False,
                    allow_no_indices: bool = True,
                    expand_wildcards: str = "open") -> dict:
        return {
            name: {"mappings": self._get_index(name).mapper_service.to_dict()}
            for name in self.resolve_indices(
                index, ignore_unavailable=ignore_unavailable,
                allow_no_indices=allow_no_indices,
                expand_wildcards=expand_wildcards,
            )
        }

    # canonical string rendering shared with the cluster facade
    _setting_str = staticmethod(setting_str)

    def get_settings(self, index: str, *, name: str | None = None,
                     flat: bool = False,
                     include_defaults: bool = False,
                     expand_wildcards: str = "all") -> dict:
        """GET [/{index}]/_settings[/{name}] (GetSettingsAction): values
        stringified, `name` filters by flat dotted key (wildcards OK),
        `flat_settings` keeps dotted keys, `include_defaults` adds the
        unset defaults section."""
        out = {}
        for idx_name in self.resolve_indices(
                index, expand_wildcards=expand_wildcards):
            svc = self._get_index(idx_name)
            out[idx_name] = index_settings_entry(
                svc.settings or {},
                num_shards=svc.num_shards, num_replicas=svc.num_replicas,
                name=name, flat=flat, include_defaults=include_defaults,
                extra={
                    "index.creation_date": str(svc.creation_date),
                    "index.uuid": svc.uuid,
                    "index.provided_name": idx_name,
                },
            )
        return out

    # -- document APIs -----------------------------------------------------

    @contextlib.contextmanager
    def _write_pressure(self, nbytes: int, operation: str):
        """Reentrant IndexingPressure guard: the outermost write entry point
        (bulk, single index/delete/update) accounts the bytes; nested calls
        (bulk item -> index_doc, update -> index_doc) are already covered.
        Reference: IndexingPressure.markCoordinatingOperationStarted — all
        write operations pass through admission control, not only _bulk."""
        if self._pressure_depth:
            yield
            return
        release = self.indexing_pressure.acquire(nbytes, operation)
        self._pressure_depth += 1
        try:
            yield
        finally:
            self._pressure_depth -= 1
            release.close()
            # request-level translog durability: ONE fsync per outer write
            # request covering every shard it touched (Translog.java:606 —
            # the reference fsyncs per request, not per op; a per-op sync is
            # fsync-bound). Runs even on partial
            # bulk failure: applied items must be durable before their acks
            dirty, self._dirty_translog_shards = (
                self._dirty_translog_shards, set()
            )
            for sh in dirty:
                sh.maybe_sync_translog()

    def index_doc(
        self,
        index: str,
        doc_id: str | None,
        source: dict,
        routing: str | None = None,
        if_seq_no: int | None = None,
        refresh: bool = False,
        op_type: str = "index",
        pipeline: str | None = None,
        version: int | None = None,
        version_type: str = "internal",
        if_primary_term: int | None = None,
    ) -> dict:
        # single-doc writes go through the same admission control as _bulk
        # (the reference accounts ALL write operations in IndexingPressure);
        # the guard is reentrant so bulk/update entry points account once
        with self._write_pressure(
            len(json.dumps(source)) if source is not None else 0, "index"
        ):
            return self._index_doc_inner(index, doc_id, source, routing,
                                         if_seq_no, refresh, op_type, pipeline,
                                         version, version_type,
                                         if_primary_term)

    def _index_doc_inner(self, index, doc_id, source, routing,
                         if_seq_no, refresh, op_type, pipeline,
                         version=None, version_type="internal",
                         if_primary_term=None) -> dict:
        if if_primary_term is not None and if_seq_no is None:
            from opensearch_tpu.common.errors import (
                ActionRequestValidationException,
            )

            raise ActionRequestValidationException(
                "Validation Failed: 1: ifSeqNo is unassigned, but "
                "primary_term is [%s];" % if_primary_term
            )
        if if_primary_term is not None and int(if_primary_term) != 1:
            # single-term engine: any other required term conflicts
            raise VersionConflictException(
                f"[{doc_id}]: version conflict, required primaryTerm "
                f"[{if_primary_term}], current primaryTerm [1]"
            )
        if version is not None and op_type == "create" and \
                version_type != "internal":
            from opensearch_tpu.common.errors import (
                ActionRequestValidationException,
            )

            raise ActionRequestValidationException(
                "Validation Failed: 1: create operations only support "
                "internal versioning. use index instead;"
            )
        _t_index0 = time.monotonic()
        index, routing = self._resolve_write_alias(index, routing)
        # ingest pipelines resolve BEFORE any index auto-creation (the
        # reference resolves pipelines first, so a drop or _index reroute
        # never leaves a stray empty index behind): request param >
        # index.default_pipeline, then the LANDING index's final_pipeline
        def _settings_of(name: str) -> dict:
            existing = self.indices.get(name)
            return existing.settings if existing is not None else {}

        resolved = pipeline
        if resolved is None:
            resolved = _index_setting(_settings_of(index), "default_pipeline")
        if resolved == "_none":
            resolved = None
        pipeline_chain = [resolved] if resolved else []
        ran_final = False
        while pipeline_chain or not ran_final:
            if pipeline_chain:
                pipe_id = pipeline_chain.pop(0)
            else:
                # final_pipeline of the index the doc actually lands in
                ran_final = True
                pipe_id = _index_setting(_settings_of(index), "final_pipeline")
                if not pipe_id or pipe_id == "_none":
                    break
            out = self.ingest.execute(pipe_id, index, doc_id, source, routing)
            if out is None:
                return {
                    "_index": index, "_id": doc_id, "_version": -3,
                    "result": "noop",
                    "_shards": {"total": 0, "successful": 0, "failed": 0},
                    "_seq_no": 0, "_primary_term": 0,
                }
            source = out.source
            index = out.meta["_index"]
            doc_id = out.meta["_id"]
            routing = out.meta["_routing"]
        svc = self._get_or_autocreate(index)
        if doc_id is None:
            import uuid

            doc_id = uuid.uuid4().hex[:20]
        doc_id = str(doc_id)
        if len(doc_id.encode()) > 512:
            raise IllegalArgumentException(
                f"id is too long, must be no longer than 512 bytes but "
                f"was: {len(doc_id.encode())}"
            )
        shard = svc.shard_for(doc_id, routing)
        # record where this write actually landed (post-pipeline index AND
        # post-pipeline routing) so _bulk's refresh=true touches the right
        # shard even after an ingest _index/_routing reroute (ADVICE r1);
        # safe: all doc mutations are serialized through the single writer
        self._last_write_shard = (index, shard.shard_id.shard)
        if op_type == "create" and shard.get(doc_id) is not None:
            # atomic here: all doc mutations are serialized through the
            # node's single writer (see rest/http.py executor)
            raise VersionConflictException(
                f"[{doc_id}]: version conflict, document already exists "
                "(current version [1])"
            )
        self._check_nested_limit(svc, source)
        mappers_before = len(svc.mapper_service.mappers)
        result = shard.apply_index_on_primary(
            doc_id, source, routing, if_seq_no=if_seq_no,
            version=version, version_type=version_type,
        )
        self._dirty_translog_shards.add(shard)
        if refresh:
            shard.refresh()
        if len(svc.mapper_service.mappers) != mappers_before:
            # dynamic mapping introduced new fields — persist the registry
            # (the cluster-state "mapping update" publication analog)
            self._persist_index_registry()
        self.indexing_slowlog.maybe_log(
            (time.monotonic() - _t_index0) * 1000, index, f"id[{doc_id}]"
        )
        return {
            "_index": index,
            "_id": doc_id,
            "_version": result.version,
            "result": result.result,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "_seq_no": result.seq_no,
            "_primary_term": 1,
        }

    def get_doc(self, index: str, doc_id: str, routing: str | None = None,
                realtime: bool = True, version: int | None = None,
                refresh: bool = False) -> dict:
        index, routing = self._resolve_write_alias(index, routing,
                                                   for_write=False)
        svc = self._get_open_index(index)
        shard = svc.shard_for(doc_id, routing)
        if refresh:
            # GET ?refresh=true forces a refresh before the read
            # (RealtimeRequest.refresh)
            shard.refresh()
        got = shard.get(doc_id, realtime=realtime)
        if got is None:
            return {"_index": index, "_id": doc_id, "found": False}
        if version is not None and got["_version"] != version:
            raise VersionConflictException(
                f"[{doc_id}]: version conflict, current version "
                f"[{got['_version']}] is different than the one provided "
                f"[{version}]"
            )
        out = {
            "_index": index,
            "_id": doc_id,
            "_version": got["_version"],
            "_seq_no": got["_seq_no"],
            "_primary_term": 1,
            "found": True,
            "_source": got["_source"],
        }
        if got.get("_routing") is not None:
            out["_routing"] = got["_routing"]
        return out

    def delete_doc(self, index: str, doc_id: str, routing: str | None = None,
                   refresh: bool = False,
                   if_seq_no: int | None = None,
                   version: int | None = None,
                   version_type: str = "internal") -> dict:
        # deletes carry no source; account a small fixed op cost
        with self._write_pressure(64, "delete"):
            return self._delete_doc_inner(index, doc_id, routing, refresh,
                                          if_seq_no, version, version_type)

    def _delete_doc_inner(self, index, doc_id, routing, refresh,
                          if_seq_no, version=None,
                          version_type="internal") -> dict:
        index, routing = self._resolve_write_alias(index, routing)
        svc = self._get_open_index(index)
        shard = svc.shard_for(doc_id, routing)
        self._last_write_shard = (index, shard.shard_id.shard)
        result = shard.apply_delete_on_primary(
            doc_id, if_seq_no=if_seq_no, version=version,
            version_type=version_type,
        )
        self._dirty_translog_shards.add(shard)
        if refresh:
            shard.refresh()
        return {
            "_index": index,
            "_id": doc_id,
            "_version": result.version,
            "result": result.result,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "_seq_no": result.seq_no,
            "_primary_term": 1,
        }

    def _note_noop(self, index: str, doc_id: str, routing) -> None:
        """indexing.noop_update_total (reference: InternalIndexingStats
        noticed via TransportUpdateAction noop results)."""
        svc = self.indices.get(index)
        if svc is not None:
            eng = svc.shard_for(doc_id, routing).engine
            eng.stats["noop_update_total"] = \
                eng.stats.get("noop_update_total", 0) + 1

    def update_doc(self, index: str, doc_id: str, body: dict,
                   routing: str | None = None, refresh: bool = False,
                   if_seq_no: int | None = None,
                   require_alias: bool = False) -> dict:
        """Partial update via doc merge or script
        (action/update/UpdateHelper.java: prepareUpdateScriptRequest)."""
        if require_alias and index not in self._alias_map():
            e = IndexNotFoundException(index)
            e.reason = (
                f"no such index [{index}] and [require_alias] request "
                f"flag is [true] and [{index}] is not an alias"
            )
            raise e
        with self._write_pressure(len(json.dumps(body)), "update"):
            out = self._update_doc_inner(index, doc_id, body, routing,
                                         refresh, if_seq_no)
        src_spec = (body or {}).get("_source")
        if src_spec and out.get("result") != "noop":
            got = self.get_doc(index, doc_id, routing=routing)
            if got.get("found"):
                from opensearch_tpu.search.service import _source_filter

                out["get"] = {
                    "found": True,
                    "_source": _source_filter(src_spec)(got["_source"]),
                    "_seq_no": got.get("_seq_no"),
                    "_primary_term": got.get("_primary_term", 1),
                }
        return out

    _UPDATE_KEYS = {"script", "doc", "upsert", "doc_as_upsert",
                    "detect_noop", "scripted_upsert", "_source", "fields",
                    "lang", "params"}

    def _update_doc_inner(self, index, doc_id, body, routing, refresh,
                          if_seq_no=None) -> dict:
        import difflib

        for key in body or {}:
            if key not in self._UPDATE_KEYS:
                near = difflib.get_close_matches(key, self._UPDATE_KEYS, 1)
                hint = f" did you mean [{near[0]}]?" if near else ""
                raise IllegalArgumentException(
                    f"[UpdateRequest] unknown field [{key}]{hint}"
                )
        index, routing = self._resolve_write_alias(index, routing)
        # updates auto-create the target index like index ops do
        # (TransportUpdateAction routes through the bulk auto-create path)
        svc = self._get_or_autocreate(index)
        shard = svc.shard_for(doc_id, routing)
        current = shard.get(doc_id)
        if if_seq_no is not None:
            if current is None and not (
                body.get("upsert") or body.get("doc_as_upsert")
            ):
                raise DocumentMissingException(
                    f"[{doc_id}]: document missing"
                )
            current_seq = current["_seq_no"] if current is not None else -1
            if current_seq != if_seq_no:
                raise VersionConflictException(
                    f"[{doc_id}]: version conflict, required seqNo "
                    f"[{if_seq_no}], current document has seqNo "
                    f"[{current_seq}]"
                )
        if "script" in body:
            from opensearch_tpu.script import default_script_service

            if current is None:
                if "upsert" in body:
                    if body.get("scripted_upsert"):
                        ctx = {"_source": dict(body["upsert"]), "op": "create",
                               "_index": index, "_id": doc_id}
                        ast, params = default_script_service.compile(body["script"])
                        default_script_service.execute_update(ast, params, ctx)
                        if ctx.get("op") in ("none", "noop"):
                            return {"_index": index, "_id": doc_id,
                                    "result": "noop", "_shards":
                                    {"total": 0, "successful": 0, "failed": 0}}
                        return self.index_doc(index, doc_id, ctx["_source"],
                                              routing, refresh=refresh)
                    return self.index_doc(index, doc_id, body["upsert"],
                                          routing, refresh=refresh)
                raise DocumentMissingException(f"[{doc_id}]: document missing")
            ctx = {"_source": dict(current["_source"]), "op": "index",
                   "_index": index, "_id": doc_id,
                   "_version": current["_version"], "_seq_no": current["_seq_no"]}
            ast, params = default_script_service.compile(body["script"])
            default_script_service.execute_update(ast, params, ctx)
            op = ctx.get("op", "index")
            if op in ("none", "noop"):
                self._note_noop(index, doc_id, routing)
                return {"_index": index, "_id": doc_id, "result": "noop",
                        "_version": current["_version"],
                        "_seq_no": current["_seq_no"], "_primary_term": 1,
                        "_shards": {"total": 0, "successful": 0, "failed": 0}}
            if op == "delete":
                return self.delete_doc(index, doc_id, routing, refresh=refresh)
            out = self.index_doc(index, doc_id, ctx["_source"], routing,
                                 refresh=refresh)
            out["result"] = "updated"
            return out
        if "doc" in body:
            if current is None:
                if body.get("doc_as_upsert"):
                    return self.index_doc(index, doc_id, body["doc"], routing, refresh=refresh)
                if "upsert" in body:
                    return self.index_doc(index, doc_id, body["upsert"],
                                          routing, refresh=refresh)
                raise DocumentMissingException(f"[{doc_id}]: document missing")
            merged = _deep_merge(current["_source"], body["doc"])
            if merged == current["_source"] and not body.get("detect_noop") is False:
                self._note_noop(index, doc_id, routing)
                return {"_index": index, "_id": doc_id, "result": "noop",
                        "_version": current["_version"],
                        "_seq_no": current["_seq_no"], "_primary_term": 1,
                        "_shards": {"total": 0, "successful": 0, "failed": 0}}
            out = self.index_doc(index, doc_id, merged, routing, refresh=refresh)
            out["result"] = "updated"
            return out
        if "upsert" in body and current is None:
            return self.index_doc(index, doc_id, body["upsert"], routing, refresh=refresh)
        raise IllegalArgumentException("update requires [doc] or [upsert]")

    def bulk(self, operations: list[tuple[str, dict, dict | None]],
             refresh: bool = False, pipeline: str | None = None,
             payload_bytes: int | None = None,
             query_group: str | None = None) -> dict:
        """operations: [(action, metadata, source)]; action in
        index|create|update|delete. `payload_bytes` lets the transport
        layer pass the already-known request size so the pressure estimate
        doesn't re-serialize every document. `query_group` tags the request
        for wlm bulk admission (429 shed past the group's slot share)."""
        t0 = time.monotonic()
        if payload_bytes is not None:
            payload_bytes = int(payload_bytes)
        if payload_bytes is None:
            payload_bytes = sum(
                len(json.dumps(source)) for _, _, source in operations
                if source is not None
            )
        release_admission = self.query_groups.admit_bulk(query_group)
        try:
            return self._bulk_admitted(
                operations, refresh, pipeline, payload_bytes, t0)
        finally:
            release_admission()

    def _bulk_admitted(self, operations, refresh, pipeline,
                       payload_bytes, t0) -> dict:
        with self._write_pressure(payload_bytes, "bulk"):
            with self.task_manager.task_scope(
                "indices:data/write/bulk",
                description=f"requests[{len(operations)}]",
                cancellable=False,
            ):
                return self._bulk_inner(operations, refresh, pipeline, t0)

    def _bulk_inner(self, operations, refresh, pipeline, t0) -> dict:
        items = []
        errors = False
        touched: set[tuple[str, int]] = set()
        for action, meta, source in operations:
            index = meta.get("_index")
            doc_id = meta.get("_id")
            if doc_id is not None and not isinstance(doc_id, str):
                doc_id = str(doc_id)
            routing = meta.get("routing") or meta.get("_routing")
            if routing is not None:
                routing = str(routing)
            try:
                if doc_id == "":
                    raise IllegalArgumentException(
                        "if _id is specified it must not be empty"
                    )
                if meta.get("require_alias") in (True, "true") and \
                        index not in self._alias_map():
                    from opensearch_tpu.common.errors import (
                        IndexNotFoundException,
                    )

                    e = IndexNotFoundException(index)
                    e.reason = (
                        f"no such index [{index}] and [require_alias] "
                        f"request flag is [true] and [{index}] is not an "
                        f"alias"
                    )
                    raise e
                if action == "index" and meta.get("op_type") == "create":
                    action = "create"
                if action in ("index", "create"):
                    m_seq = meta.get("if_seq_no")
                    m_pt = meta.get("if_primary_term")
                    resp = self.index_doc(
                        index, doc_id, source, routing,
                        op_type=action,
                        if_seq_no=int(m_seq) if m_seq is not None else None,
                        if_primary_term=(int(m_pt) if m_pt is not None
                                         else None),
                        pipeline=meta.get("pipeline", pipeline))
                    status = 201 if resp["result"] == "created" else 200
                elif action == "update":
                    if meta.get("_source") is not None and \
                            isinstance(source, dict) \
                            and "_source" not in source:
                        source = {**source, "_source": meta["_source"]}
                    m_seq = meta.get("if_seq_no")
                    if m_seq is not None and \
                            self.indices.get(index) is not None:
                        svc_u = self.indices[index]
                        cur_u = svc_u.shard_for(str(doc_id), routing).get(
                            str(doc_id))
                        if cur_u is None:
                            # bulk CAS on a missing doc conflicts (the
                            # item-level contract differs from the single
                            # update API's 404)
                            raise VersionConflictException(
                                f"[{doc_id}]: version conflict, required "
                                f"seqNo [{m_seq}], but no document was found"
                            )
                    resp = self.update_doc(
                        index, doc_id, source, routing,
                        if_seq_no=int(m_seq) if m_seq is not None else None,
                    )
                    status = 200
                elif action == "delete":
                    resp = self.delete_doc(index, doc_id, routing)
                    status = 200 if resp["result"] == "deleted" else 404
                else:
                    raise IllegalArgumentException(f"unknown bulk action [{action}]")
                # the inner write path records (landed index, shard) AFTER
                # ingest-pipeline rerouting, so refresh=true touches the
                # shard the doc actually landed on (ADVICE r1: resolving the
                # original target's alias routing against the landed index's
                # shard count picked the wrong shard after an _index reroute)
                if resp.get("result") != "noop" and self._last_write_shard:
                    touched.add(self._last_write_shard)
                items.append({action: {**resp, "status": status}})
            except OpenSearchTpuException as e:
                errors = True
                items.append({
                    action: {
                        "_index": index, "_id": doc_id, "status": e.status,
                        "error": e.to_dict(),
                    }
                })
        if refresh:
            for index, sid in touched:
                self.indices[index].shards[sid].refresh()
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "errors": errors,
            "items": items,
        }

    # -- mget / explain / field_caps / termvectors -------------------------

    def mget(self, index: str | None, body: dict,
             realtime: bool = True, refresh: bool = False,
             stored_fields: list | None = None) -> dict:
        """TransportMultiGetAction analog: batched realtime gets."""
        from opensearch_tpu.common.errors import (
            ActionRequestValidationException,
        )

        body = body or {}
        if "docs" in body:
            specs = body["docs"]
            if not isinstance(specs, list):
                raise IllegalArgumentException("[docs] must be an array")
        elif "ids" in body:
            if index is None:
                raise ActionRequestValidationException(
                    "Validation Failed: 1: index is missing;"
                )
            if not isinstance(body["ids"], list):
                raise IllegalArgumentException("[ids] must be an array")
            specs = [{"_id": i} for i in body["ids"]]
        else:
            raise ActionRequestValidationException(
                "Validation Failed: 1: no documents to get;"
            )
        if not specs:
            raise ActionRequestValidationException(
                "Validation Failed: 1: no documents to get;"
            )
        problems = []
        for i, spec in enumerate(specs):
            if not isinstance(spec, dict):
                continue
            if spec.get("_index", index) is None:
                problems.append(f"{len(problems) + 1}: index is missing")
            if spec.get("_id") is None:
                problems.append(f"{len(problems) + 1}: id is missing")
        if problems:
            raise ActionRequestValidationException(
                "Validation Failed: " + "; ".join(problems) + ";"
            )
        docs = []
        for spec in specs:
            target = spec.get("_index", index)
            doc_id = spec.get("_id")
            try:
                got = self.get_doc(target, str(doc_id),
                                   routing=spec.get("routing"),
                                   realtime=realtime, refresh=refresh)
            except OpenSearchTpuException as e:
                # per-doc failures (missing index, closed, bad alias) are
                # reported in the doc's error slot, not as a request
                # failure; the slot carries the full error envelope shape
                docs.append({"_index": target, "_id": str(doc_id),
                             "error": {"root_cause": [e.to_dict()],
                                       **e.to_dict()}})
                continue
            if "_source" in spec and got.get("found"):
                from opensearch_tpu.search.service import _source_filter

                filtered = _source_filter(spec["_source"])(got["_source"])
                if filtered is None:
                    got.pop("_source", None)
                else:
                    got["_source"] = filtered
            sf = spec.get("stored_fields", stored_fields)
            if sf and got.get("found"):
                if isinstance(sf, str):
                    sf = sf.split(",")
                src = got.get("_source") or {}
                fields = {}
                for f in sf:
                    if f in src:
                        v = src[f]
                        fields[f] = v if isinstance(v, list) else [v]
                if fields:
                    got = {**got, "fields": fields}
                if "_source" not in sf:
                    got.pop("_source", None)
            docs.append(got)
        return {"docs": docs}

    def explain(self, index: str, doc_id: str, body: dict,
                routing: str | None = None) -> dict:
        """TransportExplainAction analog: why does (or doesn't) this doc
        match — runs the query on the owning shard restricted to the doc."""
        body = body or {}
        if body and "query" not in body:
            raise IllegalArgumentException(
                "request body must contain a [query] element")
        concrete, routing = self._resolve_write_alias(index, routing, check_blocks=False)
        svc = self._get_open_index(concrete)
        shard = svc.shard_for(doc_id, routing)
        got = shard.get(doc_id)
        if got is None:
            raise DocumentMissingException(f"[{concrete}]: document missing [{doc_id}]")
        from opensearch_tpu.search import query_dsl
        from opensearch_tpu.search.executor import execute_query_phase
        from opensearch_tpu.search.fetch import explain_for_hit

        node_q = query_dsl.parse_query(body.get("query"))
        restricted = query_dsl.BoolQuery(
            must=[node_q], filter=[query_dsl.IdsQuery(values=[doc_id])]
        )
        snapshot = shard.acquire_searcher()
        result = execute_query_phase(
            snapshot, svc.mapper_service, restricted, size=1
        )
        matched = bool(result.hits)
        out = {
            "_index": concrete,
            "_id": doc_id,
            "matched": matched,
        }
        if matched:
            h = result.hits[0]
            out["explanation"] = explain_for_hit(h.score, node_q)
        else:
            out["explanation"] = {
                "value": 0.0, "description": "no matching term",
                "details": [],
            }
        # GetResult rider (ExplainResponse.getGetResult): the fetched doc
        # with _source, so ?_source filtering applies to explain too
        out["get"] = {"found": True, "_source": got.get("_source")}
        return out

    def field_caps(self, index: str | None, fields: str,
                   include_unmapped: bool = False,
                   index_filter: dict | None = None) -> dict:
        """TransportFieldCapabilitiesAction analog. `index_filter` drops
        indices where the filter query matches no documents; the merged
        response carries the reference's per-type provenance keys
        (`indices`, `non_searchable_indices`, `non_aggregatable_indices`)
        and cross-index `meta` merging."""
        names = self.resolve_indices(index if index is not None else "_all")
        patterns = [p.strip() for p in fields.split(",") if p.strip()]
        if not patterns:
            raise IllegalArgumentException("[field_caps] requires [fields]")
        if index_filter:
            names = [
                name for name in names
                if self.count(name, {"query": index_filter}).get("count", 0)
            ]
        return build_field_caps(
            names,
            lambda n: self._get_index(n).mapper_service,
            patterns, include_unmapped=include_unmapped,
        )

    def termvectors(self, index: str, doc_id: str, body: dict | None = None,
                    fields: str | None = None, realtime: bool = True,
                    routing: str | None = None) -> dict:
        """TransportTermVectorsAction analog: re-analyzes the doc (the
        realtime path the reference takes when vectors aren't stored).
        realtime=False reads through the last refresh only; field and term
        statistics come from the resident postings
        (TermVectorsService.java semantics)."""
        body = body or {}
        concrete, routing = self._resolve_write_alias(index, routing, check_blocks=False)
        svc = self._get_open_index(concrete)
        shard = svc.shard_for(doc_id, routing)
        got = shard.get(doc_id, realtime=realtime)
        if got is None:
            return {"_index": concrete, "_id": doc_id, "found": False}
        want = fields.split(",") if fields else body.get("fields")
        if isinstance(want, str):
            want = [want]
        want_stats = bool(body.get("term_statistics"))
        want_field_stats = body.get("field_statistics", True) is not False
        want_offsets = body.get("offsets", True) is not False
        want_positions = body.get("positions", True) is not False
        source = got["_source"]
        ms = svc.mapper_service
        tv: dict[str, Any] = {}
        flat = _flatten_source_fields(source)
        snapshot = shard.acquire_searcher()
        for fname, value in flat.items():
            mapper = ms.field_mapper(fname)
            if mapper is None or mapper.type != "text":
                continue
            if want and not any(fnmatch_one(fname, w) for w in want):
                continue
            analyzer = ms.analysis.get(mapper.analyzer)
            texts = value if isinstance(value, list) else [value]
            # per-term occurrence list with character offsets; multi-value
            # entries continue the offset/position space with the standard
            # gaps (+1 char, +100 positions — Lucene's offset/posInc gaps)
            occurrences: dict[str, list[dict]] = {}
            char_base = 0
            pos_base = 0
            for t in texts:
                t = str(t)
                max_pos = -1
                for term, s, e, pos in analyzer.analyze_with_offsets(t):
                    tok: dict[str, Any] = {}
                    if want_positions:
                        tok["position"] = pos_base + pos
                    if want_offsets:
                        tok["start_offset"] = char_base + s
                        tok["end_offset"] = char_base + e
                    occurrences.setdefault(term, []).append(tok)
                    max_pos = max(max_pos, pos)
                char_base += len(t) + 1
                pos_base += max_pos + 1 + 100
            seg_fields = [
                host.text_fields[fname]
                for host, _dev in snapshot.segments
                if fname in host.text_fields
            ]
            terms_out = {}
            for term, tokens in sorted(occurrences.items()):
                entry: dict[str, Any] = {"term_freq": len(tokens)}
                if want_stats:
                    entry["doc_freq"] = sum(
                        f.doc_freq(term) for f in seg_fields)
                    entry["ttf"] = sum(
                        f.total_term_freq(term) for f in seg_fields)
                if tokens and tokens[0]:
                    entry["tokens"] = tokens
                terms_out[term] = entry
            tv[fname] = {"terms": terms_out}
            if want_field_stats:
                tv[fname]["field_statistics"] = {
                    "sum_doc_freq": sum(f.sum_doc_freq for f in seg_fields),
                    "doc_count": sum(f.docs_with_field for f in seg_fields),
                    "sum_ttf": sum(int(f.total_terms) for f in seg_fields),
                }
        return {
            "_index": concrete, "_id": doc_id, "found": True,
            "_version": got.get("_version", 1),
            "took": 0, "term_vectors": tv,
        }

    def mtermvectors(self, body: dict | None = None,
                     index: str | None = None,
                     ids: str | None = None,
                     term_statistics: bool = False,
                     realtime: bool = True) -> dict:
        """_mtermvectors (TransportMultiTermVectorsAction): docs list with
        per-doc _index/_id (+ inherited defaults), or index + ids."""
        body = body or {}
        specs: list[dict] = []
        if body.get("docs") is not None:
            if not isinstance(body["docs"], list):
                raise IllegalArgumentException("[docs] must be an array")
            for d in body["docs"]:
                if not isinstance(d, dict):
                    raise IllegalArgumentException(
                        "[docs] entries must be objects")
                unknown = set(d) - {"_index", "_id", "_routing", "fields",
                                    "term_statistics", "field_statistics",
                                    "offsets", "positions", "payloads",
                                    "version", "version_type"}
                if unknown:
                    # camelCase / underscore legacy spellings reject like
                    # the reference's strict parser
                    raise IllegalArgumentException(
                        f"unknown parameter {sorted(unknown)} "
                        f"in multi term vectors doc")
                specs.append(d)
        elif ids is not None or body.get("ids") is not None:
            raw = ids if ids is not None else body["ids"]
            id_list = raw.split(",") if isinstance(raw, str) else list(raw)
            specs.extend({"_id": i} for i in id_list)
        docs = []
        for spec in specs:
            idx = spec.get("_index", index)
            did = spec.get("_id")
            if idx is None or did is None:
                raise IllegalArgumentException(
                    "multi term vectors docs require [_index] and [_id]")
            sub_body = {
                "term_statistics": spec.get("term_statistics",
                                            term_statistics),
                "field_statistics": spec.get("field_statistics", True),
                "offsets": spec.get("offsets", True),
                "positions": spec.get("positions", True),
            }
            if spec.get("fields"):
                sub_body["fields"] = spec["fields"]
            docs.append(self.termvectors(
                idx, str(did), sub_body, realtime=realtime,
                routing=spec.get("_routing"),
            ))
        return {"docs": docs}

    # -- search / refresh --------------------------------------------------

    def refresh(self, index: str = "_all") -> dict:
        count = 0
        for name in self.resolve_indices(index):
            for shard in self._get_index(name).shards.values():
                shard.refresh()
                count += 1
        return {"_shards": {"total": count, "successful": count, "failed": 0}}

    def flush(self, index: str = "_all") -> dict:
        count = 0
        for name in self.resolve_indices(index):
            for shard in self._get_index(name).shards.values():
                shard.flush()
                count += 1
        return {"_shards": {"total": count, "successful": count, "failed": 0}}

    def force_merge(self, index: str = "_all",
                    max_num_segments: int = 1,
                    only_expunge_deletes: bool = False,
                    flush: bool = True) -> dict:
        """POST /{index}/_forcemerge (TransportForceMergeAction →
        InternalEngine merges via OpenSearchConcurrentMergeScheduler,
        InternalEngine.java:152)."""
        count = 0
        for name in self.resolve_indices(index):
            for shard in self._get_open_index(name).shards.values():
                shard.engine.force_merge(
                    max_num_segments=max_num_segments,
                    only_expunge_deletes=only_expunge_deletes,
                )
                if flush:
                    shard.flush()
                count += 1
        return {"_shards": {"total": count, "successful": count, "failed": 0}}

    def search(self, index: str | None = None, body: dict | None = None,
               scroll: str | None = None,
               search_pipeline: str | None = None,
               ignore_unavailable: bool = False,
               query_group: str | None = None,
               request_cache: bool | None = None,
               precomputed_results: list | None = None) -> dict:
        body = dict(body or {})
        # per-request stat groups ("stats": [..]) feed indices.stats
        # search.groups counters (reference: SearchRequest.stats ->
        # ShardSearchStats.groupStats)
        stat_groups = body.get("stats")
        if stat_groups is not None and not isinstance(stat_groups, list):
            raise ParsingException("[stats] must be an array of group names")
        try:
            for cname in self.resolve_indices(
                    index if index is not None else "_all",
                    ignore_unavailable=True):
                svc_g = self.indices.get(cname)
                if svc_g is None:
                    continue
                totals = getattr(svc_g, "_search_stats", None)
                if totals is None:
                    totals = svc_g._search_stats = {
                        "query_total": 0, "fetch_total": 0}
                totals["query_total"] += 1
                totals["fetch_total"] += 1
                if not stat_groups:
                    continue
                reg = getattr(svc_g, "_search_group_stats", None)
                if reg is None:
                    reg = svc_g._search_group_stats = {}
                for g in stat_groups:
                    e = reg.setdefault(str(g), {
                        "query_total": 0, "query_time_in_millis": 0,
                        "query_current": 0, "fetch_total": 0,
                        "fetch_time_in_millis": 0, "fetch_current": 0})
                    e["query_total"] += 1
                    e["fetch_total"] += 1
        except Exception as e:  # noqa: BLE001
            # stats accounting must never fail a search
            logger.debug("search group-stats accounting failed: %s", e)
        # body key is always consumed; an explicit param takes precedence
        body_pipeline = body.pop("search_pipeline", None)
        pipeline_id = search_pipeline or body_pipeline
        pit = body.pop("pit", None)
        if pit is not None:
            if scroll is not None:
                raise IllegalArgumentException(
                    "[scroll] cannot be used with a point-in-time"
                )
            if index is not None:
                raise IllegalArgumentException(
                    "[pit] cannot be used with an index in the request path"
                )
            ctx = self._resolve_reader_context(str(pit.get("id", "")), "pit")
            if pit.get("keep_alive"):
                ctx["expires_at"] = _now_ms() + parse_time_value_millis(
                    pit["keep_alive"], "keep_alive", positive=True
                )
            pit_names = sorted({s.shard_id.index for s in ctx["shards"]})
            self.search_backpressure.admit()
            with self.task_manager.task_scope(
                "indices:data/read/search", description=f"pit[{ctx['id']}]"
            ) as task:
                resp = self._search_with_pipeline(
                    pipeline_id, pit_names, ctx["shards"], body,
                    acquired=ctx["snapshots"],
                    shard_filters=ctx.get("shard_filters"),
                    task=task,
                )
            resp["pit_id"] = ctx["id"]
            return resp
        expr = index if index is not None else "_all"
        # cross-cluster expressions ("alias:pattern") fan out to remote
        # clusters and merge coordinator-side (TransportSearchAction +
        # SearchResponseMerger)
        from opensearch_tpu.cluster.remote import (
            RemoteClusterService,
            merge_cross_cluster,
            split_index_expression,
        )

        rcs = RemoteClusterService(self)
        remote_groups, local_parts = split_index_expression(expr)
        registered = rcs.registered()
        known_groups = {a: ps for a, ps in remote_groups.items()
                        if a in registered}
        if remote_groups and not ignore_unavailable:
            unknown_remotes = set(remote_groups) - set(registered)
            # a ":"-bearing part could also be a plain (odd) index name;
            # only treat it as a remote expression when ANY alias resolves
            # or the prefix is clearly not a local index
            if unknown_remotes and known_groups:
                raise IllegalArgumentException(
                    f"no such remote cluster: "
                    f"[{sorted(unknown_remotes)[0]}]"
                )
        remote_groups = known_groups
        if remote_groups and scroll is None:
            remote_resps = {
                alias: rcs.search_remote(alias, ",".join(patterns), body)
                for alias, patterns in remote_groups.items()
            }
            local_resp = None
            if local_parts:
                local_resp = self.search(
                    ",".join(local_parts), body,
                    search_pipeline=search_pipeline,
                    ignore_unavailable=ignore_unavailable,
                )
            return merge_cross_cluster(local_resp, remote_resps, body)
        sort_spec = body.get("sort")
        sort_list = [sort_spec] if isinstance(sort_spec, (str, dict)) else (sort_spec or [])
        for s_ in sort_list:
            fname = s_ if isinstance(s_, str) else next(iter(s_), None)
            if fname == "_shard_doc":
                from opensearch_tpu.common.errors import (
                    ActionRequestValidationException,
                )

                raise ActionRequestValidationException(
                    "Validation Failed: 1: [_shard_doc] sort field is only "
                    "supported with point-in-time (PIT) searches;"
                )
        shards, shard_filters, names = self.resolve_search_shards(
            expr, ignore_unavailable=ignore_unavailable)
        self._validate_search_request(names, body, scroll=scroll is not None)
        if body.get("indices_boost") is not None:
            body = dict(body)
            body["indices_boost"] = self._resolve_indices_boost(
                body["indices_boost"], ignore_unavailable=ignore_unavailable
            )
        if scroll is not None:
            if int(body.get("from", 0)) > 0:
                raise IllegalArgumentException("[from] is not supported with scroll")
            if body.get("search_after") is not None:
                raise IllegalArgumentException(
                    "[search_after] is not supported with scroll"
                )
            if int(body.get("size", search_service.DEFAULT_SIZE)) == 0:
                raise IllegalArgumentException(
                    "[size] cannot be [0] in a scroll context"
                )
            return self._start_scroll(shards, body, scroll,
                                      pipeline_id=pipeline_id, names=names,
                                      shard_filters=shard_filters)
        # per-hit _index comes from each shard's ShardId inside the service
        from opensearch_tpu.index.request_cache import RequestCache as _RC

        cache_on = request_cache
        if cache_on is None:
            for n in names:
                svc = self.indices.get(n)
                if svc is not None and str(
                    svc.setting("requests.cache.enable", True)
                ).lower() == "false":
                    cache_on = False
                    break
        cache_key = None
        cache_snaps = None
        if _RC.cacheable(body, cache_on) and precomputed_results is None:
            # acquire the snapshots FIRST and key by THEIR generations:
            # searches run on the parallel pool, so reading the engine's
            # generation counter separately from the snapshot acquire could
            # cache a pre-refresh response under the post-refresh key (a
            # refresh bumps the counter before publishing the new searcher)
            cache_snaps = [s.acquire_searcher() for s in shards]
            gens = [snap.generation for snap in cache_snaps]
            shard_keys = [
                (s.shard_id.index, s.shard_id.shard, s.engine.engine_uuid)
                for s in shards
            ]
            cache_key = _RC.key(tuple(sorted(names)), shard_keys, gens, body)
            cached = self.request_cache.get(cache_key)
            if cached is not None:
                return json.loads(cached)
        self.search_backpressure.admit()
        with self.query_groups.admit(query_group), self.task_manager.task_scope(
            "indices:data/read/search", description=f"indices[{expr}]"
        ) as task:
            resp = self._search_with_pipeline(pipeline_id, names, shards, body,
                                              acquired=cache_snaps,
                                              shard_filters=shard_filters,
                                              task=task,
                                              precomputed_results=precomputed_results)
        if cache_key is not None:
            self.request_cache.put(cache_key, json.dumps(resp, default=str))
        return resp

    @staticmethod
    def _find_expensive_query(qbody) -> str | None:
        """First expensive clause in the raw query JSON (the set
        ALLOW_EXPENSIVE_QUERIES gates in the reference)."""
        expensive = {"script", "script_score", "fuzzy", "regexp", "prefix",
                     "wildcard", "percolate", "join", "distance_feature",
                     "nested", "has_child", "has_parent", "parent_id"}
        # multi_match/query_string/intervals are NOT categorically expensive
        # in the reference — only the expensive clause kinds they may expand
        # to (fuzzy/prefix/wildcard/regexp) are gated
        multi_term_markers = {"fuzzy", "prefix", "wildcard", "regexp"}

        def contains_marker(obj) -> str | None:
            if isinstance(obj, dict):
                for k, v in obj.items():
                    if k in multi_term_markers:
                        return k
                    found = contains_marker(v)
                    if found:
                        return found
            elif isinstance(obj, list):
                for v in obj:
                    found = contains_marker(v)
                    if found:
                        return found
            return None

        def walk(obj, ms=None):
            if isinstance(obj, dict):
                for k, v in obj.items():
                    if k == "range" and isinstance(v, dict):
                        return ("range", next(iter(v), None))
                    if k in expensive:
                        field = (next(iter(v), None)
                                 if isinstance(v, dict) else None)
                        return (k, field)
                    if k == "intervals" and isinstance(v, dict):
                        marker = contains_marker(v)
                        if marker:
                            return (marker, next(iter(v), None))
                        continue
                    if k == "multi_match" and isinstance(v, dict):
                        if v.get("fuzziness") is not None:
                            return ("fuzzy", None)
                        # phrase_prefix AND bool_prefix expand to prefix
                        # queries on the last term
                        if str(v.get("type", "")) in ("phrase_prefix",
                                                      "bool_prefix"):
                            return ("prefix", None)
                        continue
                    if k == "query_string" and isinstance(v, dict):
                        qs = str(v.get("query", ""))
                        # escaped chars are literal; quoted phrases (incl.
                        # "…"~N proximity) compile to PhraseQuery, not a
                        # gated multi-term query — strip both before
                        # looking for wildcard/fuzzy/regex syntax. The
                        # fuzziness PARAM alone gates nothing: it is only
                        # a default for terms that use the ~ operator.
                        stripped = re.sub(r"\\.", "", qs)
                        stripped = re.sub(r'"[^"]*"(~\d+)?', "", stripped)
                        if any(c in stripped for c in "*?~") or re.search(
                            r"/[^/]*/", stripped
                        ):
                            return ("query_string", None)
                        continue
                    found = walk(v)
                    if found:
                        return found
            elif isinstance(obj, list):
                for v in obj:
                    found = walk(v)
                    if found:
                        return found
            return None

        return walk(qbody)

    def _resolve_indices_boost(self, spec,
                               ignore_unavailable: bool = False) -> dict:
        """indices_boost: {index: boost} or [{index-or-pattern: boost}, ...]
        resolved to concrete index names; unknown names 404 like the
        reference (SearchService.resolveIndexBoosts)."""
        entries: list[tuple[str, float]] = []
        if isinstance(spec, dict):
            entries = [(k, float(v)) for k, v in spec.items()]
        elif isinstance(spec, list):
            for item in spec:
                if not isinstance(item, dict) or len(item) != 1:
                    raise IllegalArgumentException(
                        "[indices_boost] must contain one entry per object"
                    )
                k, v = next(iter(item.items()))
                entries.append((k, float(v)))
        else:
            raise IllegalArgumentException(
                "[indices_boost] must be an object or an array"
            )
        out: dict[str, float] = {}
        for name, boost in entries:
            for concrete in self.resolve_indices(
                name, ignore_unavailable=ignore_unavailable
            ):
                out.setdefault(concrete, boost)  # first match wins
        return out

    @staticmethod
    def _check_nested_limit(svc, source: dict) -> None:
        """index.mapping.nested_objects.limit: cap the number of nested
        documents one doc may expand to (MapperService.checkNestedDocsLimit
        analog; this engine flattens nested docs but keeps the cap)."""
        paths = getattr(svc.mapper_service, "nested_paths", None)
        if not paths:
            return
        limit = int(svc.setting("mapping.nested_objects.limit", 10000))

        def count(obj, prefix=""):
            total = 0
            if isinstance(obj, dict):
                for k, v in obj.items():
                    full = f"{prefix}{k}"
                    if isinstance(v, list) and full in paths:
                        total += sum(1 for x in v if isinstance(x, dict))
                        for x in v:
                            total += count(x, f"{full}.")
                    elif isinstance(v, dict):
                        total += count(v, f"{full}.")
            return total

        n = count(source)
        if n > limit:
            raise IllegalArgumentException(
                f"The number of nested documents has exceeded the allowed "
                f"limit of [{limit}]. This limit can be set by changing "
                f"the [index.mapping.nested_objects.limit] index level "
                f"setting."
            )

    def _check_keep_alive(self, keep_ms: int, raw: str) -> None:
        """search.max_keep_alive cap (SearchService.validateKeepAlives)."""
        max_raw = self.effective_cluster_setting("search.max_keep_alive", "24h")
        max_ms = parse_time_value_millis(str(max_raw), "search.max_keep_alive",
                                         positive=True)
        if keep_ms > max_ms:
            raise IllegalArgumentException(
                f"Keep alive for request ({raw}) is too large. It must be "
                f"less than ({max_raw}). This limit can be set by changing "
                f"the [search.max_keep_alive] cluster level setting."
            )

    def effective_cluster_setting(self, key: str, default=None):
        """transient over persistent over default (ClusterSettings.get)."""
        t = getattr(self, "_transient_cluster_settings", {}) or {}
        p = getattr(self, "_cluster_settings", {}) or {}
        return t.get(key, p.get(key, default))

    def _index_int_setting(self, name: str, key: str, default: int) -> int:
        svc = self.indices.get(name)
        if svc is None:
            return default
        try:
            return int(svc.setting(key, default))
        except (TypeError, ValueError):
            return default

    def _validate_search_request(self, names: list, body: dict,
                                 scroll: bool = False) -> None:
        """Request-level limits the reference enforces in
        SearchService.validateSearchContext / SearchRequest.validate:
        result windows, rescore windows, field-count caps, collapse
        combination rules."""
        int_max = 2**31 - 1
        for key in ("from", "size"):
            v = body.get(key)
            if v is None:
                continue
            v = int(v)
            if v > int_max or v < -(2**31):
                raise InputCoercionException(
                    f"Numeric value ({v}) out of range of int "
                    f"(-2147483648 - 2147483647)"
                )
        from_ = int(body.get("from") or 0)
        size_raw = body.get("size")
        size = int(size_raw) if size_raw is not None else search_service.DEFAULT_SIZE
        if from_ < 0:
            raise IllegalArgumentException(
                f"[from] parameter cannot be negative, found [{from_}]"
            )
        if size_raw is not None and size < 0:
            raise IllegalArgumentException(
                f"[size] parameter cannot be negative, found [{size}]"
            )
        rescore = body.get("rescore")
        rescore_stages = (rescore if isinstance(rescore, list)
                          else [rescore] if rescore is not None else [])
        dv_count = len(body.get("docvalue_fields") or [])
        sf_count = len(body.get("script_fields") or {})
        for n in names:
            if n not in self.indices:
                continue
            mrw = self._index_int_setting(n, "max_result_window", 10000)
            if scroll:
                if size > mrw:
                    raise IllegalArgumentException(
                        f"Batch size is too large, size must be less than "
                        f"or equal to: [{mrw}] but was [{size}]. Scroll "
                        f"batch sizes cost as much memory as result windows "
                        f"so they are controlled by the "
                        f"[index.max_result_window] index level setting."
                    )
            elif from_ + size > mrw and body.get("search_after") is None:
                raise IllegalArgumentException(
                    f"Result window is too large, from + size must be less "
                    f"than or equal to: [{mrw}] but was [{from_ + size}]. "
                    f"See the scroll api for a more efficient way to "
                    f"request large data sets. This limit can be set by "
                    f"changing the [index.max_result_window] index level "
                    f"setting."
                )
            max_rescore = self._index_int_setting(n, "max_rescore_window", 10000)
            for stage in rescore_stages:
                if not isinstance(stage, dict):
                    continue
                w = int(stage.get("window_size", 10))
                if w > max_rescore:
                    raise IllegalArgumentException(
                        f"Rescore window [{w}] is too large. It must be "
                        f"less than [{max_rescore}]. This prevents "
                        f"allocating massive heaps for storing the results "
                        f"to be rescored. This limit can be set by changing "
                        f"the [index.max_rescore_window] index level "
                        f"setting."
                    )
            max_dv = self._index_int_setting(
                n, "max_docvalue_fields_search", 100)
            if dv_count > max_dv:
                raise IllegalArgumentException(
                    f"Trying to retrieve too many docvalue_fields. Must be "
                    f"less than or equal to: [{max_dv}] but was "
                    f"[{dv_count}]. This limit can be set by changing the "
                    f"[index.max_docvalue_fields_search] index level "
                    f"setting."
                )
            max_sf = self._index_int_setting(n, "max_script_fields", 32)
            if sf_count > max_sf:
                raise IllegalArgumentException(
                    f"Trying to retrieve too many script_fields. Must be "
                    f"less than or equal to: [{max_sf}] but was "
                    f"[{sf_count}]. This limit can be set by changing the "
                    f"[index.max_script_fields] index level setting."
                )
        if str(self.effective_cluster_setting(
                "search.allow_expensive_queries", True)).lower() == "false":
            expensive = self._find_expensive_query(body.get("query"))
            if expensive and expensive[0] == "range":
                # ranges are expensive only over text/keyword columns
                ftypes = set()
                for n in names:
                    svc_q = self.indices.get(n)
                    m_q = (svc_q.mapper_service.field_mapper(expensive[1])
                           if svc_q and expensive[1] else None)
                    if m_q is not None:
                        ftypes.add(m_q.type)
                if not ftypes & {"text", "keyword", "flat_object"}:
                    expensive = None
            if expensive:
                kind, qfield = expensive
                msg = (f"[{kind}] queries cannot be executed when "
                       f"'search.allow_expensive_queries' is set to false.")
                def _field_type(fld):
                    for n in names:
                        svc_q = self.indices.get(n)
                        m_q = (svc_q.mapper_service.field_mapper(fld)
                               if svc_q and fld else None)
                        if m_q is not None:
                            return m_q.type
                    return None

                if kind == "prefix" and _field_type(qfield) == "text":
                    msg += (" For optimised prefix queries on text "
                            "fields please enable [index_prefixes].")
                elif kind == "range":
                    msg = ("[range] queries on [text] or [keyword] fields "
                           "cannot be executed when "
                           "'search.allow_expensive_queries' is set to "
                           "false.")
                elif kind in ("nested", "has_child", "has_parent",
                              "parent_id"):
                    msg = ("[joining] queries cannot be executed when "
                           "'search.allow_expensive_queries' is set to "
                           "false.")
                raise IllegalArgumentException(msg)
        # mixed-type sort across indices: unsigned_long cannot sort
        # against other numeric types (FieldSortBuilder's validation)
        sort_b = body.get("sort")
        sort_list_v = ([sort_b] if isinstance(sort_b, (str, dict))
                       else (sort_b or []))
        for spec_v in sort_list_v:
            fname_v = (spec_v if isinstance(spec_v, str)
                       else next(iter(spec_v), None))
            if not fname_v or fname_v.startswith("_"):
                continue
            kinds = set()
            for n in names:
                svc_v = self.indices.get(n)
                if svc_v is None:
                    continue
                m_v = svc_v.mapper_service.field_mapper(fname_v)
                if m_v is None:
                    continue
                kinds.add("unsigned_long"
                          if m_v.original_type == "unsigned_long"
                          else m_v.type)
            if "unsigned_long" in kinds and len(kinds) > 1:
                from opensearch_tpu.common.errors import (
                    SearchPhaseExecutionException,
                )

                cause_msg = (
                    "Can't do sort across indices, as a field has "
                    "[unsigned_long] type in one index, and different "
                    "type in another index!"
                )
                e = SearchPhaseExecutionException(
                    f"{cause_msg} (field [{fname_v}])"
                )
                e.status = 400
                raise e from IllegalArgumentException(cause_msg)
        if body.get("collapse") is not None:
            if scroll:
                raise IllegalArgumentException(
                    "cannot use `collapse` in a scroll context"
                )
            if rescore_stages:
                raise IllegalArgumentException(
                    "cannot use `collapse` in conjunction with `rescore`"
                )
            if body.get("search_after") is not None:
                cfield = (body["collapse"] or {}).get("field")
                sort = body.get("sort")
                if isinstance(sort, (str, dict)):
                    sort = [sort]
                sort_fields = []
                for s in sort or []:
                    if isinstance(s, str):
                        sort_fields.append(s)
                    elif isinstance(s, dict) and s:
                        sort_fields.append(next(iter(s)))
                if sort_fields != [cfield]:
                    raise IllegalArgumentException(
                        "collapse field and sort field must be the same "
                        "when use `collapse` in conjunction with "
                        "`search_after`"
                    )

    def _search_with_pipeline(
        self,
        pipeline_id: str | None,
        index_names: list[str],
        shards: list,
        body: dict,
        acquired: list | None = None,
        shard_filters: list | None = None,
        task=None,
        precomputed_results: list | None = None,
    ) -> dict:
        """search_service.search wrapped in the pipeline pre/post steps.
        Telemetry (span, metrics, slowlog) lives HERE so PIT and scroll
        searches are covered too, not just the plain path."""
        expr = ",".join(index_names) or "_pit"
        body = self._resolve_mlt_doc_refs(body, index_names)
        body = self._resolve_terms_lookup(body)
        pl, pr_config = self._resolve_search_pipeline(pipeline_id, index_names)
        pl_ctx = {}
        if pl is not None:
            body = self.search_pipelines.transform_request(pl, body)
            if "_original_size" in body:
                pl_ctx["_original_size"] = body.pop("_original_size")
        from opensearch_tpu.telemetry import spans as span_names
        from opensearch_tpu.telemetry import tracing

        # activate() scopes phase spans (can_match/rescore/collapse) to
        # THIS node's ring; the slowlog call stays inside the span so its
        # entry can carry the trace_id
        with tracing.activate(self.telemetry.tracer), \
                self.telemetry.tracer.start_span(
                    span_names.SEARCH, {"indices": expr}
                ) as span:
            resp = search_service.search(
                shards, body, acquired=acquired,
                phase_results_config=pr_config,
                shard_filters=shard_filters, task=task,
                precomputed_results=precomputed_results,
            )
            took = resp.get("took", 0)
            span.set_attribute("took_ms", took)
            self.search_slowlog.maybe_log(
                took, expr, json.dumps(body.get("query") or {})
            )
            # metrics record INSIDE the span so the histogram exemplar
            # captures this trace id (a p99 bucket links to the trace)
            self.telemetry.metrics.counter("search.total").add(1)
            self.telemetry.metrics.histogram("search.took_ms").record(took)
            # per-index series under the SAME constant metric name (vary
            # labels, not names — TPU013); wildcard/multi-index targets
            # stay base-series-only, and the registry bounds cardinality
            if len(index_names) == 1 and "*" not in expr:
                self.telemetry.metrics.histogram(
                    "search.took_ms", labels={"index": expr}).record(took)
            # per-LANE series (ISSUE 11): the lane rides the request's
            # contextvar scope from the REST boundary, so interactive vs
            # background tail behavior separates in one histogram family
            from opensearch_tpu.search import lanes as lanes_mod

            self.telemetry.metrics.histogram(
                "search.took_ms",
                labels={"lane": lanes_mod.active_lane()}).record(took)
        if pl is not None:
            resp = self.search_pipelines.transform_response(
                pl, {**body, **pl_ctx}, resp
            )
        return resp

    def _resolve_terms_lookup(self, body: dict) -> dict:
        """Terms lookup ({"terms": {"f": {"index","id","path"}}}) resolved
        coordinator-side to a concrete values array BEFORE shard execution
        (TermsQueryBuilder's fetch in the rewrite phase)."""
        import copy as _copy

        found = False

        def scan(obj):
            nonlocal found
            if isinstance(obj, dict):
                t = obj.get("terms")
                if isinstance(t, dict) and any(
                    isinstance(v, dict) and "index" in v
                    and ("id" in v or "query" in v)
                    for v in t.values()
                ):
                    found = True
                for v in obj.values():
                    scan(v)
            elif isinstance(obj, list):
                for v in obj:
                    scan(v)

        scan(body)
        if not found:
            return body
        body = _copy.deepcopy(body)

        def resolve(obj):
            if isinstance(obj, dict):
                t = obj.get("terms")
                if isinstance(t, dict):
                    for fname, spec in list(t.items()):
                        if not (isinstance(spec, dict) and "index" in spec
                                and ("id" in spec or "query" in spec)):
                            continue
                        path = str(spec.get("path", ""))

                        def extract(source: dict) -> list:
                            values: list = []
                            nodes = [source or {}]
                            for part in path.split("."):
                                nxt = []
                                for nd in nodes:
                                    if isinstance(nd, list):
                                        nd2 = [x.get(part) for x in nd
                                               if isinstance(x, dict)]
                                        nxt.extend(x for x in nd2
                                                   if x is not None)
                                    elif isinstance(nd, dict) \
                                            and part in nd:
                                        nxt.append(nd[part])
                                nodes = nxt
                            for nd in nodes:
                                if isinstance(nd, list):
                                    values.extend(
                                        v for v in nd if v is not None
                                    )
                                elif nd is not None:
                                    values.append(nd)
                            return values

                        values = []
                        if "id" in spec:
                            got = self.get_doc(str(spec["index"]),
                                               str(spec["id"]),
                                               routing=spec.get("routing"))
                            if got.get("found"):
                                values = extract(got.get("_source", {}))
                        else:
                            # lookup by QUERY (3.2.0): every matching doc
                            # contributes its path values
                            resp = self.search(str(spec["index"]), {
                                "query": spec["query"],
                                "size": int(spec.get("size", 10000)),
                            })
                            for hit in resp["hits"]["hits"]:
                                values.extend(
                                    extract(hit.get("_source", {}))
                                )
                        t[fname] = values
                for v in obj.values():
                    resolve(v)
            elif isinstance(obj, list):
                for v in obj:
                    resolve(v)

        resolve(body)
        return body

    def _resolve_mlt_doc_refs(self, body: dict,
                              index_names: list[str] | None = None) -> dict:
        """Resolve more_like_this {_index,_id} doc refs to their field
        texts BEFORE shard execution (the two-phase rewrite of
        MoreLikeThisQueryBuilder, which multi-gets the like-docs)."""
        found_refs = False

        def scan(obj):
            nonlocal found_refs
            if isinstance(obj, dict):
                mlt = obj.get("more_like_this")
                if isinstance(mlt, dict):
                    like = mlt.get("like")
                    likes = (like if isinstance(like, list)
                             else [like] if like is not None else [])
                    if any(isinstance(x, dict) for x in likes):
                        found_refs = True
                for v in obj.values():
                    scan(v)
            elif isinstance(obj, list):
                for x in obj:
                    scan(x)

        scan(body)
        if not found_refs:
            return body
        import copy

        body = copy.deepcopy(body)

        def resolve(obj):
            if isinstance(obj, dict):
                mlt = obj.get("more_like_this")
                if isinstance(mlt, dict):
                    like = mlt.get("like")
                    likes = (like if isinstance(like, list)
                             else [like] if like is not None else [])
                    texts = [x for x in likes if isinstance(x, str)]
                    fields = mlt.get("fields")
                    default_index = (index_names or [""])[0]
                    for ref in (x for x in likes if isinstance(x, dict)):
                        try:
                            got = self.get_doc(
                                str(ref.get("_index", default_index)),
                                str(ref.get("_id", "")),
                            )
                        except OpenSearchTpuException:
                            continue
                        if not got.get("found"):
                            continue
                        flat = _flatten_source_fields(got["_source"])
                        for fname, val in flat.items():
                            if fields and fname not in fields:
                                continue
                            vals = val if isinstance(val, list) else [val]
                            texts.extend(str(v) for v in vals)
                    mlt["like"] = texts
                for v in obj.values():
                    resolve(v)
            elif isinstance(obj, list):
                for x in obj:
                    resolve(x)

        resolve(body)
        return body

    def _resolve_search_pipeline(
        self, pipeline_id: str | dict | None, index_names: list[str]
    ) -> tuple[dict | None, dict | None]:
        """Explicit search_pipeline param > the body's `search_pipeline` (an
        id, or a pipeline object: a temporary search pipeline, this
        request's alone) > index.search.default_pipeline.
        Returns (pipeline, phase_results_config)."""
        if pipeline_id == "_none":
            return None, None
        if pipeline_id is None:
            for name in index_names:
                svc = self.indices.get(name)
                default = (
                    (svc.settings.get("search") or {}).get("default_pipeline")
                    if svc else None
                )
                if default and default != "_none":
                    pipeline_id = default
                    break
        if pipeline_id is None:
            return None, None
        pl = self.search_pipelines.resolve(pipeline_id)
        return pl, self.search_pipelines.phase_results_config(pl)

    # -- reader contexts: scroll + point-in-time (ReaderContext registry) --

    def _reap_expired_contexts(self) -> None:
        now = _now_ms()
        # PIT searches run on the parallel search pool: two reaps can race
        # each other (and the serial worker's inserts), so iterate over an
        # atomic list() snapshot and pop() — a victim already removed by a
        # concurrent reap is simply gone, never a KeyError
        for cid, ctx in list(self._reader_contexts.items()):
            if ctx["expires_at"] < now:
                self._reader_contexts.pop(cid, None)

    def _resolve_reader_context(self, cid: str, kind: str) -> dict:
        self._reap_expired_contexts()
        ctx = self._reader_contexts.get(cid)
        if ctx is None or ctx["kind"] != kind:
            raise SearchContextMissingException(cid)
        return ctx

    def _start_scroll(self, shards: list, body: dict, scroll: str,
                      pipeline_id: str | None = None,
                      names: list[str] | None = None,
                      shard_filters: list | None = None) -> dict:
        self._reap_expired_contexts()
        keep_ms = parse_time_value_millis(scroll, "scroll", positive=True)
        self._check_keep_alive(keep_ms, scroll)
        cid = f"scroll_{uuid.uuid4().hex}"
        snapshots = [s.acquire_searcher() for s in shards]
        size = int(body.get("size", search_service.DEFAULT_SIZE))
        ctx = {
            "id": cid, "kind": "scroll", "shards": shards,
            "snapshots": snapshots, "body": body, "seen": size,
            "size": size, "keep_alive_ms": keep_ms,
            "expires_at": _now_ms() + keep_ms,
            "pipeline_id": pipeline_id, "names": names or [],
            "shard_filters": shard_filters,
        }
        self.search_backpressure.admit()
        with self.task_manager.task_scope(
            "indices:data/read/search", description=f"scroll[{cid}]"
        ) as task:
            resp = self._search_with_pipeline(
                pipeline_id, names or [], shards, body, acquired=snapshots,
                shard_filters=shard_filters, task=task,
            )
        self._reader_contexts[cid] = ctx
        resp["_scroll_id"] = cid
        return resp

    def scroll(self, scroll_id: str, scroll: str | None = None) -> dict:
        """Next scroll page. Pages deepen from+size against the PINNED
        snapshots (deterministic order on an immutable view — the reference
        instead persists per-shard collector state; deepening trades compute
        for simplicity and is exact)."""
        ctx = self._resolve_reader_context(scroll_id, "scroll")
        if scroll is not None:
            keep_ms = parse_time_value_millis(scroll, "scroll", positive=True)
            self._check_keep_alive(keep_ms, scroll)
            ctx["keep_alive_ms"] = keep_ms
        ctx["expires_at"] = _now_ms() + ctx["keep_alive_ms"]
        page_body = {k: v for k, v in ctx["body"].items()
                     if k not in ("aggs", "aggregations")}
        page_body["from"] = ctx["seen"]
        page_body["size"] = ctx["size"]
        self.search_backpressure.admit()
        with self.task_manager.task_scope(
            "indices:data/read/search", description=f"scroll[{scroll_id}]"
        ) as task:
            resp = self._search_with_pipeline(
                ctx.get("pipeline_id"), ctx.get("names", []), ctx["shards"],
                page_body, acquired=ctx["snapshots"],
                shard_filters=ctx.get("shard_filters"), task=task,
            )
        ctx["seen"] += len(resp["hits"]["hits"])
        resp["_scroll_id"] = scroll_id
        return resp

    def clear_scroll(self, scroll_ids: list[str] | None) -> dict:
        self._reap_expired_contexts()
        freed = 0
        # list() snapshot: a parallel-pool PIT search may reap concurrently
        ids = scroll_ids or [c for c, x in list(self._reader_contexts.items())
                             if x["kind"] == "scroll"]
        for cid in list(ids):
            if self._reader_contexts.pop(cid, None) is not None:
                freed += 1
        return {"succeeded": True, "num_freed": freed}

    def open_pit(self, index: str, keep_alive: str) -> dict:
        self._reap_expired_contexts()
        keep_ms = parse_time_value_millis(keep_alive, "keep_alive", positive=True)
        shards, shard_filters, _ = self.resolve_search_shards(index)
        cid = f"pit_{uuid.uuid4().hex}"
        created = int(time.time() * 1000)
        self._reader_contexts[cid] = {
            "id": cid, "kind": "pit", "shards": shards,
            "snapshots": [s.acquire_searcher() for s in shards],
            "shard_filters": shard_filters,
            "keep_alive_ms": keep_ms, "expires_at": _now_ms() + keep_ms,
            "creation_time": created,
        }
        return {"pit_id": cid, "_shards": {"total": len(shards),
                                           "successful": len(shards),
                                           "skipped": 0, "failed": 0},
                "creation_time": created}

    def list_all_pits(self) -> dict:
        """GET /_search/point_in_time/_all (RestGetAllPitsAction): every
        live PIT with its configured keep_alive and creation time."""
        self._reap_expired_contexts()
        pits = [
            {"pit_id": cid,
             "creation_time": ctx.get("creation_time", 0),
             "keep_alive": ctx["keep_alive_ms"]}
            for cid, ctx in list(self._reader_contexts.items())
            if ctx["kind"] == "pit"
        ]
        return {"pits": pits}

    def close_pit(self, pit_ids: list[str] | None) -> dict:
        self._reap_expired_contexts()
        ids = pit_ids or [c for c, x in list(self._reader_contexts.items())
                          if x["kind"] == "pit"]
        pits = []
        for cid in list(ids):
            ok = self._reader_contexts.pop(cid, None) is not None
            pits.append({"pit_id": cid, "successful": ok})
        return {"pits": pits}

    def msearch(self, searches: list[tuple[dict, dict]]) -> dict:
        """Runs of consecutive bare-knn sub-searches against the SAME index
        execute their query phase as ONE batched device dispatch
        (search_service.try_batched_knn_msearch — B query vectors in one
        program launch); everything else runs serially, exactly as the
        reference's TransportMultiSearchAction fans out per sub-request."""
        responses: list[dict | None] = [None] * len(searches)
        for group in search_service.msearch_groups(searches):
            index = searches[group[0]][0].get("index")
            precomputed = None
            if len(group) > 1:
                precomputed = self._try_msearch_knn_batch(
                    index, [searches[g][1] for g in group]
                )
            # precomputed None -> the whole group runs serially (each
            # member still eligible for the single-query device path)
            for slot, g in enumerate(group):
                gidx = searches[g][0].get("index")
                try:
                    responses[g] = self.search(
                        # None (no index) keeps the PIT path legal in msearch
                        gidx, searches[g][1],
                        precomputed_results=(
                            precomputed[slot] if precomputed else None
                        ),
                    )
                except OpenSearchTpuException as e:
                    responses[g] = {"error": e.to_dict(), "status": e.status}
        return {"took": 0, "responses": responses}

    def _try_msearch_knn_batch(
        self, index: str, bodies: list[dict]
    ) -> list[list] | None:
        """Resolve `index` once, pin one set of searcher snapshots, and run
        the batched knn query phase over them. Returns per-body
        precomputed_results for search(), or None (serial fallback)."""
        try:
            shards, shard_filters, names = self.resolve_search_shards(index)
        except OpenSearchTpuException:
            return None  # the serial path reports the error per sub-search
        # alias filters differ per shard and are not folded into a shared
        # batch mask; keep those on the serial path (each sub-search is
        # still eligible for the single-query device path with its filter)
        if any(f is not None for f in (shard_filters or [])):
            return None
        # a default search pipeline rewrites the request AFTER this batch
        # would have scored it — those indices must take the serial path,
        # where _search_with_pipeline applies the transform first
        for name in names:
            svc = self.indices.get(name)
            if svc is not None and svc.setting("search.default_pipeline"):
                return None
        snaps = [s.acquire_searcher() for s in shards]
        return search_service.try_batched_knn_msearch(shards, bodies, snaps)

    def count(self, index: str, body: dict | None = None) -> dict:
        body = dict(body or {})
        body["size"] = 0
        resp = self.search(index, body)
        return {
            "count": resp["hits"]["total"]["value"],
            "_shards": resp["_shards"],
        }

    # -- cluster/stats APIs ------------------------------------------------

    def put_index_settings(self, index_expr: str, body: dict) -> dict:
        """PUT /{index}/_settings: merge DYNAMIC index settings (the
        IndexScopedSettings update path). Static settings
        (number_of_shards) reject on open indices like the reference."""
        settings = body.get("settings", body) or {}
        flat = Settings.from_nested(settings).as_dict()
        norm = {}
        for k, v in flat.items():
            norm[k[len("index."):] if k.startswith("index.") else k] = v
        if "number_of_shards" in norm:
            raise IllegalArgumentException(
                "final index setting [index.number_of_shards], not updateable"
            )
        for name in self.resolve_indices(index_expr,
                                         expand_wildcards="all"):
            svc = self._get_index(name)
            nested = Settings.from_flat(norm).as_nested()
            svc.settings = _deep_merge(svc.settings, nested)
            svc.settings_changed()
            if "number_of_replicas" in norm:
                svc.num_replicas = int(norm["number_of_replicas"])
        self._persist_index_registry()
        self._configure_slowlogs()
        return {"acknowledged": True}

    def _settings_view(self, flat_map: dict, flat: bool) -> dict:
        return settings_section(flat_map, flat)

    # the reference test cluster starts nodes with node.attr.testattr=test;
    # surfaced by ?include_defaults (cluster.get_settings YAML)
    _CLUSTER_SETTING_DEFAULTS = {
        "node.attr.testattr": "test",
        "cluster.routing.allocation.enable": "all",
        "search.max_buckets": "65536",
        "search.allow_expensive_queries": "true",
    }

    def _apply_dynamic_node_settings(self, changed=()) -> None:
        """Push the effective dynamic cluster settings into the node
        components that consume them (the addSettingsUpdateConsumer analog
        for the single-node deployment): the kNN dispatch batcher and the
        request-cache byte budget.

        The batcher is PROCESS-wide, so it is only touched when this
        node's effective settings carry batch keys or this update
        (`changed` = the keys the caller just PUT, including null
        deletions) names one — another in-process node updating an
        unrelated setting (or merely booting) must not clobber live
        configuration with its own defaults. A null deletion reverts to
        the Setting default: the deleted key is in `changed`, and
        apply_settings/get resolve absent keys to defaults. The request
        cache is per-node and applies unconditionally."""
        from opensearch_tpu.cluster.cluster_settings import effective
        from opensearch_tpu.common.settings import Settings
        from opensearch_tpu.index.request_cache import CACHE_SIZE_SETTING
        from opensearch_tpu.search.batcher import BATCH_SETTINGS

        eff = effective(
            getattr(self, "_cluster_settings", {}),
            getattr(self, "_transient_cluster_settings", {}),
        )
        if any(s.key in eff or s.key in changed for s in BATCH_SETTINGS):
            self.knn_batcher.apply_settings(eff)
        # ANN serving knobs share the batcher's process-wide guard: only an
        # update that actually names an ANN key may touch the live config
        from opensearch_tpu.search.ann import ANN_SETTINGS, default_config

        if any(s.key in eff or s.key in changed for s in ANN_SETTINGS):
            default_config.apply_settings(eff)
        # shard-mesh HBM byte budget: the registry is process-wide like the
        # batcher, so the same only-when-named guard applies
        from opensearch_tpu.cluster.shard_mesh import (
            MESH_SETTINGS,
            default_registry,
        )

        if any(s.key in eff or s.key in changed for s in MESH_SETTINGS):
            default_registry.apply_settings(eff)
        # priority lanes + residency routing (ISSUE 11): process-wide
        # policy toggles under the same only-when-named guard
        from opensearch_tpu.cluster import residency as residency_mod
        from opensearch_tpu.search import lanes as lanes_mod

        if any(s.key in eff or s.key in changed
               for s in lanes_mod.LANE_SETTINGS):
            lanes_mod.default_config.apply_settings(eff)
        if any(s.key in eff or s.key in changed
               for s in residency_mod.ROUTING_SETTINGS):
            residency_mod.default_config.apply_settings(eff)
        # heat/touch accounting (telemetry/device_ledger.py): the ledger
        # is process-wide like the batcher — same only-when-named guard
        from opensearch_tpu.telemetry.device_ledger import (
            HEAT_SETTINGS,
            default_ledger,
        )

        if any(s.key in eff or s.key in changed for s in HEAT_SETTINGS):
            default_ledger.apply_heat_settings(eff)
        self.request_cache.set_max_bytes(
            CACHE_SIZE_SETTING.get(Settings.from_flat(eff)))
        # span exporter: per-node (like the request cache), applies
        # unconditionally — absent keys resolve to the "none" default so a
        # null deletion detaches a live exporter
        from opensearch_tpu.telemetry.export import apply_tracing_settings

        apply_tracing_settings(self.telemetry, eff, self.data_path,
                               service_name=self.node_name)

    def put_cluster_settings(self, body: dict, *, flat: bool = False) -> dict:
        """Single-node /_cluster/settings: same validation + persistent/
        transient model, persisted to disk (persistent only). The response
        echoes the EFFECTIVE sections after the update (null deletions
        leave them empty, as the YAML suite asserts)."""
        from opensearch_tpu.cluster.cluster_settings import (
            flatten,
            merge,
            validate_settings,
        )

        persistent = flatten((body or {}).get("persistent") or {})
        transient = flatten((body or {}).get("transient") or {})
        validate_settings(persistent)
        validate_settings(transient)
        self._cluster_settings = merge(
            getattr(self, "_cluster_settings", {}), persistent
        )
        self._transient_cluster_settings = merge(
            getattr(self, "_transient_cluster_settings", {}), transient
        )
        self._apply_dynamic_node_settings(
            changed=set(persistent) | set(transient))
        import json as _json

        self.data_path.mkdir(parents=True, exist_ok=True)
        (self.data_path / "cluster_settings.json").write_text(
            _json.dumps(self._cluster_settings)
        )
        return {
            "acknowledged": True,
            "persistent": self._settings_view(self._cluster_settings, flat),
            "transient": self._settings_view(
                self._transient_cluster_settings, flat),
        }

    def get_cluster_settings(self, *, flat: bool = False,
                             include_defaults: bool = False) -> dict:
        import json as _json

        if not hasattr(self, "_cluster_settings"):
            path = self.data_path / "cluster_settings.json"
            self._cluster_settings = (
                _json.loads(path.read_text()) if path.exists() else {}
            )
        out = {
            "persistent": self._settings_view(self._cluster_settings, flat),
            "transient": self._settings_view(
                getattr(self, "_transient_cluster_settings", {}), flat),
        }
        if include_defaults:
            out["defaults"] = self._settings_view(
                {k: v for k, v in self._CLUSTER_SETTING_DEFAULTS.items()
                 if k not in self._cluster_settings
                 and k not in getattr(self, "_transient_cluster_settings",
                                      {})},
                flat,
            )
        return out

    def cluster_health(self, index: str | None = None,
                       level: str = "cluster",
                       expand_wildcards: str = "all") -> dict:
        """GET _cluster/health. Single-node truth: every primary is active
        on this node, every configured replica is unassigned (no peer to
        hold it) — so any index with replicas > 0 reports yellow, like the
        reference's single-node default. Closed indices are replicated
        (7.2+ semantics): they count toward health exactly like open ones,
        so a closed index with replicas stays yellow."""
        names = (sorted(self.indices) if index in (None, "", "_all")
                 else self.resolve_indices(index,
                                           expand_wildcards=expand_wildcards))
        active = 0
        unassigned = 0
        per_index: dict[str, Any] = {}
        worst = "green"
        for name in names:
            svc = self.indices[name]
            idx_active = svc.num_shards
            idx_unassigned = svc.num_shards * svc.num_replicas
            active += idx_active
            unassigned += idx_unassigned
            status = "yellow" if idx_unassigned else "green"
            if status == "yellow":
                worst = "yellow"
            entry: dict[str, Any] = {
                "status": status,
                "number_of_shards": svc.num_shards,
                "number_of_replicas": svc.num_replicas,
                "active_primary_shards": idx_active,
                "active_shards": idx_active,
                "relocating_shards": 0,
                "initializing_shards": 0,
                "unassigned_shards": idx_unassigned,
            }
            if level == "shards":
                entry["shards"] = {
                    str(s): {
                        "status": status,
                        "primary_active": True,
                        "active_shards": 1,
                        "relocating_shards": 0,
                        "initializing_shards": 0,
                        "unassigned_shards": svc.num_replicas,
                    }
                    for s in range(svc.num_shards)
                }
            per_index[name] = entry
        total = active + unassigned
        out = {
            "cluster_name": "opensearch-tpu",
            "status": worst,
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "discovered_master": True,
            "discovered_cluster_manager": True,
            "active_primary_shards": active,
            "active_shards": active,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": unassigned,
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number":
                (100.0 * active / total) if total else 100.0,
        }
        if level in ("indices", "shards"):
            out["indices"] = per_index
        return out

    # -- cluster state / coordination / allocation surface -----------------
    # (ClusterStateAction, TransportAddVotingConfigExclusionsAction,
    #  ClusterAllocationExplainAction, TransportClusterRerouteAction —
    #  single-node truth: this node is the elected cluster manager, every
    #  primary is local, every replica is unassigned)

    # index-level block settings -> (block id, levels) as in
    # cluster/block/ClusterBlockLevel + IndexMetadata.INDEX_*_BLOCK
    _INDEX_BLOCKS = {
        "blocks.read_only": (5, "index read-only (api)",
                             ["write", "metadata_write"]),
        "blocks.read": (7, "index read (api)", ["read"]),
        "blocks.write": (8, "index write (api)", ["write"]),
        "blocks.metadata": (9, "index metadata (api)",
                            ["metadata_read", "metadata_write"]),
        "blocks.read_only_allow_delete": (
            12, "disk usage exceeded flood-stage watermark, "
                "index has read-only-allow-delete block",
            ["write"]),
    }

    def add_voting_config_exclusions(self, node_ids: str | None = None,
                                     node_names: str | None = None) -> dict:
        provided = [p for p in (node_ids, node_names) if p]
        if len(provided) != 1:
            raise IllegalArgumentException(
                "Please set node identifiers correctly. One and only one "
                "of [node_name], [node_names] and [node_ids] has to be set"
            )
        if node_ids:
            entries = [{"node_id": nid.strip(), "node_name": "_absent_"}
                       for nid in str(node_ids).split(",") if nid.strip()]
        else:
            entries = [{"node_id": "_absent_", "node_name": nm.strip()}
                       for nm in str(node_names).split(",") if nm.strip()]
        for e in entries:
            if e not in self._voting_config_exclusions:
                self._voting_config_exclusions.append(e)
        self._state_version += 1
        return {}

    def clear_voting_config_exclusions(self) -> dict:
        self._voting_config_exclusions.clear()
        self._state_version += 1
        return {}

    def pending_cluster_tasks(self) -> dict:
        """GET /_cluster/pending_tasks: the single-node cluster applies
        state synchronously, so the queue is always drained."""
        return {"tasks": []}

    def _index_blocks(self, name: str) -> dict:
        svc = self.indices[name]
        out = {}
        for setting, (bid, desc, levels) in self._INDEX_BLOCKS.items():
            if str(svc.setting(setting, "false")).lower() == "true":
                out[str(bid)] = {"description": desc, "retryable": False,
                                 "levels": levels}
        return out

    def _shard_routing(self, name: str, shard: int, *, primary: bool,
                       assigned: bool) -> dict:
        entry: dict[str, Any] = {
            "state": "STARTED" if assigned else "UNASSIGNED",
            "primary": primary,
            "node": "node-0" if assigned else None,
            "relocating_node": None,
            "shard": shard,
            "index": name,
        }
        if assigned:
            entry["allocation_id"] = {"id": f"{name}#{shard}"}
        else:
            entry["recovery_source"] = {"type": "PEER"}
            entry["unassigned_info"] = {
                "reason": "INDEX_CREATED",
                "at": time.strftime("%Y-%m-%dT%H:%M:%S.000Z", time.gmtime()),
                "delayed": False,
                "allocation_status": "no_attempt",
            }
        return entry

    def cluster_state(self, metrics: list[str] | None = None,
                      index: str | None = None,
                      expand_wildcards: str = "all",
                      ignore_unavailable: bool = False,
                      allow_no_indices: bool = True) -> dict:
        want = set(metrics or ["_all"])
        everything = "_all" in want

        def on(metric: str) -> bool:
            return everything or metric in want

        names = (self.resolve_indices(
            index, expand_wildcards=expand_wildcards,
            ignore_unavailable=ignore_unavailable,
            allow_no_indices=allow_no_indices,
        ) if index else sorted(self.indices))
        out: dict[str, Any] = {
            "cluster_name": "opensearch-tpu",
            "cluster_uuid": self.cluster_uuid,
        }
        if everything or want & {"version", "master_node",
                                 "cluster_manager_node", "nodes", "blocks",
                                 "metadata", "routing_table", "routing_nodes"}:
            out["state_uuid"] = f"state-{self._state_version}"
        if on("version"):
            out["version"] = self._state_version
        if on("master_node"):
            out["master_node"] = "node-0"
        if on("cluster_manager_node"):
            out["cluster_manager_node"] = "node-0"
        if on("nodes"):
            out["nodes"] = {"node-0": {
                "name": self.node_name,
                "ephemeral_id": self.cluster_uuid,
                "transport_address": "127.0.0.1:9300",
                "attributes": {},
            }}
        if on("blocks"):
            blocks: dict[str, Any] = {}
            indices_blocks = {
                name: b for name in names
                if (b := self._index_blocks(name))
            }
            if indices_blocks:
                blocks["indices"] = indices_blocks
            out["blocks"] = blocks
        if on("metadata"):
            out["metadata"] = {
                "cluster_uuid": self.cluster_uuid,
                "cluster_uuid_committed": True,
                "cluster_coordination": {
                    "term": 1,
                    "last_committed_config": ["node-0"],
                    "last_accepted_config": ["node-0"],
                    "voting_config_exclusions":
                        list(self._voting_config_exclusions),
                },
                "templates": {},
                "indices": {
                    name: {
                        "state": ("close" if self.indices[name].closed
                                  else "open"),
                        "settings": self.get_settings(name)[name]["settings"],
                        "mappings":
                            self.indices[name].mapper_service.to_dict(),
                        "aliases": sorted(self.indices[name].aliases),
                    }
                    for name in names
                },
            }
        if on("routing_table"):
            out["routing_table"] = {"indices": {
                name: {"shards": {
                    str(s): (
                        [self._shard_routing(name, s, primary=True,
                                             assigned=True)]
                        + [self._shard_routing(name, s, primary=False,
                                               assigned=False)
                           for _ in range(self.indices[name].num_replicas)]
                    )
                    for s in range(self.indices[name].num_shards)
                }}
                for name in names
            }}
        if on("routing_nodes"):
            assigned = []
            unassigned = []
            for name in names:
                svc = self.indices[name]
                for s in range(svc.num_shards):
                    assigned.append(self._shard_routing(
                        name, s, primary=True, assigned=True))
                    for _ in range(svc.num_replicas):
                        unassigned.append(self._shard_routing(
                            name, s, primary=False, assigned=False))
            out["routing_nodes"] = {
                "unassigned": unassigned,
                "nodes": {"node-0": assigned},
            }
        return out

    def resize_index(self, kind: str, source: str, target: str,
                     body: dict | None = None) -> dict:
        """_shrink/_split/_clone (TransportResizeAction). In this design a
        resize is a RE-LAYOUT of the source's immutable docs onto the
        target's shard ring: same ids, same sources, new murmur3 routing —
        the columnar rebuild is the same sealed-segment path every write
        takes, so the result is bit-identical to a fresh index of the same
        docs. Source must be write-blocked for shrink/split; shard-count
        factor rules match the reference."""
        body = body or {}
        if source not in self.indices:
            raise IndexNotFoundException(source)
        if not _valid_index_name(target):
            raise IllegalArgumentException(f"invalid index name [{target}]")
        if target in self.indices:
            raise ResourceAlreadyExistsException(
                f"index [{target}] already exists")
        svc = self.indices[source]
        src_shards = svc.num_shards
        tgt_settings = dict((body.get("settings") or {}))
        flat_tgt = Settings.from_nested(tgt_settings).as_dict()

        def tgt_setting(name, default=None):
            return flat_tgt.get(name, flat_tgt.get(f"index.{name}", default))

        if tgt_setting("number_of_routing_shards") is not None:
            raise IllegalArgumentException(
                "cannot provide index.number_of_routing_shards on resize")
        for blk in ("blocks.metadata", "blocks.read_only"):
            if str(tgt_setting(blk, "false")).lower() == "true":
                from opensearch_tpu.common.errors import (
                    ActionRequestValidationException,
                )

                raise ActionRequestValidationException(
                    f"Validation Failed: 1: target index [{target}] will "
                    f"be blocked by [index.{blk}=true], this will disable "
                    f"metadata writes and cause the shards to be "
                    f"unassigned;")
        defaults = {"shrink": 1, "split": src_shards * 2, "clone": src_shards}
        tgt_shards = int(tgt_setting("number_of_shards", defaults[kind]))
        if kind == "shrink" and src_shards % tgt_shards != 0:
            raise IllegalArgumentException(
                f"the number of source shards [{src_shards}] must be a "
                f"multiple of [{tgt_shards}]")
        if kind == "split" and tgt_shards % src_shards != 0:
            raise IllegalArgumentException(
                f"the number of source shards [{src_shards}] must be a "
                f"factor of [{tgt_shards}]")
        if kind == "clone" and tgt_shards != src_shards:
            raise IllegalArgumentException(
                f"cannot clone from [{src_shards}] shards to "
                f"[{tgt_shards}] shards")
        # every resize kind requires a write-blocked source (the copy must
        # not race live writes); checked AFTER the shard-count argument
        # validation, matching the reference's error precedence
        if str(svc.setting("blocks.write", "false")).lower() != "true":
            from opensearch_tpu.common.errors import IllegalStateException

            raise IllegalStateException(
                f"index {source} must be read-only to resize index. use "
                f"\"index.blocks.write=true\"")

        # target settings = source settings COPIED (30_copy_settings)
        # overridden by the request's; explicit nulls UNSET inherited keys
        src_settings = Settings.from_nested(svc.settings or {}).as_dict()
        merged = dict(src_settings)
        for k, v in flat_tgt.items():
            key = k[len("index."):] if k.startswith("index.") else k
            if v is None:
                merged.pop(key, None)
            else:
                merged[key] = v
        merged["number_of_shards"] = tgt_shards
        # a read-only/metadata block INHERITED from the source (not set by
        # this request) also invalidates the target, as a plain 400
        for blk in ("blocks.metadata", "blocks.read_only"):
            if str(merged.get(blk, "false")).lower() == "true":
                raise IllegalArgumentException(
                    f"target index [{target}] will be blocked by "
                    f"[index.{blk}=true], this will disable metadata "
                    f"writes and cause the shards to be unassigned")
        # the copied write block applies AFTER the re-layout populates the
        # target, or the copy itself would be rejected
        deferred_blocks = {k: merged.pop(k) for k in list(merged)
                          if k.startswith("blocks.")}
        mappings = svc.mapper_service.to_dict()
        self.create_index(target, {
            "settings": Settings.from_flat(merged).as_nested(),
            "mappings": mappings,
        })
        tgt_svc = self.indices[target]
        for shard in svc.shards.values():
            snapshot = shard.acquire_searcher()
            seen: set[str] = set()
            for entry in shard.engine._buffer:
                if entry is None:
                    continue
                parsed, _seq = entry
                tgt_svc.shard_for(parsed.doc_id, parsed.routing) \
                    .apply_index_on_primary(parsed.doc_id, parsed.source,
                                            parsed.routing)
                seen.add(parsed.doc_id)
            for host, _dev in snapshot.segments:
                for d in range(host.n_docs):
                    if not host.live[d]:
                        continue
                    doc_id = host.doc_ids[d]
                    if doc_id in seen:
                        continue
                    seen.add(doc_id)
                    # an unrefreshed delete is only visible in the version
                    # map; the segment's live bitmap still says yes
                    entry = shard.engine.version_map.get(doc_id)
                    if entry is not None and entry.deleted:
                        continue
                    routing = host.doc_routings[d] \
                        if d < len(host.doc_routings) else None
                    tgt_svc.shard_for(doc_id, routing) \
                        .apply_index_on_primary(
                            doc_id, json.loads(host.sources[d]), routing)
        for shard in tgt_svc.shards.values():
            shard.engine.ensure_synced()
            # the re-layout hands over a SEARCHABLE index (the reference's
            # resize target recovers from complete segments)
            shard.refresh()
        if deferred_blocks:
            tgt_svc.settings = _deep_merge(
                tgt_svc.settings,
                Settings.from_flat(deferred_blocks).as_nested())
            tgt_svc.settings_changed()
        self._persist_index_registry()
        return {"acknowledged": True, "shards_acknowledged": True,
                "index": target}

    def search_shards(self, index: str | None = None,
                      routing: str | None = None,
                      body: dict | None = None,
                      preference: str | None = None) -> dict:
        """GET [/{index}]/_search_shards (ClusterSearchShardsAction): the
        shard groups a search would fan out to, plus per-index alias
        filter rendering; `routing` narrows to the routed shard, a `slice`
        body narrows to that slice's shards (shard % max == id)."""
        import fnmatch

        body = body or {}
        expr = index if index not in (None, "") else "_all"
        alias_map = self._alias_map()
        requested_aliases: dict[str, set] = {}
        filter_routes: dict[str, list] = {}
        names: list[str] = []

        def add_index(name: str, filt):
            svc = self._get_index(name)
            if svc.closed:
                return
            if name not in filter_routes:
                names.append(name)
                filter_routes[name] = []
            filter_routes[name].append(filt)

        def add_alias(alias: str):
            for name, conf in [
                (n, self.indices[n].aliases[alias])
                for n in alias_map.get(alias, [])
            ]:
                requested_aliases.setdefault(name, set()).add(alias)
                add_index(name, (conf or {}).get("filter"))

        for part in str(expr).split(","):
            part = part.strip()
            if not part:
                continue
            if part in ("_all", "*"):
                for n in sorted(self.indices):
                    add_index(n, None)
            elif "*" in part or "?" in part:
                for cand in sorted(set(self.indices) | set(alias_map)):
                    if fnmatch.fnmatch(cand, part):
                        if cand in alias_map:
                            add_alias(cand)
                        else:
                            add_index(cand, None)
            elif part in alias_map:
                add_alias(part)
            elif part in self.indices:
                add_index(part, None)
            else:
                raise IndexNotFoundException(part)

        def render_filter(f: dict) -> dict:
            # QueryBuilder toXContent shape: term filters expand to the
            # object form with explicit value/boost
            if isinstance(f, dict) and len(f) == 1 and "term" in f \
                    and isinstance(f["term"], dict) and len(f["term"]) == 1:
                fname, v = next(iter(f["term"].items()))
                if not isinstance(v, dict):
                    v = {"value": v}
                return {"term": {fname: {"boost": 1.0, **v}}}
            return f

        indices_out: dict[str, Any] = {}
        for name in sorted(names):
            entry: dict[str, Any] = {}
            aliases = sorted(requested_aliases.get(name, ()))
            if aliases:
                entry["aliases"] = aliases
            routes = filter_routes[name]
            if routes and all(f is not None for f in routes):
                if len(routes) == 1:
                    entry["filter"] = render_filter(routes[0])
                else:
                    entry["filter"] = {"bool": {
                        "should": [render_filter(f) for f in routes],
                        "adjust_pure_negative": True,
                        "boost": 1.0,
                    }}
            indices_out[name] = entry

        shard_groups = []
        sl = body.get("slice")
        for name in sorted(names):
            svc = self.indices[name]
            shard_ids = list(range(svc.num_shards))
            if routing is not None:
                shard_ids = [shard_id_for_routing(str(routing),
                                                  svc.num_shards)]
            elif str(preference or "").startswith("_shards:"):
                want = {int(s) for s in preference[len("_shards:"):].split(",")
                        if s.strip().isdigit()}
                shard_ids = [s for s in shard_ids if s in want]
            if isinstance(sl, dict) and routing is None:
                # the slice selects POSITIONS of the candidate list
                # (SliceBuilder over the target shards, so it composes
                # with _shards preference)
                sl_max = int(sl.get("max", 1))
                sl_id = int(sl.get("id", 0))
                shard_ids = [s for i, s in enumerate(shard_ids)
                             if i % sl_max == sl_id]
            for s in shard_ids:
                shard_groups.append([self._shard_routing(
                    name, s, primary=True, assigned=True)])
        return {
            "nodes": {"node-0": {
                "name": self.node_name,
                "ephemeral_id": self.cluster_uuid,
                "transport_address": "127.0.0.1:9300",
                "attributes": {},
            }},
            "indices": indices_out,
            "shards": shard_groups,
        }

    def allocation_explain(self, body: dict | None,
                           include_disk_info: bool = False) -> dict:
        """POST /_cluster/allocation/explain
        (ClusterAllocationExplainAction). With an explicit (index, shard,
        primary) triple, explains that shard; with an empty body, explains
        the first unassigned shard (the reference's useAnyUnassignedShard
        path) or rejects when nothing is unassigned."""
        body = body or {}
        index = body.get("index")
        if index is not None:
            names = self.resolve_indices(index)
            if not names:
                raise IndexNotFoundException(str(index))
            name = names[0]
            shard = int(body.get("shard", 0))
            primary = bool(body.get("primary", False))
            svc = self.indices[name]
            if shard >= svc.num_shards:
                raise IllegalArgumentException(
                    f"No shard was specified in the explain API request "
                    f"or shard [{shard}] does not exist in [{name}]"
                )
            assigned = primary  # primaries local, replicas unassigned
        else:
            name = shard = None
            for cname in sorted(self.indices):
                if self.indices[cname].num_replicas > 0:
                    name, shard, primary, assigned = cname, 0, False, False
                    break
            if name is None:
                raise IllegalArgumentException(
                    "unable to find any unassigned shards to explain "
                    "[ClusterAllocationExplainRequest[useAnyUnassignedShard="
                    "true,includeYesDecisions?=false]"
                )
        out: dict[str, Any] = {
            "index": name,
            "shard": shard,
            "primary": primary,
            "current_state": "started" if assigned else "unassigned",
        }
        if include_disk_info:
            fs = self.monitor.fs_stats()
            out["cluster_info"] = {"nodes": {"node-0": {
                "node_name": self.node_name,
                "least_available": fs,
                "most_available": fs,
            }}}
        if assigned:
            out["current_node"] = {
                "id": "node-0", "name": self.node_name,
                "transport_address": "127.0.0.1:9300",
            }
            out["can_remain_on_current_node"] = "yes"
            out["can_rebalance_cluster"] = "yes"
            out["can_rebalance_to_other_node"] = "no"
            out["rebalance_explanation"] = (
                "cannot rebalance as no target node exists that can both "
                "allocate this shard and improve the cluster balance"
            )
        else:
            out["unassigned_info"] = {
                "reason": "INDEX_CREATED",
                "at": time.strftime("%Y-%m-%dT%H:%M:%S.000Z", time.gmtime()),
                "last_allocation_status": "no_attempt",
            }
            out["can_allocate"] = "no"
            out["allocate_explanation"] = (
                "cannot allocate because allocation is not permitted to "
                "any of the nodes"
            )
            out["node_allocation_decisions"] = [{
                "node_id": "node-0",
                "node_name": self.node_name,
                "transport_address": "127.0.0.1:9300",
                "node_decision": "no",
                "deciders": [{
                    "decider": "same_shard",
                    "decision": "NO",
                    "explanation": (
                        "a copy of this shard is already allocated to "
                        "this node"
                    ),
                }],
            }]
        return out

    def cluster_reroute(self, body: dict | None, *, explain: bool = False,
                        dry_run: bool = False,
                        metrics: list[str] | None = None) -> dict:
        """POST /_cluster/reroute (TransportClusterRerouteAction). The
        single-node allocator has nowhere to move shards, so commands only
        produce explanations; the response carries the filtered cluster
        state like the reference (RestClusterRerouteAction defaults to
        everything except metadata)."""
        body = body or {}
        explanations = []
        for cmd in body.get("commands", []) or []:
            if not isinstance(cmd, dict) or len(cmd) != 1:
                raise IllegalArgumentException(
                    f"malformed reroute command [{cmd}]")
            (kind, args), = cmd.items()
            args = args or {}
            params = {
                "index": args.get("index"),
                "shard": args.get("shard"),
                "node": args.get("node"),
            }
            if kind in ("cancel", "allocate_replica", "allocate_stale_primary",
                        "allocate_empty_primary"):
                if kind == "cancel":
                    params["allow_primary"] = bool(args.get("allow_primary",
                                                            False))
                if kind in ("allocate_stale_primary",
                            "allocate_empty_primary"):
                    params["accept_data_loss"] = bool(
                        args.get("accept_data_loss", False))
                decider = (f"{kind}_allocation_command"
                           if kind == "cancel" else "allocate_command")
                explanations.append({
                    "command": kind,
                    "parameters": params,
                    "decisions": [{
                        "decider": decider,
                        "decision": "NO",
                        "explanation": (
                            f"can't {kind} [{params['index']}]["
                            f"{params['shard']}], failed to find it on "
                            f"node [{params['node']}]"
                        ),
                    }],
                })
            elif kind == "move":
                params["from_node"] = args.get("from_node")
                params["to_node"] = args.get("to_node")
                explanations.append({
                    "command": kind,
                    "parameters": params,
                    "decisions": [{
                        "decider": "move_allocation_command",
                        "decision": "NO",
                        "explanation": (
                            "shard not found on source node"
                        ),
                    }],
                })
            else:
                raise IllegalArgumentException(
                    f"unknown reroute command [{kind}]")
        default_metrics = ["version", "master_node", "cluster_manager_node",
                           "nodes", "routing_table", "routing_nodes",
                           "blocks"]
        state = self.cluster_state(metrics=metrics or default_metrics)
        state.pop("cluster_name", None)
        out: dict[str, Any] = {"acknowledged": True, "state": state}
        if explain or body.get("commands") is not None:
            out["explanations"] = explanations
        return out

    _STATS_SECTIONS = (
        "docs", "store", "indexing", "get", "search", "merges", "refresh",
        "flush", "warmer", "query_cache", "fielddata", "completion",
        "segments", "translog", "request_cache", "recovery",
    )
    # REST metric name -> response section (IndicesStatsRequest flags)
    _METRIC_ALIASES = {"merge": "merges"}

    @staticmethod
    def _field_bytes(shard, field: str) -> int:
        """Estimated columnar (fielddata-class) bytes for one field across
        a shard's sealed segments."""
        total = 0
        for host, _dev in shard.engine._segments:
            kf = host.keyword_fields.get(field)
            if kf is not None:
                total += int(kf.mv_ords.nbytes + kf.first_ord.nbytes)
            nf = host.numeric_fields.get(field)
            if nf is not None:
                total += 8 * host.n_docs
            tf = host.text_fields.get(field)
            if tf is not None:
                total += int(tf.doc_len.nbytes)
        return total

    def _completion_fields_of(self, svc) -> list[str]:
        # completion fields store keyword-style with mapper.completion=True
        return [n for n, m in svc.mapper_service.mappers.items()
                if m.type == "completion" or getattr(m, "completion", False)]

    def _full_shard_stats(self, svc, shard, *, f_pats, c_pats,
                          groups, file_sizes, human) -> dict:
        import fnmatch as _fn

        eng = shard.engine
        seg = eng.segment_stats()
        tlog = eng.translog.stats()
        store_bytes = tlog["size_in_bytes"]
        for host, _dev in eng._segments:
            store_bytes += sum(len(s) for s in host.sources)
        st: dict[str, Any] = {
            "docs": {"count": eng.num_docs,
                     "deleted": max(seg["docs"] - seg["live_docs"], 0)},
            "store": {"size_in_bytes": store_bytes, "reserved_in_bytes": 0},
            "indexing": {
                "index_total": eng.stats["index_total"],
                "index_time_in_millis": int(eng.stats["index_time_ms"]),
                "index_current": 0, "index_failed": 0,
                "delete_total": eng.stats["delete_total"],
                "delete_time_in_millis": 0, "delete_current": 0,
                "noop_update_total": eng.stats.get("noop_update_total", 0),
                "is_throttled": False, "throttle_time_in_millis": 0,
            },
            "get": {"total": 0, "time_in_millis": 0, "exists_total": 0,
                    "exists_time_in_millis": 0, "missing_total": 0,
                    "missing_time_in_millis": 0, "current": 0},
            "search": {"open_contexts": 0, "query_total": 0,
                       "query_time_in_millis": 0, "query_current": 0,
                       "fetch_total": 0, "fetch_time_in_millis": 0,
                       "fetch_current": 0, "scroll_total": 0,
                       "scroll_time_in_millis": 0, "scroll_current": 0},
            "merges": {"current": 0, "current_docs": 0,
                       "current_size_in_bytes": 0, "total": 0,
                       "total_time_in_millis": 0, "total_docs": 0,
                       "total_size_in_bytes": 0},
            "refresh": {"total": eng.stats["refresh_total"],
                        "total_time_in_millis": 0,
                        "external_total": eng.stats["refresh_total"],
                        "external_total_time_in_millis": 0, "listeners": 0},
            "flush": {"total": eng.stats["flush_total"], "periodic": 0,
                      "total_time_in_millis": 0},
            "warmer": {"current": 0, "total": 0, "total_time_in_millis": 0},
            "query_cache": {"memory_size_in_bytes": 0, "total_count": 0,
                            "hit_count": 0, "miss_count": 0,
                            "cache_size": 0, "cache_count": 0,
                            "evictions": 0},
            "fielddata": {
                # resident column bytes across this shard's fields — the
                # engine's analog of loaded fielddata (always resident here)
                "memory_size_in_bytes": sum(
                    self._field_bytes(shard, fname)
                    for fname, m in svc.mapper_service.mappers.items()
                    if not getattr(m, "completion", False)),
                "evictions": 0,
            },
            "completion": {"size_in_bytes": 0},
            "segments": {
                "count": seg["count"],
                "memory_in_bytes": 0, "terms_memory_in_bytes": 0,
                "stored_fields_memory_in_bytes": 0,
                "term_vectors_memory_in_bytes": 0,
                "norms_memory_in_bytes": 0, "points_memory_in_bytes": 0,
                "doc_values_memory_in_bytes": 0,
                "index_writer_memory_in_bytes": 0,
                "version_map_memory_in_bytes": 0,
                "fixed_bit_set_memory_in_bytes": 0,
                "max_unsafe_auto_id_timestamp": -1,
                "file_sizes": {},
            },
            "translog": tlog,
            "request_cache": {"memory_size_in_bytes": 0, "evictions": 0,
                              "hit_count": 0, "miss_count": 0},
            "recovery": {"current_as_source": 0, "current_as_target": 0,
                         "throttle_time_in_millis": 0},
        }
        if human:
            st["get"]["time"] = "0s"
            st["get"]["getTime"] = "0s"
        if file_sizes:
            st["segments"]["file_sizes"] = {
                "src": {"size_in_bytes": store_bytes,
                        "description": "source documents"},
            }
        # per-field fielddata/completion breakdowns (?fields= patterns)
        if f_pats:
            fields = {}
            for fname in sorted(svc.mapper_service.mappers):
                m = svc.mapper_service.mappers[fname]
                if getattr(m, "completion", False):
                    continue
                if any(_fn.fnmatch(fname, p) for p in f_pats):
                    b = self._field_bytes(shard, fname)
                    fields[fname] = {"memory_size_in_bytes": max(b, 1)}
            if fields:
                st["fielddata"]["fields"] = fields
                st["fielddata"]["memory_size_in_bytes"] = sum(
                    f["memory_size_in_bytes"] for f in fields.values())
        comp_total = 0
        comp_fields = {}
        for fname in self._completion_fields_of(svc):
            size = 0
            for host, _dev in shard.engine._segments:
                w = host.completion_weights.get(fname)
                if w:
                    size += sum(len(k) + 8 for k in w)
            if size == 0:
                # no explicit inputs: the FST size scales with the
                # completion column's stored values
                size = self._field_bytes(shard, fname)
            comp_total += size
            if c_pats and any(_fn.fnmatch(fname, p) for p in c_pats):
                comp_fields[fname] = {"size_in_bytes": max(size, 1)}
        st["completion"]["size_in_bytes"] = comp_total
        if comp_fields:
            st["completion"]["fields"] = comp_fields
        return st

    @staticmethod
    def _merge_stats(a: dict, b: dict) -> dict:
        out = dict(a)
        for k, v in b.items():
            cur = out.get(k)
            if isinstance(v, dict):
                out[k] = TpuNode._merge_stats(cur or {}, v)
            elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and isinstance(cur, (int, float)):
                out[k] = cur + v
            elif cur is None:
                out[k] = v
        return out

    def index_stats(self, index: str = "_all", *, metrics=None, fields=None,
                    completion_fields=None, fielddata_fields=None,
                    groups=None, level: str = "indices",
                    include_segment_file_sizes: bool = False,
                    human: bool = False) -> dict:
        """GET [/{index}]/_stats[/{metric}] (IndicesStatsAction /
        CommonStats; reference rest-api-spec indices.stats)."""
        sections = set(self._STATS_SECTIONS)
        if metrics:
            want = set()
            for m in metrics:
                m = self._METRIC_ALIASES.get(m, m)
                if m == "_all":
                    want = set(self._STATS_SECTIONS)
                    break
                if m not in self._STATS_SECTIONS:
                    import difflib

                    msg = (f"request [/_stats/{','.join(metrics)}] contains "
                           f"unrecognized metric: [{m}]")
                    close = difflib.get_close_matches(
                        m, self._STATS_SECTIONS, n=3)
                    if close:
                        msg += " -> did you mean " + (
                            f"[{close[0]}]?" if len(close) == 1
                            else f"any of {sorted(close)}?")
                    raise IllegalArgumentException(msg)
                want.add(m)
            sections = want
        f_pats = [p for p in (fields or "").split(",") if p] or \
            [p for p in (fielddata_fields or "").split(",") if p]
        c_pats = [p for p in (fields or "").split(",") if p] or \
            [p for p in (completion_fields or "").split(",") if p]
        group_list = [g for g in (groups or "").split(",") if g]

        out: dict[str, Any] = {
            "_shards": {"total": 0, "successful": 0, "failed": 0},
            "_all": {"primaries": {}, "total": {}},
            "indices": {},
        }
        all_prim: dict = {}
        for name in self.resolve_indices(index):
            svc = self._get_index(name)
            prim: dict = {}
            shards_out: dict = {}
            for sid, shard in sorted(svc.shards.items()):
                sstats = self._full_shard_stats(
                    svc, shard, f_pats=f_pats, c_pats=c_pats,
                    groups=group_list,
                    file_sizes=include_segment_file_sizes, human=human)
                sstats = {k: v for k, v in sstats.items() if k in sections}
                prim = self._merge_stats(prim, sstats)
                # total counts every targeted copy (primaries + replicas);
                # successful counts the copies that reported (primaries on
                # this single node)
                out["_shards"]["total"] += 1 + svc.num_replicas
                out["_shards"]["successful"] += 1
                if level == "shards":
                    entry = dict(sstats)
                    entry["routing"] = {
                        "state": "STARTED", "primary": True,
                        "node": self.node_name,
                    }
                    entry["commit"] = {
                        "id": shard.engine.engine_uuid,
                        "generation": shard.engine.translog.checkpoint.generation,
                        "num_docs": shard.engine.num_docs,
                        "user_data": {},
                    }
                    shards_out[str(sid)] = [entry]
            # search totals and stat-group counters are INDEX-level (the
            # per-shard merge would multiply them by shard count)
            if "search" in sections and "search" in prim:
                import fnmatch as _fn

                totals = getattr(svc, "_search_stats", {})
                prim["search"]["query_total"] = totals.get("query_total", 0)
                prim["search"]["fetch_total"] = totals.get("fetch_total", 0)
                if group_list:
                    tracked = getattr(svc, "_search_group_stats", {})
                    matched = {
                        g: dict(c) for g, c in tracked.items()
                        if any(_fn.fnmatch(g, p) for p in group_list)
                    }
                    if matched:
                        prim["search"]["groups"] = matched
            idx_entry: dict[str, Any] = {
                "uuid": getattr(svc, "uuid", name),
                "primaries": prim,
                "total": prim,
            }
            if level == "shards":
                idx_entry["shards"] = shards_out
            out["indices"][name] = idx_entry
            all_prim = self._merge_stats(all_prim, prim)
        out["_all"] = {"primaries": all_prim, "total": all_prim}
        if level == "cluster":
            out.pop("indices")
        return out

    def close(self) -> None:
        # flush-on-shutdown: buffered trace fragments decide + drain so an
        # investigation never loses the tail that was in flight
        from opensearch_tpu.telemetry.export import close_exporter

        close_exporter(self.telemetry)
        for svc in self.indices.values():
            svc.close()


def _index_setting(settings: dict, name: str):
    """Read an index-scoped setting from either flat ("index.default_pipeline")
    or nested ({"index": {"default_pipeline": ...}}) / top-level shapes."""
    v = settings.get(name)
    if v is None:
        v = settings.get(f"index.{name}")
    if v is None:
        nested = settings.get("index")
        if isinstance(nested, dict):
            v = nested.get(name)
    return v


def _deep_merge(base: dict, update: dict) -> dict:
    out = dict(base)
    for k, v in update.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out
