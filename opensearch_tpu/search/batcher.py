"""Adaptive cross-request micro-batching for device kNN dispatch.

The continuous-batching pattern of every inference-serving stack applied to
the search path: today N concurrent requests over the same device-resident
corpus pay N kernel launches, each with its fixed dispatch cost (TPU-KNN's
whole point, arxiv 2206.14286, is amortizing one large batched distance
computation across many queries; FusionANNS, arxiv 2409.16576, shows the
same coalescing for heterogeneous serving).

Mechanism: shard-level kNN dispatch sites (executor.shard_knn_selection's
exact scan and ANN search, and the distributed serving program in
search/service.py) route each query through :func:`dispatch` with a BATCH
KEY — the identity of the kernel launch they would have made: (kind,
device-column identity, reader GENERATION, k bucket, similarity, ...).
Concurrent queries with the same key coalesce into one padded batch launch;
per-query rows scatter back to the waiting requests. Because the key
carries the snapshot generation, a mid-flight refresh can never merge a
query into a batch against the wrong snapshot — the bumped generation is a
different key, a different bucket, a different launch.

Flush policy (the "adaptive" part):
 - size threshold: a bucket reaching ``max_batch_size`` flushes at once;
 - deadline: otherwise the earliest-queued entry flushes the bucket after
   ``max_wait_ms`` (timeutil clock, so sim runs stay deterministic);
 - adaptive solo fast-path: when recent flushes show no concurrency (EWMA
   of merged batch sizes at/below ~1) and no launch for the key is in
   flight, a new arrival launches immediately — sequential clients pay
   zero added latency, and the wait window re-engages as soon as merged
   batches reappear. While a launch IS in flight, arrivals queue and the
   completing leader flags the backlog for immediate flush (continuous
   batching: the next batch forms while the device is busy).

Batch sizes are padded to powers of two (pad rows are zero queries whose
results are sliced off) so the jit program cache stays warm across batch
widths — the PR 3 profiler's per-operator `retraced` flag is the
regression oracle for this.

Since the shard-mesh data plane (ISSUE 7) the batcher coalesces across
SHARDS as well as requests: the mesh kNN path's batch key spans a whole
node's shard set (service.py's distributed_knn key), so one launch serves
many concurrent queries over all resident shards at once. Callers declare
the span via ``dispatch(..., shards=S)``; `cross_shard_launches` /
`cross_shard_queries` in the stats (and the `knn.batch.shards` histogram)
show when that amortization is happening.

Since the batched ANN path (ISSUE 9) the batcher also serves IVF-PQ
launches: the executor's ANN branch dispatches with kernel kind "ivfpq"
keys carrying the INDEX-BUILD GENERATION (a rebuild can never merge into
an old batch), nprobe/k buckets, and the live ADC precision pair
(search/ann.py). ``kind="ann"`` splits the `ann_dispatches` /
`exact_dispatches` counters, and ``alt_keys`` enables CROSS-K coalescing:
a k=5 arrival rides a same-family k=8 batch already forming
(`cross_k_served`), since the bigger-k rows truncate for free.

Since the fused Pallas ADC scan (ISSUE 14) the ANN key ALSO carries the
RESOLVED KERNEL VARIANT (search/ann.resolve_kernel: "pallas" fused scan
vs "xla" monolithic lowering): a live `search.knn.ann.kernel` flip starts
new batches under the new variant, and because the key still carries the
build generation, a mid-stream ANN rebuild can never merge old-generation
queries into the new kernel variant either.

Backpressure: the pending-query queue is bounded by a
:class:`~opensearch_tpu.index.pressure.QueuePressure` budget — crossing it
sheds the request with RejectedExecutionException (HTTP 429) instead of
growing the queue (the IndexingPressure shedding contract, and the
tpulint unbounded-queue concern).

Since the tail-latency control plane (ISSUE 11) the wait window is
PER-KEY AUTO-TUNED: a :class:`_KeyTuner` per stable key family (the
``tune_key`` callers pass — the batch key minus its generation terms, so
a refresh doesn't reset what the controller learned) tracks the EWMA of
merged batch sizes, measured per-entry queue waits, and inter-arrival
gaps, and derives each arrival's effective wait from them. Solo traffic
converges to a ~0 ms window (no added latency); bursty keys earn up to
the configured ``max_wait_ms``. The request's priority LANE
(search/lanes.py contextvar) rides along: background entries accept a
longer deadline (they earn bigger merges), but because every entry keeps
its OWN deadline and a flush takes the whole bucket, an interactive
arrival's short deadline flushes any backlog of background entries it
joins — background queueing can never extend an interactive wait.

Settings (dynamic, cluster scope — see common/settings.py Setting model):
  search.knn.batch.max_wait_ms   flush deadline ceiling (default 2ms)
  search.knn.batch.max_batch_size  flush size bound  (default 32)
  search.knn.batch.max_queue     pending-query bound (default 1024)
  search.knn.batch.enabled       kill switch         (default true)
  search.knn.batch.auto_tune     per-key wait tuner  (default true)
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Sequence

from opensearch_tpu.common import timeutil
from opensearch_tpu.common.settings import Property, Setting
from opensearch_tpu.index.pressure import QueuePressure
from opensearch_tpu.telemetry import spans as span_names
from opensearch_tpu.telemetry import tracing

# -- settings (registered dynamic in cluster/cluster_settings.py) -----------

MAX_WAIT_MS_SETTING = Setting.time_setting(
    "search.knn.batch.max_wait_ms", 2,
    Property.NODE_SCOPE, Property.DYNAMIC,
)
MAX_BATCH_SIZE_SETTING = Setting.int_setting(
    "search.knn.batch.max_batch_size", 32,
    Property.NODE_SCOPE, Property.DYNAMIC, min_value=1,
)
MAX_QUEUE_SETTING = Setting.int_setting(
    "search.knn.batch.max_queue", 1024,
    Property.NODE_SCOPE, Property.DYNAMIC, min_value=0,
)
ENABLED_SETTING = Setting.bool_setting(
    "search.knn.batch.enabled", True,
    Property.NODE_SCOPE, Property.DYNAMIC,
)
AUTO_TUNE_SETTING = Setting.bool_setting(
    "search.knn.batch.auto_tune", True,
    Property.NODE_SCOPE, Property.DYNAMIC,
)

BATCH_SETTINGS = (
    MAX_WAIT_MS_SETTING, MAX_BATCH_SIZE_SETTING, MAX_QUEUE_SETTING,
    ENABLED_SETTING, AUTO_TUNE_SETTING,
)

# EWMA of merged batch sizes at/below this -> no recent concurrency ->
# skip the wait window for idle-device arrivals
_SOLO_EWMA_THRESHOLD = 1.25
_EWMA_DECAY = 0.7
# background-lane entries accept this multiple of the configured wait:
# they are throughput traffic, and a longer window earns bigger merges —
# interactive entries in the same bucket still flush it at THEIR deadline
_BACKGROUND_WAIT_FACTOR = 4
# per-key tuner table bound (LRU): tune_keys are generation-free and few,
# but a pathological workload must not grow the table without bound
_MAX_TUNERS = 256


class _KeyTuner:
    """Per-key-family wait controller. Fed (under the batcher lock) by
    every arrival and every flush; read at dispatch time to derive the
    entry's effective wait window from what this key's traffic has
    actually been doing — the measured queue-wait and arrival-rate
    distributions, not the static ceiling."""

    __slots__ = ("ewma_merged", "ewma_wait_ms", "ewma_gap_ms", "flushes",
                 "last_arrival_ms")

    def __init__(self) -> None:
        # optimistic start (matches the batcher's global EWMA): assume
        # concurrency until flushes prove otherwise, so a key's first
        # burst coalesces instead of stampeding solo
        self.ewma_merged = 2.0 * _SOLO_EWMA_THRESHOLD
        self.ewma_wait_ms = 0.0
        self.ewma_gap_ms: float | None = None
        self.flushes = 0
        self.last_arrival_ms: int | None = None

    def note_arrival(self, now_ms: int) -> None:
        if self.last_arrival_ms is not None:
            gap = max(0, now_ms - self.last_arrival_ms)
            self.ewma_gap_ms = (
                gap if self.ewma_gap_ms is None
                else _EWMA_DECAY * self.ewma_gap_ms + (1 - _EWMA_DECAY) * gap)
        self.last_arrival_ms = now_ms

    def note_flush(self, merged: int, max_wait_ms: float) -> None:
        self.ewma_merged = (_EWMA_DECAY * self.ewma_merged
                            + (1 - _EWMA_DECAY) * merged)
        self.ewma_wait_ms = (_EWMA_DECAY * self.ewma_wait_ms
                             + (1 - _EWMA_DECAY) * max_wait_ms)
        self.flushes += 1

    @property
    def solo(self) -> bool:
        return self.ewma_merged <= _SOLO_EWMA_THRESHOLD

    def effective_wait(self, ceiling_ms: int) -> int:
        """0 for solo traffic; for concurrent traffic, scale toward the
        ceiling with the observed merge factor, CAPPED at the measured
        wait the key's batches actually needed (batches that fill by size
        before the deadline never needed the whole window), and floored
        at the observed inter-arrival gap (waiting less than one gap can
        never coalesce the next arrival)."""
        if ceiling_ms <= 0 or self.solo:
            return 0
        frac = min(1.0, self.ewma_merged - 1.0)
        wait = max(1, round(ceiling_ms * frac))
        if self.flushes >= 4:
            # enough history: the window need not exceed what the
            # measured per-entry waits show this key's merges cost
            wait = min(wait, max(1, round(self.ewma_wait_ms) + 1))
        if self.ewma_gap_ms is not None and self.ewma_gap_ms < ceiling_ms:
            wait = max(wait, min(ceiling_ms, int(self.ewma_gap_ms) + 1))
        return min(wait, ceiling_ms)

    def snapshot(self) -> dict:
        return {
            "ewma_merged": round(self.ewma_merged, 3),
            "ewma_wait_ms": round(self.ewma_wait_ms, 3),
            "ewma_gap_ms": (round(self.ewma_gap_ms, 3)
                            if self.ewma_gap_ms is not None else None),
            "flushes": self.flushes,
        }


class _Entry:
    __slots__ = ("payload", "enq_ns", "taken_ns", "taken", "done", "result",
                 "error", "batch_size", "wall_ns", "retraced", "launch",
                 "rank", "tune_key", "leader_span")

    def __init__(self, payload: Any, enq_ns: int, launch=None, rank: int = 0,
                 tune_key: Any = None):
        self.payload = payload
        # the queue wait is measured by ONE pair of stamps
        # (`time.perf_counter_ns`): enqueue, here, and take, by the leader
        # in `_take_locked`. `wait_ms`, the `knn.batch.queue_wait_ms`
        # histogram, the tuner's measured waits and the `batch.wait` span's
        # `queue_wait_ns` all derive from it
        self.enq_ns = enq_ns
        self.taken_ns = enq_ns
        self.taken = False
        self.done = False
        self.result: Any = None
        self.error: BaseException | None = None
        self.batch_size = 1
        self.wall_ns = 0
        self.retraced = False
        # the entry's own launch closure + its k-bucket rank: a batch is
        # always launched by the closure of its LARGEST-rank member, so a
        # smaller-k joiner (cross-k coalescing) can ride a bigger-k launch
        # but can never shrink one
        self.launch = launch
        self.rank = rank
        # generation-free key family feeding the per-key wait auto-tuner
        self.tune_key = tune_key
        # the `launch` span of the leader that took the entry, which a
        # follower's `batch.wait` span names as its cause
        self.leader_span: str | None = None

    @property
    def wait_ms(self) -> float:
        """Milliseconds queued, enqueue to take, in ns resolution."""
        return (self.taken_ns - self.enq_ns) / 1e6


class _Bucket:
    __slots__ = ("entries", "flush_now")

    def __init__(self) -> None:
        self.entries: list[_Entry] = []
        # set by a completing leader: the backlog that queued while the
        # device was busy flushes at once instead of waiting out a deadline
        self.flush_now = False


class DispatchOutcome:
    """What one query learns about the launch that served it."""

    __slots__ = ("value", "merged", "wall_ns", "retraced", "wait_ms")

    def __init__(self, value: Any, merged: int, wall_ns: int,
                 retraced: bool, wait_ms: float):
        self.value = value
        self.merged = merged          # live queries in the batch
        self.wall_ns = wall_ns        # fenced wall of the whole launch
        self.retraced = retraced
        self.wait_ms = wait_ms        # time this query spent queued

    @property
    def kernel_share_ns(self) -> int:
        """This query's share of the fenced kernel time (profiler entry)."""
        return self.wall_ns // max(self.merged, 1)


class KnnDispatchBatcher:
    """Per-node scheduler coalescing concurrent same-key kNN dispatches."""

    def __init__(self, *, max_batch_size: int | None = None,
                 max_wait_ms: int | None = None,
                 max_queue: int | None = None,
                 enabled: bool | None = None,
                 auto_tune: bool | None = None,
                 metrics=None):
        from opensearch_tpu.common.settings import Settings

        self.max_batch_size = (max_batch_size if max_batch_size is not None
                               else MAX_BATCH_SIZE_SETTING.default(Settings.EMPTY))
        self.max_wait_ms = (max_wait_ms if max_wait_ms is not None
                            else MAX_WAIT_MS_SETTING.default(Settings.EMPTY))
        self.enabled = (enabled if enabled is not None
                        else ENABLED_SETTING.default(Settings.EMPTY))
        self.auto_tune = (auto_tune if auto_tune is not None
                          else AUTO_TUNE_SETTING.default(Settings.EMPTY))
        limit = (max_queue if max_queue is not None
                 else MAX_QUEUE_SETTING.default(Settings.EMPTY))
        self.pressure = QueuePressure(limit, operation="knn batch dispatch")
        self.metrics = metrics       # optional telemetry MetricsRegistry
        self._cond = threading.Condition()
        self._buckets: dict[Any, _Bucket] = {}
        self._in_flight: dict[Any, int] = {}
        # per-key-family wait controllers (LRU-bounded, guarded by _cond)
        self._tuners: dict[Any, _KeyTuner] = {}
        # optimistic start (above the solo threshold): a fresh node assumes
        # concurrency until flushes prove otherwise, so the very first burst
        # coalesces instead of stampeding solo
        self._ewma = 2.0 * _SOLO_EWMA_THRESHOLD
        self.stats = {
            "dispatches": 0,        # device launches
            "merged_queries": 0,    # queries served by those launches
            "coalesced_batches": 0,  # launches with more than one query
            "max_batch": 0,
            "solo_fast_path": 0,    # adaptive immediate launches
            "rejections": 0,        # queue-bound sheds (429)
            # launches whose key spans a whole shard MESH (shards > 1):
            # one device program served every shard of the node at once,
            # so the batcher amortized across shards AND requests
            "cross_shard_launches": 0,
            "cross_shard_queries": 0,
            # ANN (IVF-PQ) vs exact-scan launch split, and queries served
            # from a LARGER k-bucket's pending batch (cross-k coalescing:
            # a k=5 arrival rides an in-formation k=8 batch of the same
            # family, truncation is free — extra rows never win the cut)
            "ann_dispatches": 0,
            "exact_dispatches": 0,
            "cross_k_served": 0,
        }

    # -- config ------------------------------------------------------------

    def configure(self, *, max_batch_size: int | None = None,
                  max_wait_ms: int | None = None,
                  max_queue: int | None = None,
                  enabled: bool | None = None,
                  auto_tune: bool | None = None) -> None:
        # config fields are plain atomic assignments read racily by design:
        # a dispatch that reads the old value completes under the old
        # policy, which is exactly the dynamic-settings contract
        if max_batch_size is not None:
            self.max_batch_size = max(1, int(max_batch_size))
        if max_wait_ms is not None:
            self.max_wait_ms = int(max_wait_ms)
        if enabled is not None:
            self.enabled = bool(enabled)
        if auto_tune is not None:
            self.auto_tune = bool(auto_tune)
        if max_queue is not None:
            self.pressure.set_limit(max_queue)
        with self._cond:
            self._cond.notify_all()

    def apply_settings(self, flat: dict) -> None:
        """Pick this batcher's keys out of a flat effective-settings map
        (the cluster-settings update consumer)."""
        from opensearch_tpu.common.settings import Settings

        s = Settings.from_flat({
            st.key: flat[st.key] for st in BATCH_SETTINGS if st.key in flat
        })
        self.configure(
            max_wait_ms=MAX_WAIT_MS_SETTING.get(s),
            max_batch_size=MAX_BATCH_SIZE_SETTING.get(s),
            max_queue=MAX_QUEUE_SETTING.get(s),
            enabled=ENABLED_SETTING.get(s),
            auto_tune=AUTO_TUNE_SETTING.get(s),
        )

    # tuner entries surfaced in stats (the table itself is bounded at
    # _MAX_TUNERS; the stats payload shows the busiest few)
    _STATS_TUNER_ROWS = 16

    def snapshot_stats(self) -> dict:
        with self._cond:
            out = dict(self.stats)
            out["mean_merged_batch"] = (
                out["merged_queries"] / out["dispatches"]
                if out["dispatches"] else 0.0
            )
            out["ewma_batch"] = round(self._ewma, 3)
            busiest = sorted(self._tuners.items(),
                             key=lambda kv: -kv[1].flushes)
            out["auto_tune"] = {
                "enabled": self.auto_tune,
                "tuned_keys": len(self._tuners),
                "keys": {
                    str(tk): {
                        **tuner.snapshot(),
                        "effective_wait_ms": tuner.effective_wait(
                            self.max_wait_ms),
                    }
                    for tk, tuner in busiest[: self._STATS_TUNER_ROWS]
                },
            }
        out["queue"] = self.pressure.stats()
        out["rejections"] = out["queue"]["rejections"]
        out["enabled"] = self.enabled
        out["max_batch_size"] = self.max_batch_size
        out["max_wait_ms"] = self.max_wait_ms
        # live ANN serving knobs + index-build accounting ride the same
        # stats section (one `knn_batch` surface for the whole kNN
        # dispatch tier, single-node and cluster alike)
        from opensearch_tpu.search import ann as ann_mod

        out["ann"] = ann_mod.default_config.snapshot()
        return out

    def reset(self) -> None:
        """Test hook: forget adaptive state and counters (never pending
        entries — callers must be idle, so no lock discipline applies)."""
        for k in self.stats:
            self.stats[k] = 0
        self._ewma = 2.0 * _SOLO_EWMA_THRESHOLD
        self._tuners.clear()
        self.pressure.rejections = 0
        self.pressure.total = 0

    # -- dispatch ----------------------------------------------------------

    def dispatch(self, key: Any, payload: Any,
                 launch: Callable[[Sequence[Any]],
                                  tuple[list, bool]],
                 shards: int = 1, *, kind: str = "exact",
                 rank: int = 0,
                 alt_keys: Sequence[Any] = (),
                 family: str | None = None,
                 tune_key: Any = None) -> DispatchOutcome:
        """Run `payload` through the batch identified by `key`.

        `launch(payloads)` performs ONE device launch for the whole batch
        (padding the width as it sees fit) and returns
        (per-payload results, retraced flag). Every payload sharing a key
        MUST be servable by any member's launch closure — the key is the
        caller's promise that the kernel and its device-resident arguments
        are identical. key=None means "not mergeable" (e.g. a filtered
        query whose valid mask is request-private): the launch runs solo,
        still counted in the stats.

        `shards` declares how many shards the launch covers (the
        shard-mesh path passes its mesh width): cross-shard launches are
        tracked separately so the stats show when one launch amortized
        across the whole node instead of one shard.

        `kind` ("exact" | "ann") splits the dispatch counters so the
        stats/Prometheus surface shows which scan family launches serve.

        `alt_keys` (cross-k coalescing) are LARGER-k-bucket variants of
        `key`, nearest first, that this request may ride: if one already
        has a batch forming, the entry joins it instead of opening its own
        bucket — the bigger-k result is a superset, the caller's top-k cut
        truncates for free. `rank` orders the k-buckets: a batch launches
        with its largest-rank member's closure, so joiners can never
        shrink the launch the natives asked for.

        `family` names the kernel family for the device-residency ledger's
        retrace/compile accounting: a launch whose retraced flag fires
        counts one jit-cache entry (plus its first-launch wall) there.

        `tune_key` names the entry's GENERATION-FREE key family for the
        per-key wait auto-tuner (defaults to `key` itself): the controller
        derives this arrival's effective wait window from the family's
        measured merge factor / queue waits / arrival gaps instead of the
        static `max_wait_ms` ceiling. The active priority lane
        (search/lanes.py) widens the window for background entries.
        """
        if key is None or not self.enabled or self.max_batch_size <= 1:
            return self._solo(payload, launch, shards, kind, family)
        from opensearch_tpu.search import lanes as lanes_mod

        # the lanes kill switch governs the batcher's wait-widening too:
        # control-plane-off must be exactly the pre-lane behavior (and the
        # bench's OFF baseline must not keep one lever engaged)
        background = (lanes_mod.default_config.enabled
                      and lanes_mod.active_lane() == lanes_mod.BACKGROUND)
        if tune_key is None:
            tune_key = key
        with tracing.detail(span_names.BATCH_WAIT) as waited:
            with self._cond:
                self.pressure.acquire()
                entry = _Entry(payload, time.perf_counter_ns(),
                               launch=launch, rank=rank, tune_key=tune_key)
                # arrivals and the flush deadline stay on the timeutil
                # clock, which a sim may have made virtual
                now_ms = timeutil.monotonic_millis()
                tuner = None
                if self.auto_tune:
                    tuner = self._tuner_locked(tune_key)
                    tuner.note_arrival(now_ms)
                    eff_wait = tuner.effective_wait(self.max_wait_ms)
                else:
                    eff_wait = self.max_wait_ms
                if background:
                    # background traffic accepts a longer window (it earns
                    # bigger merges); never BELOW the configured ceiling so a
                    # tuned-down interactive window doesn't shrink it
                    eff_wait = max(self.max_wait_ms, eff_wait) \
                        * _BACKGROUND_WAIT_FACTOR
                deadline = now_ms + max(eff_wait, 0)
                for alt in alt_keys:
                    alt_bucket = self._buckets.get(alt)
                    if (alt_bucket is not None and alt_bucket.entries
                            and len(alt_bucket.entries) < self.max_batch_size):
                        # ride the bigger-k batch already forming; never CREATE
                        # a bigger-k bucket just for a smaller-k request
                        key = alt
                        self.stats["cross_k_served"] += 1
                        break
                bucket = self._buckets.get(key)
                if bucket is None:
                    bucket = self._buckets[key] = _Bucket()
                bucket.entries.append(entry)
                # the per-key controller's solo verdict wins when auto-tuning;
                # the global EWMA stays the fallback signal
                solo_now = (tuner.solo if tuner is not None
                            else self._ewma <= _SOLO_EWMA_THRESHOLD)
                if len(bucket.entries) >= self.max_batch_size:
                    batch, reason = self._take_locked(key), "size"
                elif self.max_wait_ms <= 0 or (
                    self._in_flight.get(key, 0) == 0 and solo_now
                ):
                    if len(bucket.entries) == 1:
                        self.stats["solo_fast_path"] += 1
                    batch, reason = self._take_locked(key), "solo"
                else:
                    batch, reason = None, ""
            if batch is None:
                led = self._await_or_lead(key, entry, deadline)
                if led is not None:
                    batch, reason = led
            _describe_wait(waited, entry, reason)
        while True:
            if batch is None:
                # another leader served us
                if entry.error is not None:
                    raise entry.error
                return DispatchOutcome(
                    entry.result, entry.batch_size, entry.wall_ns,
                    entry.retraced, entry.wait_ms,
                )
            out = self._run_batch(key, batch, own=entry,
                                  shards=shards, kind=kind,
                                  family=family, reason=reason)
            if out is not None:
                return out
            # we led a batch that did not include our own entry (the
            # size bound shrank under us): keep waiting for ours
            with tracing.detail(span_names.BATCH_WAIT) as waited:
                led = self._await_or_lead(key, entry, deadline)
                batch, reason = led if led is not None else (None, "")
                _describe_wait(waited, entry, reason)

    # -- internals ---------------------------------------------------------

    def _solo(self, payload: Any, launch, shards: int = 1,
              kind: str = "exact",
              family: str | None = None) -> DispatchOutcome:
        t0 = time.perf_counter_ns()
        with tracing.detail(span_names.LAUNCH) as span:
            span.set_attribute("merged", 1)
            span.set_attribute("reason", "unbatched")
            results, retraced = launch([payload])
        wall = time.perf_counter_ns() - t0
        self._record_launch(1, wall, (0,), shards, kind)
        self._after_launch(kind, family, retraced, wall, merged=1,
                           reason="unbatched")
        return DispatchOutcome(results[0], 1, wall, retraced, 0)

    def _tuner_locked(self, tune_key: Any) -> _KeyTuner:
        """The key family's controller (caller holds the lock); LRU touch
        + bound so generations of abandoned families age out."""
        tuner = self._tuners.pop(tune_key, None)
        if tuner is None:
            tuner = _KeyTuner()
        self._tuners[tune_key] = tuner
        while len(self._tuners) > _MAX_TUNERS:
            self._tuners.pop(next(iter(self._tuners)))
        return tuner

    def _after_launch(self, kind: str, family: str | None, retraced: bool,
                      wall_ns: int, merged: int, reason: str) -> None:
        """Post-launch observability: the flush reason rides the leader's
        span as an event, and a retraced launch counts one jit-cache entry
        (first-launch wall = compile + run) in the residency ledger's
        per-kernel-family compile table. Only NOTEWORTHY flushes emit an
        event — a coalesced batch or a wait-policy decision (size/
        deadline/backlog); the steady solo fast path stays event-free so
        the per-span export payload (the ≤5% otel-overhead gate) doesn't
        grow with every launch."""
        from opensearch_tpu.telemetry.device_ledger import default_ledger

        if merged > 1 or reason in ("size", "deadline", "backlog"):
            from opensearch_tpu.telemetry.tracing import add_span_event

            add_span_event("knn.batch.flush", {
                "reason": reason, "merged": merged, "kind": kind,
            })
        # launch closures that account their own compiles (the mesh path)
        # pass no family — recording here too would double-count the entry
        if retraced and family is not None:
            default_ledger.record_compile(family, wall_ns)

    def _take_locked(self, key: Any) -> list[_Entry]:
        """Detach the key's pending entries (<= max_batch_size of them) as
        one batch; caller holds the lock and becomes the leader."""
        bucket = self._buckets.get(key)
        assert bucket is not None and bucket.entries
        batch = bucket.entries[: self.max_batch_size]
        rest = bucket.entries[self.max_batch_size:]
        if rest:
            bucket.entries = rest
        else:
            del self._buckets[key]
        now_ns = time.perf_counter_ns()
        for e in batch:
            e.taken = True
            e.taken_ns = now_ns
        self.pressure.release(len(batch))
        self._in_flight[key] = self._in_flight.get(key, 0) + 1
        return batch

    def _await_or_lead(self, key: Any, entry: _Entry,
                       deadline: int) -> tuple[list[_Entry], str] | None:
        """Wait until the entry is served, or its bucket qualifies for a
        flush it can lead. Returns (batch, flush reason) to lead, or None
        if done."""
        with self._cond:
            while True:
                if entry.done:
                    return None
                if entry.taken:
                    # a leader is running our batch; the 100ms timeout is a
                    # liveness backstop, completion notifies immediately
                    self._cond.wait(0.1)
                    continue
                bucket = self._buckets.get(key)
                now = timeutil.monotonic_millis()
                if bucket is not None and (
                        len(bucket.entries) >= self.max_batch_size
                        or bucket.flush_now):
                    reason = ("size"
                              if len(bucket.entries) >= self.max_batch_size
                              else "backlog")
                    return self._take_locked(key), reason
                if now >= deadline:
                    return self._take_locked(key), "deadline"
                remaining = max((deadline - now) / 1000.0, 0.0)
                signaled = self._cond.wait(remaining)
                if not signaled and timeutil.monotonic_millis() <= now:
                    # the injected clock is virtual/frozen: real time
                    # elapsed without virtual progress, so the deadline can
                    # never arrive by waiting — flush now (keeps
                    # deterministic-sim runs from hanging on wall time)
                    deadline = now

    def _run_batch(self, key: Any, batch: list[_Entry],
                   own: _Entry, shards: int = 1,
                   kind: str = "exact", family: str | None = None,
                   reason: str = "") -> DispatchOutcome | None:
        """Launch one batch; returns the outcome for `own`, or None when
        `own` was not part of this batch (its caller keeps waiting)."""
        # cross-k coalescing: the batch launches with its LARGEST-rank
        # member's closure — every smaller-k joiner's result is a prefix
        # truncation of that launch's rows
        launch = max(batch, key=lambda e: e.rank).launch
        t0 = time.perf_counter_ns()
        try:
            # the launch belongs to the leader's trace; every follower
            # names it as the cause of its wait
            with tracing.detail(span_names.LAUNCH) as span:
                span.set_attribute("merged", len(batch))
                span.set_attribute("reason", reason or "lead")
                for e in batch:
                    e.leader_span = span.span_id
                results, retraced = launch([e.payload for e in batch])
        except BaseException as err:
            with self._cond:
                for e in batch:
                    e.error = err
                    e.done = True
                self._finish_locked(key, batch)
            raise
        wall = time.perf_counter_ns() - t0
        with self._cond:
            for e, r in zip(batch, results):
                e.result = r
                e.batch_size = len(batch)
                e.wall_ns = wall
                e.retraced = retraced
                e.done = True
            self._finish_locked(key, batch)
        self._record_launch(len(batch), wall,
                            tuple(e.wait_ms for e in batch),
                            shards, kind)
        self._after_launch(kind, family, retraced, wall,
                           merged=len(batch), reason=reason or "lead")
        if not any(e is own for e in batch):
            return None
        return DispatchOutcome(own.result, len(batch), wall, retraced,
                               own.wait_ms)

    def _finish_locked(self, key: Any, batch: list[_Entry]) -> None:
        merged = len(batch)
        n = self._in_flight.get(key, 0) - 1
        if n > 0:
            self._in_flight[key] = n
        else:
            self._in_flight.pop(key, None)
        self._ewma = _EWMA_DECAY * self._ewma + (1 - _EWMA_DECAY) * merged
        if self.auto_tune:
            # every key family represented in the batch (cross-k joiners
            # carry their own tune_key) learns this flush's merge factor
            # and its members' MEASURED waits
            by_family: dict[Any, int] = {}
            for e in batch:
                if e.tune_key is not None:
                    by_family[e.tune_key] = max(
                        by_family.get(e.tune_key, 0), e.wait_ms)
            for tk, max_wait in by_family.items():
                self._tuner_locked(tk).note_flush(merged, max_wait)
        bucket = self._buckets.get(key)
        if bucket is not None and bucket.entries:
            # continuous batching: the backlog that formed while this
            # launch ran flushes immediately, led by one of its waiters
            bucket.flush_now = True
        self._cond.notify_all()

    def _record_launch(self, merged: int, wall_ns: int,
                       wait_ms_per_entry: Sequence[float], shards: int = 1,
                       kind: str = "exact") -> None:
        with self._cond:
            self.stats["dispatches"] += 1
            self.stats["merged_queries"] += merged
            if merged > 1:
                self.stats["coalesced_batches"] += 1
            self.stats["max_batch"] = max(self.stats["max_batch"], merged)
            if shards > 1:
                self.stats["cross_shard_launches"] += 1
                self.stats["cross_shard_queries"] += merged
            if kind == "ann":
                self.stats["ann_dispatches"] += 1
            else:
                self.stats["exact_dispatches"] += 1
        # record into the EXECUTING node's registry when a request scope is
        # active (multi-node sims share this process-wide batcher; the
        # exemplar trace_id must resolve in the recording node's ring),
        # else the attached sink
        from opensearch_tpu.telemetry.tracing import active_metrics

        metrics = active_metrics() or self.metrics
        if metrics is not None:
            metrics.histogram("knn.batch.size").record(merged)
            # one observation PER ENTRY with its MEASURED queue wait (the
            # auto-tuner and its operators need the real distribution, not
            # one per-batch point — and never the configured ceiling)
            for w in wait_ms_per_entry:
                metrics.histogram("knn.batch.queue_wait_ms").record(w)
            metrics.histogram("knn.batch.shards").record(shards)
            metrics.counter("knn.batch.dispatches").add(1)
            if kind == "ann":
                metrics.counter("knn.dispatch.ann").add(1)
            else:
                metrics.counter("knn.dispatch.exact").add(1)


def _describe_wait(waited, entry: _Entry, reason: str) -> None:
    """The `batch.wait` span's attributes: `reason` is the flush this
    waiter leads, or "follower" when another leader's launch served it
    (`leader` then names that launch's span)."""
    if waited.detail is None:
        return
    attributes = {"queue_wait_ns": entry.taken_ns - entry.enq_ns,
                  "reason": reason or "follower"}
    if not reason:
        attributes["merged"] = entry.batch_size
        attributes["leader"] = entry.leader_span
    waited.attributes.update(attributes)


# process-wide default: the executor's dispatch sites are module-level code
# with no node handle (same pattern as executor.knn_path_stats); a TpuNode
# adopts it at construction (stats + settings + metrics wiring). One
# process == one device, so per-process batching is the semantically right
# scope even when several sim nodes share the interpreter.
default_batcher = KnnDispatchBatcher()


def dispatch(key: Any, payload: Any, launch, shards: int = 1, *,
             kind: str = "exact", rank: int = 0,
             alt_keys: Sequence[Any] = (),
             family: str | None = None,
             tune_key: Any = None) -> DispatchOutcome:
    return default_batcher.dispatch(key, payload, launch, shards=shards,
                                    kind=kind, rank=rank, alt_keys=alt_keys,
                                    family=family, tune_key=tune_key)
