"""Telemetry: tracing spans + metrics registry.

The analog of the reference's vendor-neutral telemetry SPI (SURVEY.md §5
"Tracing / profiling": libs/telemetry Telemetry.java / tracing/Tracer /
metrics/MetricsRegistry, wired by server TelemetryModule; context
propagation rides ThreadContext). Here:

- Tracer.start_span is a context manager; the current span propagates via
  contextvars (the asyncio-native ThreadContext), so child spans parent
  automatically across the executor boundaries the HTTP server uses.
- Spans collect into a bounded in-memory ring (exporter SPI slot) — the
  OTel plugin equivalent would ship them out; tests and the _nodes/stats
  surface read the ring.
- MetricsRegistry: counters + histograms with label support.
- Cross-NODE propagation (PR 3): `current_trace_context()` serializes the
  active (trace_id, span_id) pair into transport message headers and
  `restore_trace_context()` re-installs it on the receiving node, so a
  distributed search or recovery stitches into ONE trace tree across
  processes (the reference's ThreadContext header relay through
  TaskTransportChannel). Span ids come from a per-tracer counter prefixed
  with the tracer name — deterministic under the sim (no uuid/urandom,
  tpulint TPU006) yet unique across the nodes of one simulated cluster.
- Request DETAIL (PR 26): a root span that opens while a `jax.profiler`
  session runs marks its whole request as detailed (`Span.detail`, inherited
  by every child through the same contextvar). A detailed request also
  opens the detail spans of telemetry/spans.py (`detail()` / `phases()`),
  and every one of its spans is written twice: as a
  `jax.profiler.TraceAnnotation`, which puts it on the clock of the device's
  `XLA Ops` lines, and as a compact record in the tracer's `Capture`, which
  is written to `<path.data>/telemetry/capture-<n>.json` when the session
  has ended and the requests that opened under it have finished (the
  capture drains for CAPTURE_DRAIN_S at most, and details the requests
  that open meanwhile, so that a request of seconds is held whole). No
  setting turns this on: the profiler session is the switch.
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import json
import logging
import os
import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field as dc_field
from typing import Any

from opensearch_tpu.telemetry import spans as span_names

logger = logging.getLogger(__name__)

_current_span: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "opensearch_tpu_current_span", default=None
)
_active_tracer: contextvars.ContextVar["Tracer | None"] = contextvars.ContextVar(
    "opensearch_tpu_active_tracer", default=None
)


# per-span event cap (OTel's default span event limit ballpark): a span
# that witnesses hundreds of evictions/retries keeps the first window and
# counts the rest, so one hot span can never balloon the ring or export
MAX_SPAN_EVENTS = 32


@dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: str | None
    name: str
    attributes: dict[str, Any] = dc_field(default_factory=dict)
    start_ns: int = 0
    end_ns: int = 0
    # span EVENTS (per-span logs): point-in-time records riding the span —
    # batcher flush reasons, mesh/ledger evictions, recovery chunk retries.
    # Bounded by MAX_SPAN_EVENTS; overflow counts into dropped_events.
    events: list[dict] = dc_field(default_factory=list)
    dropped_events: int = 0
    # the Capture of the profiler session this span's request opened under
    # (decided once, at the root; children inherit it), else None
    detail: "Capture | None" = None

    @property
    def duration_ns(self) -> int:
        return max(self.end_ns - self.start_ns, 0)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def add_event(self, name: str, attributes: dict | None = None) -> None:
        if len(self.events) >= MAX_SPAN_EVENTS:
            self.dropped_events += 1
            return
        self.events.append({
            "name": name,
            "ts_ns": time.perf_counter_ns(),
            "attributes": dict(attributes or {}),
        })

    def to_dict(self) -> dict:
        out = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "attributes": dict(self.attributes),
            "start_ns": self.start_ns,
            "duration_ns": self.duration_ns,
        }
        if self.events:
            out["events"] = [dict(e) for e in self.events]
        if self.dropped_events:
            out["dropped_events"] = self.dropped_events
        return out


class _SpanScope:
    __slots__ = ("_tracer", "_name", "_attributes", "span", "_token",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attributes: dict | None):
        self._tracer = tracer
        self._name = name
        self._attributes = attributes

    def __enter__(self) -> Span:
        self.span = self._tracer.begin_span(self._name, self._attributes)
        self._annotation = (_annotate(self.span)
                            if self.span.detail is not None else None)
        self._token = _current_span.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.span.attributes["error"] = str(exc)
        _current_span.reset(self._token)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        self._tracer.end_span(self.span)
        return False


# -- request detail: on exactly while a jax.profiler session runs -----------

# records a capture holds before it counts `dropped` instead (never blocks)
CAPTURE_MAX_RECORDS = 1 << 18
CAPTURE_KEEP_FILES = 4
# a capture stays open after its session ended until the requests that
# opened under the session have finished (a filtered kNN search under 32
# clients takes seconds, not milliseconds), but no longer than this
CAPTURE_DRAIN_S = 30.0
# and is written this long after it closed, so that the spans that follow
# their root's close (`http.respond`) finish into it
CAPTURE_GRACE_S = 0.5
_CAPTURE_WRITE_CHUNK = 512
_CAPTURE_FILE = re.compile(r"capture-(\d+)\.json")
CAPTURE_FIELDS = ("name", "trace_id", "span_id", "parent_id", "thread",
                  "start_ns", "end_ns", "attributes")

_trace_annotation: Any = None  # jax.profiler.TraceAnnotation, False if absent


def _trace_annotation_type():
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except ImportError:
            _trace_annotation = False
    return _trace_annotation


def profiler_session_on() -> bool:
    """True between `jax.profiler.start_trace` and `stop_trace` (~25 ns)."""
    annotation = _trace_annotation_type()
    return bool(annotation) and annotation.is_enabled()


def clock_pair() -> tuple[int, int]:
    """(perf_counter_ns, time_ns) read together: the anchor that puts the
    tracer's monotonic stamps on the Unix clock (export.py, the capture)."""
    return time.perf_counter_ns(), time.time_ns()


def _annotate(span: Span):
    """Open the span's twin in the profiler's own trace, on this thread."""
    annotation = _trace_annotation_type()(
        span.name, trace_id=span.trace_id, span_id=span.span_id,
        parent_id=span.parent_id or "")
    annotation.__enter__()
    return annotation


class Capture:
    """Every span of the requests that opened under one profiler session,
    as compact records (`CAPTURE_FIELDS`), with the clock pairs and counter
    snapshots taken at its open and close."""

    def __init__(self, tracer: "Tracer"):
        self.tracer = tracer
        self.records: list[tuple] = []
        self.dropped = 0
        self.opened = clock_pair()
        self.closed: tuple[int, int] | None = None
        # span ids of the roots that opened under the session and have not
        # ended: while there are any, the capture outlives its session
        # (`draining`) and goes on detailing the requests that open, so
        # that what it holds of that stretch is whole
        self.session_roots: set[str] = set()
        self._session_over: float | None = None
        self.counters_open = tracer.read_capture_counters()
        self.counters_close: dict | None = None
        # taken only past the cap; re-entrant, since a collection can start
        # inside `add` and its `runtime.gc` record is added from the same
        # thread
        self._dropped_lock = threading.RLock()

    def add(self, name: str, trace_id: str | None, span_id: str | None,
            parent_id: str | None, start_ns: int, end_ns: int,
            attributes: dict | None) -> None:
        # no lock on the way in: `list.append` is atomic, and threads that
        # race past the check overshoot the cap by one record each at most
        if len(self.records) >= CAPTURE_MAX_RECORDS:
            with self._dropped_lock:
                self.dropped += 1
            return
        self.records.append((
            name, trace_id, span_id, parent_id, threading.get_ident(),
            start_ns, end_ns, attributes or None))

    def add_span(self, span: Span) -> None:
        self.add(span.name, span.trace_id, span.span_id, span.parent_id,
                 span.start_ns, span.end_ns, span.attributes)

    def draining(self) -> bool:
        """The session is over (the caller saw that) and a request that
        opened under it is still in flight, for CAPTURE_DRAIN_S at most."""
        if self._session_over is None:
            self._session_over = time.monotonic()
        return (bool(self.session_roots)
                and time.monotonic() - self._session_over < CAPTURE_DRAIN_S)


class _NullSpan:
    """What a detail site gets when its request is not detailed."""

    __slots__ = ()
    span_id = None
    detail = None
    start_ns = 0

    def set_attribute(self, key: str, value: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _NoDetail:
    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return _NULL_SPAN

    def __exit__(self, exc_type, exc, tb):
        return False

    def enter(self, name: str) -> _NullSpan:
        return _NULL_SPAN

    def close(self) -> None:
        pass


_NO_DETAIL = _NoDetail()
NO_DETAIL = _NO_DETAIL  # what `detail()` returns outside a detailed request


class _DetailScope:
    """One detail span: a Span while it is open (so children, events and
    exemplars see it through the contextvar like any other), an annotation
    in the profiler's trace, and a record in the capture when it closes.
    It never reaches the ring or the exporter."""

    __slots__ = ("_name", "_parent", "span", "_token", "_annotation")

    def __init__(self, name: str, parent: Span):
        self._name = name
        self._parent = parent

    def __enter__(self) -> Span:
        parent = self._parent
        self.span = Span(
            trace_id=parent.trace_id,
            span_id=parent.detail.tracer.next_span_id(),
            parent_id=parent.span_id,
            name=self._name,
            start_ns=time.perf_counter_ns(),
            detail=parent.detail,
        )
        self._annotation = _annotate(self.span)
        self._token = _current_span.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        span = self.span
        _current_span.reset(self._token)
        self._annotation.__exit__(None, None, None)
        span.end_ns = time.perf_counter_ns()
        span.detail.add_span(span)
        return False


class _Phases:
    """Consecutive sibling detail spans under one parent: `enter(name)`
    closes the phase that is open and opens the next, `close()` ends the
    last. For a long function whose phases follow one another (the search
    service), where a `with` block per phase would re-indent all of it."""

    __slots__ = ("_parent", "_scope")

    def __init__(self, parent: Span):
        self._parent = parent
        self._scope: _DetailScope | None = None

    def enter(self, name: str) -> Span:
        self.close()
        self._scope = _DetailScope(name, self._parent)
        return self._scope.__enter__()

    def close(self) -> None:
        if self._scope is not None:
            self._scope.__exit__(None, None, None)
            self._scope = None


def detail(name: str, parent: Span | None = None):
    """Context manager for one detail span under the current span (or under
    `parent`, for work that follows its parent's close, as the response
    write follows `http_request`). Outside a detailed request — no profiler
    session when its root opened — this is one contextvar read and an
    attribute read, and yields a span whose `set_attribute` does nothing."""
    if parent is None:
        parent = _current_span.get()
    if parent is None or parent.detail is None:
        return _NO_DETAIL
    return _DetailScope(name, parent)


def phases():
    """A `_Phases` under the current span; the same no-op when not detailed.
    The caller closes it in a `finally`."""
    parent = _current_span.get()
    if parent is None or parent.detail is None:
        return _NO_DETAIL
    return _Phases(parent)


class _RemoteContextScope:
    """Installs a synthetic parent span restored from transport headers so
    spans opened on the receiving node stitch into the sender's trace."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: dict | None):
        self._ctx = ctx if (
            isinstance(ctx, dict) and ctx.get("trace_id") and ctx.get("span_id")
        ) else None

    def __enter__(self):
        if self._ctx is None:
            self._token = None
            return None
        remote = Span(
            trace_id=str(self._ctx["trace_id"]),
            span_id=str(self._ctx["span_id"]),
            parent_id=None,
            name="<remote>",
        )
        self._token = _current_span.set(remote)
        return remote

    def __exit__(self, exc_type, exc, tb):
        if self._token is not None:
            _current_span.reset(self._token)
        return False


def current_trace_context() -> dict | None:
    """The active (trace_id, span_id) pair as a wire-ready header dict, or
    None when no span is open (messages outside any trace stay bare)."""
    span = _current_span.get()
    if span is None:
        return None
    return {"trace_id": span.trace_id, "span_id": span.span_id}


def restore_trace_context(ctx: dict | None) -> _RemoteContextScope:
    """Context manager re-installing a propagated trace context (receiving
    node side, or re-entering a stored context across scheduler callbacks).
    A None/malformed ctx yields a no-op scope."""
    return _RemoteContextScope(ctx)


class _ActivateScope:
    __slots__ = ("_tracer", "_token")

    def __init__(self, tracer: "Tracer"):
        self._tracer = tracer

    def __enter__(self) -> "Tracer":
        self._token = _active_tracer.set(self._tracer)
        return self._tracer

    def __exit__(self, exc_type, exc, tb):
        _active_tracer.reset(self._token)
        return False


def activate(tracer: "Tracer") -> _ActivateScope:
    """Scope the 'active tracer' (the node handling the current request) so
    library code (search phases, recovery) can open spans into the right
    node's ring without threading a tracer through every signature."""
    return _ActivateScope(tracer)


def active_tracer() -> "Tracer":
    return _active_tracer.get() or default_telemetry.tracer


def active_metrics() -> "MetricsRegistry | None":
    """MetricsRegistry of the node handling the current request, or None
    outside an activate() scope. Process-wide singletons (the kNN dispatch
    batcher, the shard-mesh registry) record through this so that in
    multi-node in-process sims a launch lands in the EXECUTING node's
    histograms — and its exemplar trace_id resolves in the same node's
    span ring — instead of whichever node attached its sink last."""
    tracer = _active_tracer.get()
    owner = getattr(tracer, "owner", None) if tracer is not None else None
    return owner.metrics if owner is not None else None


def span(name: str, attributes: dict | None = None):
    """Open a span on the active tracer (see `activate`)."""
    return active_tracer().start_span(name, attributes)


def add_span_event(name: str, attributes: dict | None = None) -> None:
    """Attach a span EVENT to the current span, if one is open (library
    code — the batcher, the mesh registry — records what happened inside
    whoever's request is executing; a no-op outside any span). Remote
    placeholder spans restored from transport headers are skipped: their
    events would never reach a ring or the exporter."""
    current = _current_span.get()
    if current is None or current.name == "<remote>":
        return
    current.add_event(name, attributes)


class Tracer:
    """Span factory with contextvar propagation and a bounded ring of
    finished spans (the exporter slot). `name` prefixes span ids so traces
    stitched across several tracers (sim cluster nodes) stay unambiguous.

    When an exporter (telemetry/export.py SpanExporter) is attached, every
    finished span is also offered to it; the exporter's tail-keeping
    sampler decides which traces leave the process as OTLP-JSON."""

    def __init__(self, max_finished: int = 2048, enabled: bool = True,
                 name: str = "t0"):
        self.enabled = enabled
        self.name = name
        self.max_finished = max_finished
        self.exporter = None  # SpanExporter | None (export.py)
        self.owner = None  # Telemetry backref (set by Telemetry.__init__)
        self._ids = itertools.count(1)
        self._finished: deque[Span] = deque(maxlen=max_finished)
        self._lock = threading.Lock()
        # request detail (module docstring): where captures are written
        # (<path.data>/telemetry; None keeps them in memory only) and what
        # reads the counters snapshotted at a capture's open and close —
        # both set by the node that owns this tracer
        self.capture_dir = None  # Path | None
        self.capture_counters = None  # () -> dict | None
        self._capture: Capture | None = None
        self._capture_lock = threading.Lock()
        self._gc_open: tuple | None = None
        self._captures_closed = 0
        self._last_capture = {"records": 0, "dropped": 0, "file": None}

    def start_span(self, name: str, attributes: dict | None = None):
        return _SpanScope(self, name, attributes)

    def next_span_id(self) -> str:
        return f"{self.name}-s{next(self._ids):06x}"

    def begin_span(self, name: str, attributes: dict | None = None) -> Span:
        """Start a span WITHOUT installing it as the current context — for
        operations that live across scheduler callbacks (a recovery). Pair
        with end_span; propagate via restore_trace_context({"trace_id":
        span.trace_id, "span_id": span.span_id})."""
        parent = _current_span.get()
        sid = self.next_span_id()
        return Span(
            trace_id=parent.trace_id if parent else f"trace-{sid}",
            span_id=sid,
            parent_id=parent.span_id if parent else None,
            name=name,
            attributes=dict(attributes or {}),
            start_ns=time.perf_counter_ns(),
            # a request is detailed from end to end or not at all: the
            # root asks the profiler once, its children inherit the answer
            detail=(parent.detail if parent is not None
                    else self._session_capture(sid)),
        )

    def end_span(self, span: Span) -> None:
        span.end_ns = time.perf_counter_ns()
        capture = span.detail
        if capture is not None:
            capture.add_span(span)
            if span.parent_id is None:
                capture.session_roots.discard(span.span_id)
                # the last request of a session that is over closes its
                # capture: `closed` is the moment it holds them all
                if (not capture.session_roots
                        and capture is self._capture  # tpulint: disable=TPU003
                        and not profiler_session_on()):
                    self._close_capture(capture)
        if self.enabled:
            with self._lock:
                self._finished.append(span)
            exporter = self.exporter
            if exporter is not None:
                # outside self._lock: the exporter takes its own lock and
                # may call back into sinks
                exporter.on_span_end(span, self.name)

    def current_span(self) -> Span | None:
        return _current_span.get()

    def finished_spans(self) -> list[Span]:
        with self._lock:
            return list(self._finished)

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()

    # -- request detail: the capture ---------------------------------------

    def _session_capture(self, root_id: str) -> Capture | None:
        """The open capture if a profiler session is on (opening one at the
        session's first root span) or over with requests of its own still
        in flight (`Capture.draining`), else None. The last of those
        requests to end, or the first root span that finds the session
        over and none left, hands the capture to its writer: the node may
        never shut down in an orderly way (SIGTERM), so the capture is
        written when the session's requests have ended."""
        # read without the lock: this is every root span's path, and both
        # transitions below check again under it
        capture = self._capture  # tpulint: disable=TPU003
        if profiler_session_on():
            if capture is None:
                with self._capture_lock:
                    capture = self._capture
                    if capture is None:
                        capture = self._capture = Capture(self)
                        gc.callbacks.append(self._on_gc)
            capture.session_roots.add(root_id)
            return capture
        if capture is not None:
            if capture.draining():
                return capture
            self._close_capture(capture)
        return None

    def read_capture_counters(self) -> dict:
        read = self.capture_counters
        out = read() if read is not None else {}
        out["gc"] = gc.get_stats()
        return out

    def _close_capture(self, capture: Capture) -> None:
        with self._capture_lock:
            if self._capture is not capture:
                return
            self._capture = None
            if self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)
            self._captures_closed += 1
            self._last_capture = {"records": len(capture.records),
                                  "dropped": capture.dropped, "file": None}
        capture.closed = clock_pair()
        capture.counters_close = self.read_capture_counters()
        if self.capture_dir is not None:
            # a thread of its own: serving goes on while the file is written
            threading.Thread(
                target=self._write_capture, args=(capture,), daemon=True,
                name="telemetry-capture-writer").start()

    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook, installed while a capture is open: each
        collection becomes a `runtime.gc` span on the thread it stopped."""
        # no lock: a collection can start on a thread that holds it
        capture = self._capture  # tpulint: disable=TPU003
        if capture is None:
            return
        if phase == "start":
            annotation = _trace_annotation_type()(
                span_names.RUNTIME_GC, generation=info["generation"])
            annotation.__enter__()
            self._gc_open = (time.perf_counter_ns(), annotation)
        elif self._gc_open is not None:
            (start_ns, annotation), self._gc_open = self._gc_open, None
            annotation.__exit__(None, None, None)
            capture.add(span_names.RUNTIME_GC, None, None, None, start_ns,
                        time.perf_counter_ns(),
                        {"generation": info["generation"],
                         "collected": info["collected"]})

    def _write_capture(self, capture: Capture) -> None:
        try:
            time.sleep(CAPTURE_GRACE_S)
            directory = self.capture_dir
            os.makedirs(directory, exist_ok=True)
            kept = sorted(
                (int(m.group(1)), m.group(0))
                for m in map(_CAPTURE_FILE.fullmatch, os.listdir(directory))
                if m is not None)
            number = kept[-1][0] + 1 if kept else 1
            path = os.path.join(directory, f"capture-{number}.json")
            records = capture.records
            count = len(records)
            head = {
                "tracer": self.name,
                "opened": {"perf_counter_ns": capture.opened[0],
                           "time_ns": capture.opened[1]},
                "closed": {"perf_counter_ns": capture.closed[0],
                           "time_ns": capture.closed[1]},
                "counters": {"open": capture.counters_open,
                             "close": capture.counters_close},
                "threads": {str(t.ident): t.name
                            for t in threading.enumerate()},
                "dropped": capture.dropped,
                "fields": list(CAPTURE_FIELDS),
            }
            with open(path + ".tmp", "w") as out:
                out.write(json.dumps(head)[:-1] + ', "records": [\n')
                # in chunks: one json.dumps of a whole capture would hold
                # the interpreter lock against the threads that serve
                for lo in range(0, count, _CAPTURE_WRITE_CHUNK):
                    chunk = records[lo:min(lo + _CAPTURE_WRITE_CHUNK, count)]
                    out.write((",\n" if lo else "")
                              + json.dumps(chunk, default=str)[1:-1])
                    time.sleep(0)
                out.write("\n]}\n")
            os.replace(path + ".tmp", path)
            with self._capture_lock:
                self._last_capture = {"records": count,
                                      "dropped": capture.dropped,
                                      "file": path}
            for _number, name in kept[:max(
                    0, len(kept) + 1 - CAPTURE_KEEP_FILES)]:
                os.remove(os.path.join(directory, name))
        except Exception:  # noqa: BLE001 - a lost capture must not hurt serving
            logger.exception("telemetry capture was not written")

    def capture_stats(self) -> dict:
        """The `_nodes/stats` telemetry section's `capture` entry."""
        with self._capture_lock:
            capture, last = self._capture, self._last_capture
            closed = self._captures_closed
        return {
            "open": capture is not None,
            "records": (len(capture.records) if capture is not None
                        else last["records"]),
            "dropped": (capture.dropped if capture is not None
                        else last["dropped"]),
            "captures": closed,
            "last_file": last["file"],
        }


class _Counter:
    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def add(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount


# default histogram bucket upper bounds: a 1-2-5 decade ladder wide enough
# for both millisecond latencies and batch sizes; the terminal +Inf bucket
# is implicit (Prometheus classic-histogram convention)
DEFAULT_BUCKETS = (
    1, 2, 5, 10, 25, 50, 100, 250, 500, 1_000, 2_500, 5_000, 10_000,
    30_000, 60_000,
)


# an exemplar covers this many observations before it is considered stale
# and any fresh observation (not only a larger one) may replace it: a p99
# spike from an hour ago must not shadow today's outliers forever
EXEMPLAR_WINDOW = 1024


class _Histogram:
    def __init__(self, buckets: tuple = DEFAULT_BUCKETS):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.buckets = tuple(sorted(buckets))
        # cumulative counts per upper bound (le semantics); +Inf == count
        self.bucket_counts = [0] * len(self.buckets)
        # bucket index (len(buckets) == +Inf) -> the max-latency observation
        # of the current window with the trace that produced it, so a p99
        # bucket links straight to an exportable trace (OpenMetrics
        # exemplars; OTel's exemplar reservoir with a keep-max policy)
        self.exemplars: dict[int, dict] = {}
        self._lock = threading.Lock()

    def record(self, value: float, trace_id: str | None = None) -> None:
        if trace_id is None:
            span = _current_span.get()
            trace_id = span.trace_id if span is not None else None
        with self._lock:
            self.count += 1
            self.total += value
            self.min = min(self.min, value)
            self.max = max(self.max, value)
            bucket_idx = len(self.buckets)  # +Inf unless a bound catches it
            for i, le in enumerate(self.buckets):
                if value <= le:
                    self.bucket_counts[i] += 1
                    bucket_idx = min(bucket_idx, i)
            if trace_id is not None:
                window = self.count // EXEMPLAR_WINDOW
                cur = self.exemplars.get(bucket_idx)
                if cur is None or cur["window"] != window \
                        or value >= cur["value"]:
                    self.exemplars[bucket_idx] = {
                        "value": value, "trace_id": trace_id,
                        "window": window,
                    }

    def _exemplars_locked(self) -> list[dict]:
        out = []
        for i in sorted(self.exemplars):
            e = self.exemplars[i]
            out.append({
                "le": self.buckets[i] if i < len(self.buckets) else "+Inf",
                "value": e["value"], "trace_id": e["trace_id"],
            })
        return out

    def stats(self) -> dict:
        with self._lock:  # consistent snapshot: record() holds this too
            if self.count == 0:
                return {"count": 0, "sum": 0.0, "avg": 0.0,
                        "min": 0.0, "max": 0.0,
                        "buckets": [
                            {"le": le, "count": 0} for le in self.buckets
                        ]}
            out = {
                "count": self.count, "sum": self.total,
                "avg": self.total / self.count,
                "min": self.min, "max": self.max,
                "buckets": [
                    {"le": le, "count": c}
                    for le, c in zip(self.buckets, self.bucket_counts)
                ],
            }
            exemplars = self._exemplars_locked()
            if exemplars:
                out["exemplars"] = exemplars
            return out


# labeled-series cardinality bound per histogram family: beyond this many
# distinct label sets, new ones record into the base (unlabeled) series and
# a dropped counter ticks — an unbounded label value (doc ids, trace ids)
# must never mint unbounded Prometheus series (the TPU013 concern, enforced
# at runtime for the label dimension)
MAX_LABEL_SETS = 64
# reserved label set collecting observations past the cap: one visible
# overflow bucket instead of a 65th+ series
OVERFLOW_LABEL_KEY = (("_overflow", "true"),)


class MetricsRegistry:
    def __init__(self):
        self._counters: dict[str, _Counter] = {}
        self._histograms: dict[str, _Histogram] = {}
        # family name -> sorted-label-tuple -> series (histogram LABEL
        # support: per-index `search.took_ms{index=...}` under a constant
        # metric name — vary labels, never names)
        self._labeled: dict[str, dict[tuple, _Histogram]] = {}
        self._labels_dropped: dict[str, int] = {}
        self._lock = threading.Lock()

    def counter(self, name: str) -> _Counter:
        with self._lock:
            return self._counters.setdefault(name, _Counter())

    def histogram(self, name: str, labels: dict | None = None) -> _Histogram:
        with self._lock:
            if labels:
                family = self._labeled.setdefault(name, {})
                key = tuple(sorted(
                    (str(k), str(v)) for k, v in labels.items()))
                series = family.get(key)
                if series is None:
                    if len(family) >= MAX_LABEL_SETS:
                        # cardinality bound: overflow collects in ONE
                        # reserved series (not the base — record sites feed
                        # base AND labeled, so routing overflow to base
                        # would double-count it there), visibly counted
                        self._labels_dropped[name] = (
                            self._labels_dropped.get(name, 0) + 1)
                        overflow = family.get(OVERFLOW_LABEL_KEY)
                        if overflow is None:
                            overflow = family[OVERFLOW_LABEL_KEY] = \
                                _Histogram()
                        return overflow
                    series = family[key] = _Histogram()
                return series
            return self._histograms.setdefault(name, _Histogram())

    def stats(self) -> dict:
        with self._lock:
            histograms: dict[str, dict] = {
                n: h.stats() for n, h in self._histograms.items()
            }
            for name, family in self._labeled.items():
                entry = histograms.setdefault(name, _Histogram().stats())
                entry["series"] = [
                    {"labels": dict(key), **series.stats()}
                    for key, series in family.items()
                ]
                dropped = self._labels_dropped.get(name)
                if dropped:
                    entry["label_sets_dropped"] = dropped
            return {
                "counters": {n: c.value for n, c in self._counters.items()},
                "histograms": histograms,
            }


class Telemetry:
    def __init__(self, name: str = "t0"):
        self.tracer = Tracer(name=name)
        self.metrics = MetricsRegistry()
        # backref so active_metrics() can resolve the executing node's
        # registry from the activate() scope its request handlers open
        self.tracer.owner = self


default_telemetry = Telemetry()
