"""Name the device's idle gaps: the program's host spans, read from the same
profiler trace as the device's ops, laid over the gaps between them.

Not wired into `perf/run.py` yet (a benchmark PR's to do: `perf/trace.py`
writes `unattributed` for every gap until then). Two halves, as in
`perf/trace.py`. `read_host_planes` needs JAX (only for
`jax.profiler.ProfileData`, on the CPU platform): it writes the device
planes' op events as `perf/trace.py` does and, beside them, the events of
the `/host:*` planes whose names are this program's spans (the
`jax.profiler.TraceAnnotation`s that `opensearch_tpu/telemetry/tracing.py`
opens while a profiler session runs; both kinds of event are on the
session's one clock). `idle_gaps` and `attribute` are arithmetic on that
JSON, checked by `tests/perf/test_perf_hostplanes.py` on a small recorded
trace. By hand:

    JAX_PLATFORMS=cpu python perf/hostplanes.py <trace dir> [edge seconds]

prints, per device plane, the ten longest idle gaps with the span names that
cover each, and `launch_latency`: how long before the device's first op the
host opens `launch.device` and how long after its last it closes it, which
is what separates `host.between_launch_ms` from the device's own gaps. A
trace of a program without these spans has no such host events, and every
gap stays `unattributed`.

Reduced form:
  {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
   "host": {"/host:CPU#3 search_0": [[name, start_ns, dur_ns, span_id], ...]}}
"""

from __future__ import annotations

import glob
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from perf import trace  # noqa: E402

HOST_PLANE_PREFIX = "/host:"
UNATTRIBUTED = "unattributed"
# the program's span names (opensearch_tpu/telemetry/spans.py); kept here as
# data, since the benchmark also reads traces of commits without that module
SPAN_NAMES = frozenset((
    "http_request", "http.parse", "http.pool_wait", "http.respond",
    "search", "search.parse", "search.query_phase", "search.collect",
    "search.reduce", "search.fetch", "search.respond",
    "batch.wait",
    "launch", "launch.host_pre", "launch.device", "launch.fetch",
    "launch.host_post",
    "runtime.gc",
))


def read_host_planes(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: dict = {}
    host: dict = {}
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == trace.OP_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for i, line in enumerate(plane.lines):
                events = [
                    [ev.name, float(ev.start_ns), float(ev.duration_ns),
                     dict(ev.stats).get("span_id")]
                    for ev in line.events if ev.name in SPAN_NAMES]
                if events:
                    host[f"{plane.name}#{i} {line.name}"] = events
    return {"devices": devices, "host": host}


def idle_gaps(events: list, edge_s: float = 0.0) -> list:
    """[start_ns, end_ns] of each gap between the merged busy intervals of
    one device plane's [name, start, dur] events, `edge_s` left out at each
    end as `perf/trace.py` leaves it out."""
    _busy, merged = trace.busy_union(trace.clip(events, edge_s * 1e9))
    return [[a[1], b[0]] for a, b in zip(merged, merged[1:])]


def innermost(line: list, lo: float, hi: float) -> dict:
    """name -> ns of [lo, hi] during which a span of that name was the
    innermost open one on this line (one thread): of the spans that cover
    an instant, the one that started last."""
    spans = sorted((ev[1], ev[1] + ev[2], ev[0]) for ev in line
                   if ev[1] < hi and ev[1] + ev[2] > lo)
    cuts = sorted({lo, hi, *(t for a, b, _ in spans for t in (a, b)
                             if lo < t < hi)})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        open_here = [s for s in spans if s[0] <= a and s[1] >= b]
        if open_here:
            name = max(open_here)[2]
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def attribute(gaps: list, host_events: dict) -> list:
    """For each [start_ns, end_ns] gap: [[name, seconds], ...], the names of
    the innermost spans open during it, summed over the host's threads (so
    two threads in a span at once count twice), most covered time first;
    the part of a gap that no span on any thread covers — or all of it —
    is `unattributed`."""
    out = []
    for lo, hi in gaps:
        names: dict = {}
        covered = []
        for line in host_events.values():
            for name, ns in innermost(line, lo, hi).items():
                names[name] = names.get(name, 0.0) + ns
            covered.extend((max(ev[1], lo), min(ev[1] + ev[2], hi))
                           for ev in line
                           if ev[1] < hi and ev[1] + ev[2] > lo)
        _busy, merged = trace.busy_union(
            [["", a, b - a] for a, b in covered])
        bare = (hi - lo) - sum(b - a for a, b in merged)
        row = sorted(names.items(), key=lambda kv: -kv[1])
        if bare > 0:
            row.append((UNATTRIBUTED, bare))
        out.append([[name, ns / 1e9] for name, ns in row])
    return out


def longest_gaps(reduced: dict, edge_s: float = 0.0, top: int = 10) -> dict:
    """Per device plane: the `top` longest idle gaps, each as
    {"gap_s", "start_ns", "covered_by": [[name, seconds], ...]}."""
    out = {}
    for plane, events in reduced.get("devices", {}).items():
        gaps = sorted(idle_gaps(events, edge_s),
                      key=lambda g: g[0] - g[1])[:top]
        out[plane] = [
            {"gap_s": (hi - lo) / 1e9, "start_ns": lo, "covered_by": names}
            for (lo, hi), names in zip(
                gaps, attribute(gaps, reduced.get("host", {})))]
    return out


def launch_latency(reduced: dict, edge_s: float = 0.0) -> dict:
    """Per device plane, what separates the host's view of the idle gap
    (`host.between_launch_ms`) from the device's own. The host cannot see
    the device start or stop: it opens `launch.device` `dispatch_ms` before
    the device's first op and closes it (the first host copy back)
    `fence_ms` after its last, so between two launches
        device gap = host gap + fence_ms + dispatch_ms.
    Windows are the union of the `launch.device` spans over the host's
    threads; `device_gap_ms` runs from the last op inside one window to the
    first op inside the next, `host_gap_ms` from one window's end to the
    next one's start. `busy_outside` counts the device's busy stretches
    that no window holds whole: device work that is no launch's, or two
    clocks. Means, in ms; a window the edge cuts is left out."""
    _busy, windows = trace.busy_union(
        [ev[:3] for line in reduced.get("host", {}).values()
         for ev in line if ev[0] == "launch.device"])
    out = {}
    for plane, events in reduced.get("devices", {}).items():
        _busy, busy = trace.busy_union(trace.clip(events, edge_s * 1e9))
        if not busy:
            continue
        # where `trace.clip` cut: ops run up to the cut, windows past it
        lo, hi = busy[0][0], busy[-1][1]
        held, launches = 0, []    # (window start, first op, last op, end)
        for start, end in windows:
            inside = [b for b in busy if b[0] >= start and b[1] <= end]
            held += len(inside)
            if inside and (edge_s <= 0 or (start >= lo and end <= hi)):
                launches.append((start, inside[0][0], inside[-1][1], end))

        def mean_ms(values):
            return sum(values) / len(values) / 1e6 if values else None

        pairs = list(zip(launches, launches[1:]))
        out[plane] = {
            "launches": len(launches),
            "dispatch_ms": mean_ms([first - start
                                    for start, first, _, _ in launches]),
            "fence_ms": mean_ms([end - last for _, _, last, end in launches]),
            "host_gap_ms": mean_ms([b[0] - a[3] for a, b in pairs]),
            "device_gap_ms": mean_ms([b[1] - a[2] for a, b in pairs]),
            "busy_outside": len(busy) - held,
        }
    return out


if __name__ == "__main__":
    edge = float(sys.argv[2]) if len(sys.argv) > 2 else 0.25
    planes = read_host_planes(sys.argv[1])
    print(json.dumps({"longest_gaps": longest_gaps(planes, edge),
                      "launch_latency": launch_latency(planes, edge)},
                     indent=1))
