"""From a profiler trace to device busy time, idle gaps and the top ops.

Two halves. `read_xplane` needs JAX (only for `jax.profiler.ProfileData`,
on the CPU platform) and therefore runs as a process of its own, once the
node has stopped: `python perf/trace.py <trace dir> <out.json>` writes the
device planes' events as plain JSON. Everything after that — the union of
busy intervals, the gaps, the ranking — is arithmetic on that JSON, used by
the harness (which never imports JAX) and checked by `tests/perf` on a
small recorded trace.

Reduced form: {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]}}
holding the events of each device plane's op line (`XLA Ops`).
"""

from __future__ import annotations

import glob
import json
import os
import sys

OP_LINE = "XLA Ops"
OP_NAME_CHARS = 160     # the trace names an op by its whole HLO line
DEVICE_PLANE_PREFIX = "/device:"


def read_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: dict = {}
    lines_seen: dict = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines_seen[plane.name] = [line.name for line in plane.lines]
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            devices.setdefault(plane.name, []).extend(
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events)
    return {"devices": devices, "lines": lines_seen}


def busy_union(events: list) -> tuple[float, list]:
    """(busy ns, merged [start, end] intervals) of [name, start, dur] events.
    Nested and overlapping ops (a fusion inside a loop body) count once."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: list = []
    for lo, hi in spans:
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1][1] = hi
        else:
            merged.append([lo, hi])
    return sum(hi - lo for lo, hi in merged), merged


def clip(events: list, edge_ns: float) -> list:
    """The events cut to the span that starts `edge_ns` after the first
    begins and ends `edge_ns` before the last is over."""
    live = [(s, s + d) for _, s, d in events if d > 0]
    if not live or edge_ns <= 0:
        return events
    lo = min(a for a, _ in live) + edge_ns
    hi = max(b for _, b in live) - edge_ns
    return [[name, max(s, lo), min(s + d, hi) - max(s, lo)]
            for name, s, d in events if min(s + d, hi) > max(s, lo)]


def reduce_trace(reduced: dict, edge_s: float = 0.0) -> dict | None:
    """Busy and window seconds averaged over the device planes that ran
    something, the ops that took most time and the longest idle gaps.
    None when no operation ran on any device. `edge_s` is left out at each
    end of a plane: starting and stopping the profiler stalls the process
    that serves (two gaps of ~80 ms in a B = 1 run on the v5e), and that
    stall is the measurement's, not the program's."""
    planes = {name: evs for name, evs in reduced.get("devices", {}).items()
              if evs}
    if not planes:
        return None
    busy_s, window_s, ops, gaps = [], [], {}, []
    for evs in planes.values():
        evs = clip(evs, edge_s * 1e9)
        busy_ns, merged = busy_union(evs)
        if not merged:
            continue
        busy_s.append(busy_ns / 1e9)
        window_s.append((merged[-1][1] - merged[0][0]) / 1e9)
        for name, _, dur in evs:
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        gaps.extend((b[0] - a[1]) / 1e9 for a, b in zip(merged, merged[1:]))
    n = len(busy_s)
    if n == 0:
        return None
    busy, window = sum(busy_s) / n, sum(window_s) / n
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    # the program has no host spans yet: a gap is not attributed to what
    # the host was doing in it (the next tracing issue's work)
    longest = [["unattributed", g] for g in sorted(gaps, reverse=True)[:10]]
    return {"busy_s": busy, "window_s": window, "chips": n,
            "device_ops": [[name[:OP_NAME_CHARS], s] for name, s in top],
            "idle_gaps": longest}


if __name__ == "__main__":
    with open(sys.argv[2], "w") as out:
        json.dump(read_xplane(sys.argv[1]), out)
