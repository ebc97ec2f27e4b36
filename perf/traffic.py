"""The one general load generator. A traffic mix is a data file of
parameters under `perf/mixes/`; nothing here knows a mix by name.

    {"loop": "closed", "clients": 32, "queries": 2048, "warmup_seconds": 5}
    {"loop": "open", "rate_per_s": 60, "connections": 16, "queries": 2048,
     "arrivals": "poisson", "warmup_seconds": 5}

Optional, for warm-up only: "warmup_bursts": [2, 3, 4, 8] sends bursts of
that many simultaneous requests while the node's settings of
"warmup_settings" are in force (put back afterwards), so that the batch
widths concurrent traffic can reach are compiled before the window opens.

closed: each client owns one keep-alive connection and sends its next
request when the previous one is answered. open: requests fall due on a
schedule fixed by the seed (uniform or Poisson at `rate_per_s`), are sent
by whichever of `connections` workers is free, and are timed from when
they were DUE, so the wait a stall imposes on later requests counts; how
late the generator itself ran is kept per request (`late_s`).

Every seed sends the same set of requests: the seed orders the query set
and (open loop) draws the gaps. Bodies are encoded before the window
opens; raw replies are kept and parsed after it closes.
"""

from __future__ import annotations

import http.client
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from perf.node import Client, RunFailure


@dataclass
class Window:
    t_open: float = 0.0
    seconds: float = 0.0
    query: list = field(default_factory=list)      # index into the query set
    t_from: list = field(default_factory=list)     # sent (closed) / due (open)
    t_done: list = field(default_factory=list)
    late_s: list = field(default_factory=list)
    status: list = field(default_factory=list)     # 0 = never answered
    payload: list = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.query)

    def wall_ms(self) -> list:
        return [(b - a) * 1e3 for a, b in zip(self.t_from, self.t_done)]


def schedule(mix: dict, seed: int, seconds: float) -> list:
    """Due offsets of an open-loop window, from the seed."""
    rate = float(mix["rate_per_s"])
    n = int(rate * seconds)
    if mix.get("arrivals", "uniform") == "poisson":
        rng = np.random.default_rng([seed, 3])
        gaps = rng.exponential(1.0 / rate, n)
        due = np.cumsum(gaps)
        return [float(t) for t in due if t < seconds]
    return [i / rate for i in range(n)]


def drive(port: int, path: str, bodies: list, mix: dict, seed: int,
          seconds: float) -> Window:
    """Run the mix against POST `path` for `seconds`; every request that was
    sent is waited for (up to a minute past the close) and kept."""
    order = np.random.default_rng([seed, 4]).permutation(len(bodies)).tolist()
    closed = mix["loop"] == "closed"
    workers = int(mix["clients"] if closed else mix["connections"])
    due = None if closed else schedule(mix, seed, seconds)
    out = Window(seconds=seconds)
    lock = threading.Lock()
    barrier = threading.Barrier(workers + 1)
    cursor = {"next": 0}
    errors: list = []

    def send(c: Client, q: int):
        try:
            return c.raw("POST", path, bodies[q])
        except (OSError, http.client.HTTPException):
            return 0, b""

    def closed_client(w: int) -> None:
        mine = order[w::workers]
        rows = []
        c = Client(port, timeout=60.0 + seconds)
        try:
            barrier.wait(timeout=120)
            time.sleep(max(0.0, out.t_open - time.perf_counter()))
            i = 0
            while True:
                t = time.perf_counter()
                if t >= out.t_open + seconds:
                    break
                q = mine[i % len(mine)]
                status, payload = send(c, q)
                rows.append((q, t, time.perf_counter(), 0.0, status, payload))
                i += 1
                if status == 0:
                    c.close()
                    c = Client(port, timeout=60.0 + seconds)
        finally:
            c.close()
            with lock:
                _extend(out, rows)

    def open_worker(w: int) -> None:
        rows = []
        c = Client(port, timeout=60.0 + seconds)
        try:
            barrier.wait(timeout=120)
            while True:
                with lock:
                    i = cursor["next"]
                    cursor["next"] = i + 1
                if i >= len(due):
                    break
                t_due = out.t_open + due[i]
                wait = t_due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late = max(0.0, time.perf_counter() - t_due)
                q = order[i % len(order)]
                status, payload = send(c, q)
                rows.append((q, t_due, time.perf_counter(), late, status,
                             payload))
                if status == 0:
                    c.close()
                    c = Client(port, timeout=60.0 + seconds)
        finally:
            c.close()
            with lock:
                _extend(out, rows)

    def guarded(fn, w: int) -> None:
        try:
            fn(w)
        except Exception as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)

    threads = [threading.Thread(
        target=guarded, args=(closed_client if closed else open_worker, w),
        daemon=True) for w in range(workers)]
    for t in threads:
        t.start()
    # the window opens when the last worker is connected and waiting
    out.t_open = time.perf_counter() + 0.05
    barrier.wait(timeout=120)
    for t in threads:
        t.join(timeout=seconds + 120)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise RunFailure("a load worker never finished")
    return out


def burst(port: int, path: str, bodies: list) -> list:
    """Send every body at once, one connection each; the statuses."""
    barrier = threading.Barrier(len(bodies))
    statuses = [0] * len(bodies)

    def one(i: int) -> None:
        c = Client(port, timeout=120.0)
        try:
            barrier.wait(timeout=60)
            statuses[i] = c.raw("POST", path, bodies[i])[0]
        except (OSError, http.client.HTTPException, threading.BrokenBarrierError):
            pass
        finally:
            c.close()

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    return statuses


def _extend(out: Window, rows: list) -> None:
    for q, t_from, t_done, late, status, payload in rows:
        out.query.append(q)
        out.t_from.append(t_from)
        out.t_done.append(t_done)
        out.late_s.append(late)
        out.status.append(status)
        out.payload.append(payload)
