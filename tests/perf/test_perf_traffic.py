"""The one general load generator, against a stub HTTP server: a closed
loop with its client count and an open loop with its rate are both data."""

from __future__ import annotations

import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf.traffic import drive, schedule  # noqa: E402


class Stub(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay = 0.005

    def do_POST(self):  # noqa: N802 - http.server's name
        body = self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(self.delay)
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):
        pass


@pytest.fixture(scope="module")
def port():
    server = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()
    thread.join(timeout=10)
    assert not thread.is_alive()


BODIES = [b"%d" % i for i in range(64)]


@pytest.mark.parametrize("clients", [1, 4])
def test_closed_loop_sends_the_next_request_on_reply(port, clients):
    mix = {"loop": "closed", "clients": clients}
    w = drive(port, "/x", BODIES, mix, seed=5, seconds=0.5)
    assert len(w) > 2 * clients and set(w.status) == {200}
    # each reply echoes the body that was sent: query i carries BODIES[i]
    assert all(w.payload[i] == BODIES[w.query[i]] for i in range(len(w)))
    assert min(w.t_from) >= w.t_open
    assert all(t < w.t_open + 0.5 for t in w.t_from)
    assert all(ms >= 4.0 for ms in w.wall_ms())   # the stub sleeps 5 ms
    # ~ clients / 5 ms, less the loop's own cost
    assert len(w) <= clients * 0.5 / 0.005 + clients


def test_closed_loop_clients_split_the_query_set(port):
    w = drive(port, "/x", BODIES, {"loop": "closed", "clients": 4}, 9, 0.4)
    assert len(set(w.query)) > 16            # more than one client's slice
    w1 = drive(port, "/x", BODIES, {"loop": "closed", "clients": 1}, 9, 0.2)
    first = [q for _, q in sorted(zip(w1.t_from, w1.query))][:5]
    w2 = drive(port, "/x", BODIES, {"loop": "closed", "clients": 1}, 9, 0.2)
    assert first == [q for _, q in sorted(zip(w2.t_from, w2.query))][:5]


@pytest.mark.parametrize("arrivals", ["uniform", "poisson"])
def test_open_loop_sends_on_schedule_and_times_from_due(port, arrivals):
    mix = {"loop": "open", "rate_per_s": 100, "connections": 8,
           "arrivals": arrivals}
    w = drive(port, "/x", BODIES, mix, seed=5, seconds=0.5)
    due = schedule(mix, 5, 0.5)
    assert len(w) == len(due) and set(w.status) == {200}
    offsets = sorted(t - w.t_open for t in w.t_from)
    assert offsets == pytest.approx(sorted(due), abs=1e-9)
    assert max(w.late_s) < 0.25 and min(w.late_s) >= 0.0
    assert all(ms >= 4.0 for ms in w.wall_ms())   # the stub sleeps 5 ms


def test_schedule_is_fixed_by_the_seed():
    mix = {"rate_per_s": 50, "arrivals": "poisson"}
    big = 2**31 + 99
    assert schedule(mix, big, 2.0) == schedule(mix, big, 2.0)
    assert schedule(mix, big, 2.0) != schedule(mix, big + 1, 2.0)
    assert schedule({"rate_per_s": 4}, 0, 1.0) == [0.0, 0.25, 0.5, 0.75]


def test_a_dead_server_is_counted_not_raised():
    w = drive(1, "/x", BODIES, {"loop": "closed", "clients": 2}, 0, 0.2)
    assert len(w) > 0 and set(w.status) == {0}
