"""The system under test as a child process, and one HTTP connection to it.

Copies of `chip_smoke.py`'s `Child` and `Client` (PR 21), with the child
started through `perf/launcher.py` (the same `cli.main`, plus the profiler
commands). The harness that imports this never imports JAX: a chip belongs
to one process.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
LAUNCHER = HERE / "launcher.py"
BOOT_TIMEOUT_S = 300


class RunFailure(Exception):
    """The run cannot produce a result (no device, node died, HTTP error
    in set-up). The harness exits non-zero and prints no result line."""


class Client:
    """One keep-alive connection; one per thread."""

    def __init__(self, port: int, timeout: float = 900.0):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def raw(self, method: str, path: str, data: bytes | None,
            ctype: str = "application/json") -> tuple[int, bytes]:
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": ctype})
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def call(self, method: str, path: str, body=None,
             ndjson: bool = False) -> dict:
        data = None
        if body is not None:
            data = body if isinstance(body, bytes) else json.dumps(body).encode()
        status, payload = self.raw(
            method, path, data,
            "application/x-ndjson" if ndjson else "application/json")
        if status >= 300:
            raise RunFailure(
                f"{method} {path} -> HTTP {status}: {payload[:400]!r}")
        return json.loads(payload)

    def close(self) -> None:
        self.conn.close()


class Child:
    def __init__(self, platform: str, data_dir: Path):
        env = dict(os.environ)
        # the guard against a hidden CPU is JAX's own: with this set a
        # process that finds no such device dies at its first touch of JAX.
        # JAX_COMPILATION_CACHE_DIR passes through unchanged.
        env["JAX_PLATFORMS"] = platform
        self.port = free_port()
        self.lines: list[str] = []
        self.proc = subprocess.Popen(
            [sys.executable, str(LAUNCHER), "--http-port", str(self.port),
             "--data", str(data_dir)], cwd=str(ROOT), env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._seen = threading.Condition()
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            with self._seen:
                self.lines.append(line.rstrip("\n"))
                self._seen.notify_all()
            print("[child]", line.rstrip("\n"), file=sys.stderr, flush=True)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def command(self, line: str, reply: str, timeout: float) -> None:
        """Send one launcher command and wait for its `[launcher]` reply."""
        with self._seen:
            start = len(self.lines)
            self.proc.stdin.write(line + "\n")
            self.proc.stdin.flush()
            deadline = time.monotonic() + timeout
            while True:
                for got in self.lines[start:]:
                    if got.startswith("[launcher] error"):
                        raise RunFailure(got)
                    if got == f"[launcher] {reply}":
                        return
                left = deadline - time.monotonic()
                if left <= 0 or not self.alive():
                    raise RunFailure(f"no [{reply}] from the launcher")
                self._seen.wait(timeout=min(left, 1.0))

    def started(self) -> dict | None:
        for line in list(self.lines):
            m = re.search(r"started=(\{.*\})\s*$", line)
            if m:
                return json.loads(m.group(1))
        return None

    def wait_healthy(self) -> dict:
        """The node's own start-up report, once it serves."""
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while time.monotonic() < deadline:
            if not self.alive():
                raise RunFailure(
                    f"node exited with code {self.proc.returncode} before "
                    f"it served /_cluster/health")
            c = Client(self.port, timeout=5.0)
            try:
                c.call("GET", "/_cluster/health")
                started = self.started()
                if started is None:
                    raise RunFailure("node printed no started= line")
                return started
            except (OSError, http.client.HTTPException):
                time.sleep(0.25)
            finally:
                c.close()
        raise RunFailure(f"no /_cluster/health in {BOOT_TIMEOUT_S}s")

    def stop(self) -> None:
        # SIGTERM, then SIGKILL. (Not SIGINT: a shell that started this
        # script in the background leaves SIGINT ignored in its children.)
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=20)
        self._reader.join(timeout=5)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
