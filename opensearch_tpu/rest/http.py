"""Asyncio HTTP/1.1 server hosting the REST layer.

The analog of the reference's HTTP transport
(server/src/main/java/org/opensearch/http/AbstractHttpServerTransport.java +
modules/transport-netty4 Netty4HttpServerTransport): stdlib asyncio streams,
keep-alive, content-length bodies, NDJSON detection for _bulk/_msearch, and
the OpenSearch error envelope ({"error": {...}, "status": N}).

Run: python -m opensearch_tpu.rest.http --port 9200 --data /tmp/data
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any
from urllib.parse import parse_qsl, unquote, urlsplit

from opensearch_tpu.common.errors import OpenSearchTpuException
from opensearch_tpu.node import TpuNode
from opensearch_tpu.rest.handlers import build_router
from opensearch_tpu.telemetry import spans as span_names
from opensearch_tpu.telemetry import tracing

MAX_BODY = 100 * 1024 * 1024  # the reference's http.max_content_length default


class _BadRequest(Exception):
    pass


class _EntityTooLarge(Exception):
    pass


class HttpServer:
    def __init__(self, node: TpuNode, host: str = "127.0.0.1", port: int = 9200):
        self.node = node
        self.host = host
        self.port = port
        self.router = build_router()
        self._server: asyncio.AbstractServer | None = None
        # data ops run on a single worker: TpuNode/IndexShard mutation paths
        # are not thread-safe; the engine is single-writer (like the
        # reference's per-shard write semantics). The _tasks APIs get their
        # OWN worker — the reference's dedicated `management` threadpool —
        # so task listing/cancellation stays responsive while a slow search
        # occupies the data worker (TaskManager is internally locked).
        self._executor = ThreadPoolExecutor(max_workers=1)
        self._mgmt_executor = ThreadPoolExecutor(max_workers=1)
        # read-only search requests get a PARALLEL pool (the reference's
        # `search` threadpool): they execute against immutable acquired
        # snapshots, so N concurrent clients reach the kNN dispatch batcher
        # concurrently and coalesce into shared device launches — on one
        # worker they would serialize upstream and never merge. Scroll/PIT
        # lifecycle requests stay on the serial data worker (they mutate
        # the reader-context registry).
        #
        # PRIORITY LANES (ISSUE 11): the parallel pool is the INTERACTIVE
        # lane; background-classified requests (_msearch and anything
        # ?lane=background) run a separate, smaller pool with a BOUNDED
        # queue — a background flood saturates only its own workers and
        # sheds 429 past its queue bound, so it can never occupy every
        # slot an interactive _search needs (search/lanes.py).
        import os as _os

        self._search_executor = ThreadPoolExecutor(
            max_workers=min(8, (_os.cpu_count() or 2)),
            thread_name_prefix="search",
        )
        self._background_executor = ThreadPoolExecutor(
            max_workers=max(2, min(4, (_os.cpu_count() or 2) // 2)),
            thread_name_prefix="search-bg",
        )
        from opensearch_tpu.search import lanes as _lanes

        # share the node's tracker when it has one, so the `_nodes/stats`
        # tail section reads the same cells the HTTP boundary updates
        self.lane_tracker = (getattr(node, "lane_tracker", None)
                             or _lanes.LaneTracker())

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle_connection, self.host, self.port
        )

    async def serve_forever(self) -> None:
        await self.start()
        assert self._server is not None
        async with self._server:
            await self._server.serve_forever()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        # release the worker pools: embedders and tests boot many servers
        # per process, and idle non-daemon pool threads would otherwise
        # accumulate for the process lifetime
        for pool in (self._executor, self._mgmt_executor,
                     self._search_executor, self._background_executor):
            pool.shutdown(wait=False)

    # -- connection handling ----------------------------------------------

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        try:
            while True:
                try:
                    request = await self._read_request(reader)
                except _BadRequest as e:
                    await self._write_response(
                        writer, 400,
                        {"error": {"type": "parse_exception", "reason": str(e)},
                         "status": 400},
                        "application/json", keep_alive=False, head=False,
                    )
                    break
                except _EntityTooLarge:
                    await self._write_response(
                        writer, 413,
                        {"error": {"type": "content_too_large_exception",
                                   "reason": "request entity too large"},
                         "status": 413},
                        "application/json", keep_alive=False, head=False,
                    )
                    break
                if request is None:
                    break
                method, path, query, headers, body = request
                status, payload, content_type, root = await self._dispatch(
                    method, path, query, body
                )
                keep_alive = headers.get("connection", "keep-alive") != "close"
                await self._write_response(
                    writer, status, payload, content_type,
                    keep_alive=keep_alive, head=(method == "HEAD"),
                    root=root,
                )
                if not keep_alive:
                    break
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except Exception as e:  # noqa: BLE001 - best-effort close
                logging.getLogger(__name__).debug(
                    "http connection close failed: %s", e)

    async def _read_request(self, reader: asyncio.StreamReader):
        try:
            request_line = await reader.readline()
        except (ConnectionResetError, asyncio.LimitOverrunError):
            return None
        if not request_line:
            return None
        try:
            method, target, _version = request_line.decode("latin1").split(" ", 2)
        except ValueError:
            return None
        headers: dict[str, str] = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            name, _, value = line.decode("latin1").partition(":")
            headers[name.strip().lower()] = value.strip()
        try:
            length = int(headers.get("content-length", 0))
        except ValueError as e:
            raise _BadRequest(f"invalid Content-Length header") from e
        if length > MAX_BODY:
            raise _EntityTooLarge()
        body = await reader.readexactly(length) if length else b""
        split = urlsplit(target)
        query = dict(parse_qsl(split.query, keep_blank_values=True))
        return method, unquote(split.path), query, headers, body

    # -- dispatch ----------------------------------------------------------

    @staticmethod
    def _is_parallel_search(path: str, query: dict) -> bool:
        """Read-only search requests eligible for the parallel pool.
        Scroll START (?scroll=), scroll continuation (/_search/scroll), and
        PIT lifecycle calls mutate the reader-context registry and stay on
        the serial data worker."""
        if "scroll" in query:
            return False
        tail = path.rsplit("/", 1)[-1]
        return tail in ("_search", "_msearch", "_count")

    async def _dispatch(
        self, method: str, path: str, query: dict, raw_body: bytes
    ) -> tuple[int, Any, str, Any]:
        """(status, payload, content type, the request's root span — which
        the response write hangs `http.respond` under)."""
        from opensearch_tpu.search import lanes as lanes_mod
        from opensearch_tpu.telemetry import default_telemetry

        telemetry = getattr(self.node, "telemetry", default_telemetry)
        root = None
        try:
            with telemetry.tracer.start_span(
                span_names.HTTP_REQUEST, {"method": method, "path": path}
            ) as span:
                root = span
                with tracing.detail(span_names.HTTP_PARSE):
                    handler, params = self.router.resolve(method, path)
                    body = _parse_body(path, raw_body)
                    # transport knows the payload size; hand it to bulk so
                    # the pressure estimate doesn't re-serialize every
                    # document
                    if path.endswith("/_bulk") or path == "/_bulk":
                        query["_payload_bytes"] = len(raw_body)
                    lane_cfg = lanes_mod.default_config
                    lane = (lanes_mod.classify_rest(path, query)
                            if lane_cfg.enabled else lanes_mod.INTERACTIVE)
                span.set_attribute("lane", lane)
                # in-flight request bytes against the breaker (the
                # reference's in_flight_requests child tracks transport
                # payload bytes)
                breakers = getattr(self.node, "breakers", None)
                if breakers is not None and raw_body:
                    breakers.in_flight_requests.add_estimate_and_maybe_break(
                        len(raw_body), "<http_request>"
                    )
                # only the lock-protected TaskManager endpoints may run
                # concurrently with the data worker; stats/cat iterate
                # engine structures that are single-writer. Read-only
                # searches run on the parallel search pool — split by
                # PRIORITY LANE (see __init__) so background msearch floods
                # can't occupy the interactive workers. The lane reaches
                # handlers through the lane_scope contextvar below — never
                # the query dict (strict handlers reject unrecognized
                # parameters)
                tracked = False
                if path.startswith("/_tasks"):
                    executor = self._mgmt_executor
                elif self._is_parallel_search(path, query):
                    tracked = True
                    if lane_cfg.enabled and lane == lanes_mod.BACKGROUND:
                        executor = self._background_executor
                        if not self.lane_tracker.try_submit(
                                lane, lane_cfg.background_max_queue):
                            # bounded background lane: shed, never queue
                            # without bound (the QueuePressure contract)
                            lanes_mod.record_lane_shed(
                                telemetry.metrics, lane)
                            if breakers is not None and raw_body:
                                breakers.in_flight_requests.release(
                                    len(raw_body))
                            span.set_attribute("status", 429)
                            return 429, {
                                "error": {
                                    "type": "rejected_execution_exception",
                                    "reason": "background lane queue is full",
                                },
                                "status": 429,
                            }, "application/json", root
                    else:
                        executor = self._search_executor
                        self.lane_tracker.try_submit(lane)
                    lanes_mod.record_lane_metrics(
                        telemetry.metrics, lane,
                        self.lane_tracker.depth(lane))
                else:
                    executor = self._executor
                try:
                    # handlers are synchronous work; run them off the event
                    # loop so slow searches don't stall socket IO. The
                    # contextvars context is copied into the worker thread so
                    # handler spans parent under this http_request span (and
                    # the lane scope rides it into the dispatch batcher).
                    import contextvars as _cv

                    # the wait for a pool worker: stamped here, on the loop
                    # thread, and recorded by the worker that ends it
                    submitted_ns = (time.perf_counter_ns()
                                    if span.detail is not None else 0)

                    def run_handler():
                        if submitted_ns:
                            with tracing.detail(
                                    span_names.HTTP_POOL_WAIT) as waited:
                                waited.attributes.update(
                                    wait_ns=waited.start_ns - submitted_ns,
                                    submitted_ns=submitted_ns,
                                    workers=executor._max_workers)
                        with lanes_mod.lane_scope(lane):
                            return handler(self.node, params, query, body)

                    ctx = _cv.copy_context()
                    status, payload = await asyncio.get_running_loop().run_in_executor(
                        executor, ctx.run, run_handler,
                    )
                    span.set_attribute("status", status)
                finally:
                    if tracked:
                        self.lane_tracker.complete(lane)
                    if breakers is not None and raw_body:
                        breakers.in_flight_requests.release(len(raw_body))
            if "filter_path" in query and status < 400:
                from opensearch_tpu.rest.handlers import apply_filter_path

                payload = apply_filter_path(payload, query["filter_path"])
            content_type = (
                "text/plain" if isinstance(payload, str) else "application/json"
            )
            return status, payload, content_type, root
        except OpenSearchTpuException as e:
            return e.status, _error_envelope(e), "application/json", root
        except json.JSONDecodeError as e:
            return 400, {
                "error": {"type": "parse_exception", "reason": str(e)},
                "status": 400,
            }, "application/json", root
        except Exception as e:  # noqa: BLE001 - top-level 500 guard
            traceback.print_exc()
            return 500, {
                "error": {"type": "exception", "reason": str(e)},
                "status": 500,
            }, "application/json", root

    async def _write_response(
        self, writer, status: int, payload: Any, content_type: str,
        keep_alive: bool, head: bool, root=None,
    ) -> None:
        # the write follows `http_request`'s close: its span hangs under
        # the root the dispatch handed back
        with tracing.detail(span_names.HTTP_RESPOND, parent=root) as span:
            if isinstance(payload, str):
                data = payload.encode()
            else:
                data = json.dumps(payload).encode()
            span.set_attribute("bytes", len(data))
            reason = {200: "OK", 201: "Created", 400: "Bad Request", 404: "Not Found",
                      405: "Method Not Allowed", 409: "Conflict",
                      413: "Content Too Large", 429: "Too Many Requests",
                      500: "Internal Server Error",
                      503: "Service Unavailable"}.get(status, "OK")
            head_lines = (
                f"HTTP/1.1 {status} {reason}\r\n"
                f"content-type: {content_type}; charset=UTF-8\r\n"
                f"content-length: {len(data)}\r\n"
                f"connection: {'keep-alive' if keep_alive else 'close'}\r\n\r\n"
            )
            writer.write(head_lines.encode() + (b"" if head else data))
            await writer.drain()


def _parse_body(path: str, raw: bytes) -> Any:
    if not raw:
        return None
    # NDJSON only when the LAST path segment is the bulk/msearch endpoint
    # (a doc id like "report_bulk" must not trigger NDJSON parsing)
    if path.rstrip("/").rsplit("/", 1)[-1] in ("_bulk", "_msearch"):
        lines = []
        for line in raw.split(b"\n"):
            line = line.strip()
            if line:
                lines.append(json.loads(line))
        return lines
    return json.loads(raw)


def _error_envelope(e: OpenSearchTpuException) -> dict:
    detail = e.to_dict()
    return {
        "error": {
            "root_cause": [detail],
            **detail,
        },
        "status": e.status,
    }


def main() -> None:
    parser = argparse.ArgumentParser(description="opensearch-tpu node")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=9200)
    parser.add_argument("--data", default="./data")
    args = parser.parse_args()
    node = TpuNode(args.data)
    server = HttpServer(node, args.host, args.port)
    print(f"opensearch-tpu listening on http://{args.host}:{args.port}")
    try:
        asyncio.run(server.serve_forever())
    except KeyboardInterrupt:
        pass
    finally:
        node.close()


if __name__ == "__main__":
    main()
