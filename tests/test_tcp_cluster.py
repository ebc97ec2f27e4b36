"""Real-socket cluster integration: 3 ClusterServers on localhost TCP.

The InternalTestCluster analog (SURVEY.md §4 answer #1: whole nodes in one
process with real transports on loopback) applied to the TCP transport —
done-criteria: a 3-process-shaped cluster elects a leader,
serves _bulk/_search/_cluster/health through ANY node's REST port, and
survives kill-the-leader with no acknowledged-write loss.
"""

from __future__ import annotations

import asyncio
import json
import socket

import pytest

from opensearch_tpu.server import ClusterServer


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


async def http(port: int, method: str, path: str, body=None,
               timeout: float = 10.0):
    # the WHOLE exchange is deadline-bounded: a node dying mid-response
    # used to hang the unguarded header/body reads forever, wedging the
    # suite past the tier-1 budget instead of failing one request
    async def _exchange():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            if isinstance(body, (bytes, str)):
                data = body.encode() if isinstance(body, str) else body
            elif body is not None:
                data = json.dumps(body).encode()
            else:
                data = b""
            writer.write(
                (f"{method} {path} HTTP/1.1\r\nhost: x\r\n"
                 f"content-length: {len(data)}\r\n\r\n").encode() + data
            )
            await writer.drain()
            status_line = await reader.readline()
            status = int(status_line.split()[1])
            length = 0
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.decode().partition(":")
                if k.strip().lower() == "content-length":
                    length = int(v)
            payload = (json.loads(await reader.readexactly(length))
                       if length else None)
            return status, payload
        finally:
            writer.close()

    return await asyncio.wait_for(_exchange(), timeout)


class TcpCluster:
    def __init__(self, tmp_path, n: int = 3):
        ports = free_ports(2 * n)
        self.node_ids = [f"n{i}" for i in range(n)]
        self.seeds = {
            nid: ("127.0.0.1", ports[i]) for i, nid in enumerate(self.node_ids)
        }
        self.http_ports = {
            nid: ports[n + i] for i, nid in enumerate(self.node_ids)
        }
        self.tmp_path = tmp_path
        self.servers: dict[str, ClusterServer] = {}

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        for nid in self.node_ids:
            srv = ClusterServer(
                nid, self.tmp_path / nid, "127.0.0.1",
                self.seeds[nid][1], self.http_ports[nid], self.seeds,
                loop=loop,
            )
            self.servers[nid] = srv
            await srv.start(bootstrap=self.node_ids)

    async def stop(self) -> None:
        for srv in self.servers.values():
            try:
                await srv.aclose()
            except Exception:  # noqa: BLE001 - test teardown
                pass

    # 120s: elections under randomized backoff can take several rounds on
    # a loaded CI box (the 60s budget flaked test_durable_state's phase-1
    # boot during full-suite runs); an idle box still returns in <2s
    async def wait_leader(self, timeout_s: float = 120.0) -> str:
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s
        while loop.time() < deadline:
            leaders = {
                nid for nid, srv in self.servers.items()
                if srv.node.is_leader
            }
            known = {
                srv.node.coordinator.leader_id
                for srv in self.servers.values()
            }
            if len(leaders) == 1 and known == {next(iter(leaders))}:
                return next(iter(leaders))
            await asyncio.sleep(0.05)
        raise TimeoutError("no stable leader elected")

    async def wait_health(self, port: int, want: str = "green",
                          timeout_s: float = 30.0) -> dict:
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s
        last = None
        while loop.time() < deadline:
            try:
                _, last = await http(port, "GET", "/_cluster/health")
                if last and last["status"] == want:
                    return last
            except (ConnectionError, OSError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0.1)
        raise TimeoutError(f"health never reached {want}: {last}")


@pytest.fixture()
def tcp_cluster(tmp_path):
    cluster = TcpCluster(tmp_path)

    async def run(coro_fn):
        await cluster.start()
        try:
            return await coro_fn()
        finally:
            await cluster.stop()

    yield cluster, run


def test_boot_elect_write_search_any_node(tcp_cluster):
    cluster, run = tcp_cluster

    async def scenario():
        leader = await cluster.wait_leader()
        non_leaders = [n for n in cluster.node_ids if n != leader]
        p0 = cluster.http_ports[non_leaders[0]]
        p1 = cluster.http_ports[non_leaders[1]]
        pl = cluster.http_ports[leader]

        # create through a NON-leader node (routed to the leader inside)
        status, resp = await http(p0, "PUT", "/docs", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"n": {"type": "long"}}},
        })
        assert status == 200 and resp["acknowledged"], resp
        await cluster.wait_health(pl, "green")

        # bulk through another non-leader
        nd = "".join(
            json.dumps(x) + "\n"
            for i in range(50)
            for x in ({"index": {"_index": "docs", "_id": f"d{i}"}},
                      {"n": i})
        )
        status, resp = await http(p1, "POST", "/_bulk?refresh=true", nd)
        assert status == 200 and not resp["errors"], resp
        # every item was replicated before its ack
        for item in resp["items"]:
            r = next(iter(item.values()))
            assert r["_shards"]["failed"] == 0, r

        # search through every node gives the same totals
        for nid in cluster.node_ids:
            status, resp = await http(
                cluster.http_ports[nid], "POST", "/docs/_search",
                {"query": {"match_all": {}}, "size": 0,
                 "track_total_hits": True},
            )
            assert status == 200, resp
            assert resp["hits"]["total"]["value"] == 50, (nid, resp)

        # point read through the leader
        status, resp = await http(pl, "GET", "/docs/_doc/d7")
        assert status == 200 and resp["_source"]["n"] == 7

    asyncio.run(run(scenario))


def test_leader_kill_no_acked_write_loss(tcp_cluster):
    cluster, run = tcp_cluster

    async def scenario():
        leader = await cluster.wait_leader()
        survivors = [n for n in cluster.node_ids if n != leader]
        p0 = cluster.http_ports[survivors[0]]

        status, resp = await http(p0, "PUT", "/killtest", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 2},
        })
        assert status == 200, resp
        await cluster.wait_health(p0, "green")

        # acked writes through a survivor (each write waits for ALL copies)
        for i in range(20):
            status, resp = await http(
                p0, "PUT", f"/killtest/_doc/k{i}", {"n": i}
            )
            assert status in (200, 201) and "error" not in resp, resp
            assert resp["_shards"]["failed"] == 0, resp

        # kill the leader process (socket close + node close)
        await cluster.servers[leader].aclose()
        del cluster.servers[leader]

        # survivors re-elect and the cluster serves again. The election
        # under the randomized backoff can take several rounds on a loaded
        # CI box, and the new leader still has to republish a state that
        # promotes the dead node's primaries — so the test profile waits
        # until EVERY survivor agrees on one leader before asserting
        # anything about data (the 15s post-kill budget used previously
        # flaked 2/3 runs at seed on this container).
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 120.0
        new_leader = None
        while loop.time() < deadline:
            leaders = {n for n, s in cluster.servers.items()
                       if s.node.is_leader}
            known = {s.node.coordinator.leader_id
                     for s in cluster.servers.values()}
            if len(leaders) == 1 and known == {next(iter(leaders))}:
                new_leader = next(iter(leaders))
                break
            await asyncio.sleep(0.1)
        assert new_leader is not None, "no re-election after leader kill"

        # every acknowledged write must still be readable (promotion kept
        # the in-sync copy; acks waited for replication). The refresh and
        # the search both retry: right after the election the survivor may
        # still route to the dead copy while promotion publishes.
        deadline = loop.time() + 90.0
        total = -1
        while loop.time() < deadline:
            try:
                await http(p0, "POST", "/killtest/_refresh", timeout=5.0)
                status, resp = await http(
                    p0, "POST", "/killtest/_search",
                    {"query": {"match_all": {}}, "size": 0,
                     "track_total_hits": True},
                    timeout=5.0,
                )
            except (ConnectionError, OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError):
                await asyncio.sleep(0.2)
                continue
            if status == 200:
                total = resp["hits"]["total"]["value"]
                if total == 20:
                    break
            await asyncio.sleep(0.2)
        assert total == 20, f"acked writes lost: {total}/20 after failover"
        for i in (0, 7, 19):
            status, resp = await http(p0, "GET", f"/killtest/_doc/k{i}")
            assert status == 200 and resp["_source"]["n"] == i

    asyncio.run(run(scenario))


def test_leader_kill_mid_bulk(tcp_cluster):
    """Kill the leader WHILE a bulk stream is in flight: every write the
    client saw acked (with zero failed shard copies) must survive failover;
    unacked writes may be lost but must not corrupt the index."""
    cluster, run = tcp_cluster

    async def scenario():
        leader = await cluster.wait_leader()
        survivors = [n for n in cluster.node_ids if n != leader]
        p0 = cluster.http_ports[survivors[0]]

        status, resp = await http(p0, "PUT", "/midbulk", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 2},
        })
        assert status == 200, resp
        await cluster.wait_health(p0, "green")

        acked: set[str] = set()
        stop = asyncio.Event()

        async def writer_task():
            i = 0
            while not stop.is_set():
                doc_id = f"m{i}"
                try:
                    status, resp = await http(
                        p0, "PUT", f"/midbulk/_doc/{doc_id}", {"n": i},
                        timeout=5.0,
                    )
                    if (status in (200, 201) and resp
                            and "error" not in resp
                            and resp.get("_shards", {}).get("failed") == 0):
                        acked.add(doc_id)
                except (ConnectionError, OSError, asyncio.TimeoutError,
                        asyncio.IncompleteReadError):
                    pass  # in-flight write during failover: no ack, no claim
                i += 1

        writers = asyncio.create_task(writer_task())
        # condition, not sleep: the kill must land while writes are acking
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 30.0
        while loop.time() < deadline and len(acked) < 5:
            await asyncio.sleep(0.05)
        assert len(acked) >= 5, "writes never started acking"
        await cluster.servers[leader].aclose()   # kill mid-stream
        del cluster.servers[leader]
        # keep writing until a survivor leads AND at least one post-kill
        # write acked through it (proves the failover path, however long
        # the election takes under load)
        acked_at_kill = len(acked)
        deadline = loop.time() + 60.0
        while loop.time() < deadline:
            if (any(s.node.is_leader for s in cluster.servers.values())
                    and len(acked) > acked_at_kill):
                break
            await asyncio.sleep(0.1)
        stop.set()
        await writers

        # survivors re-elect
        deadline = loop.time() + 60.0
        while loop.time() < deadline:
            if any(s.node.is_leader for s in cluster.servers.values()):
                break
            await asyncio.sleep(0.1)
        assert any(s.node.is_leader for s in cluster.servers.values()), \
            "no re-election after mid-bulk leader kill"
        assert len(acked) > 0, "no writes were acked before/after the kill"

        # every acked doc must be readable after failover; promotion and
        # replica repair may still be settling, so retry to a deadline
        # (condition-based, not a fixed sleep)
        deadline = loop.time() + 60.0
        missing = sorted(acked)
        while missing and loop.time() < deadline:
            try:
                await http(p0, "POST", "/midbulk/_refresh", timeout=5.0)
                still = []
                for doc_id in missing:
                    status, resp = await http(p0, "GET",
                                              f"/midbulk/_doc/{doc_id}",
                                              timeout=5.0)
                    if status != 200:
                        still.append(doc_id)
                missing = still
            except (ConnectionError, OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError):
                pass  # promotion still settling: retry the whole pass
            if missing:
                await asyncio.sleep(0.3)
        assert not missing, f"acked writes lost: {missing[:10]} " \
                            f"({len(missing)}/{len(acked)})"

    asyncio.run(run(scenario))


def test_handshake_rejects_wrong_cluster(tmp_path):
    """A peer with a different cluster name must not join (the
    TransportHandshaker cluster-name check)."""

    async def scenario():
        from opensearch_tpu.transport.tcp import TcpTransport

        [pa, pb] = free_ports(2)
        loop = asyncio.get_running_loop()
        a = TcpTransport("a", "127.0.0.1", pa, {"b": ("127.0.0.1", pb)},
                         loop=loop, cluster_name="one", timeout_ms=2000)
        b = TcpTransport("b", "127.0.0.1", pb, {"a": ("127.0.0.1", pa)},
                         loop=loop, cluster_name="two", timeout_ms=2000)
        await a.start()
        await b.start()
        b.register("b", "ping", lambda s, p: {"pong": True})
        failures: list[Exception] = []
        a.send("a", "b", "ping", {}, on_response=lambda r: failures.append(
            AssertionError("should not connect")), on_failure=failures.append)
        for _ in range(100):
            if failures:
                break
            await asyncio.sleep(0.05)
        assert failures and isinstance(failures[0], (ConnectionError, TimeoutError))
        await a.aclose()
        await b.aclose()

    asyncio.run(scenario())


def test_request_timeout_and_late_response_dropped(tmp_path):
    """Correlation-id timeouts: a slow handler's late response must not fire
    a recycled callback (TransportService timeout semantics). The callback
    fires EXACTLY once (the failure), and the late frame is counted as
    tombstone-dropped — while an unrelated in-flight request on the same
    pipelined connection still resolves normally."""

    async def scenario():
        from opensearch_tpu.transport.base import DeferredResponse
        from opensearch_tpu.transport.tcp import TcpTransport

        [pa, pb] = free_ports(2)
        loop = asyncio.get_running_loop()
        a = TcpTransport("a", "127.0.0.1", pa, {"b": ("127.0.0.1", pb)},
                         loop=loop, timeout_ms=300)
        b = TcpTransport("b", "127.0.0.1", pb, {"a": ("127.0.0.1", pa)},
                         loop=loop)
        await a.start()
        await b.start()
        slow: list[DeferredResponse] = []

        def slow_handler(sender, payload):
            d = DeferredResponse()
            slow.append(d)
            return d

        b.register("b", "slow", slow_handler)
        b.register("b", "fast", lambda s, p: {"ok": True})
        events: list[str] = []
        a.send("a", "b", "slow", {},
               on_response=lambda r: events.append("response"),
               on_failure=lambda e: events.append(type(e).__name__))
        # a healthy request sharing the connection is unaffected
        fast_events: list = []
        a.send("a", "b", "fast", {}, on_response=fast_events.append,
               on_failure=lambda e: fast_events.append(("fail", e)))
        await asyncio.sleep(0.6)      # past the 300ms timeout
        assert events == ["TimeoutError"]
        assert fast_events == [{"ok": True}]
        slow[0].set_result({"late": True})   # now answer — must be dropped
        await asyncio.sleep(0.2)
        assert events == ["TimeoutError"]    # exactly once, never twice
        assert a.stats["late_dropped"] == 1
        await a.aclose()
        await b.aclose()

    asyncio.run(scenario())


def test_lazy_connection_reopens_after_peer_restart(tmp_path):
    """The per-target outbound connection is lazy: when the peer process
    dies, in-flight requests fail, and a RESTARTED peer on the same address
    is reachable again through a fresh dial — no manual reconnect step
    (ClusterConnectionManager re-dial semantics)."""

    async def scenario():
        from opensearch_tpu.transport.tcp import TcpTransport

        [pa, pb] = free_ports(2)
        loop = asyncio.get_running_loop()
        a = TcpTransport("a", "127.0.0.1", pa, {"b": ("127.0.0.1", pb)},
                         loop=loop, timeout_ms=2000)
        b1 = TcpTransport("b", "127.0.0.1", pb, {"a": ("127.0.0.1", pa)},
                          loop=loop)
        await a.start()
        await b1.start()
        b1.register("b", "ping", lambda s, p: {"gen": 1})

        async def rpc():
            fut = loop.create_future()
            a.send("a", "b", "ping", {},
                   on_response=lambda r: fut.done() or fut.set_result(r),
                   on_failure=lambda e: fut.done() or fut.set_result(e))
            return await asyncio.wait_for(fut, 5.0)

        assert (await rpc()) == {"gen": 1}

        # peer dies: the next request fails (connection error or timeout)
        await b1.aclose()
        failed = await rpc()
        assert isinstance(failed, Exception), failed

        # peer restarts on the SAME address: the lazy dial reconnects
        b2 = TcpTransport("b", "127.0.0.1", pb, {"a": ("127.0.0.1", pa)},
                          loop=loop)
        await b2.start()
        b2.register("b", "ping", lambda s, p: {"gen": 2})
        got = None
        for _ in range(20):
            got = await rpc()
            if got == {"gen": 2}:
                break
            await asyncio.sleep(0.1)
        assert got == {"gen": 2}, got
        await a.aclose()
        await b2.aclose()

    asyncio.run(scenario())


@pytest.mark.slow
@pytest.mark.chaos
def test_tcp_elastic_topology_soak(tmp_path):
    """The full elastic reshape on real sockets: a node joins mid-traffic,
    the allocator rebalances onto it, a disk ramp evacuates a
    replica-holder over the high watermark, and a founding member drains
    and departs — with live HTTP writes/searches flowing throughout and
    the invariants-only audit at the end (testing/soak_tcp.py, the same
    runner `scripts/check.sh --soak-tcp` drives)."""
    from opensearch_tpu.testing.soak_tcp import TcpSoak

    async def scenario():
        soak = TcpSoak(tmp_path, seconds=90.0)
        try:
            return await soak.run()
        finally:
            await soak.stop()

    report = asyncio.run(scenario())
    events = [m["event"] for m in report["milestones"]]
    for want in ("join_started", "join_warm", "rebalanced", "disk_ramp",
                 "evacuated", "drain_started", "depart", "reshape_done",
                 "verified"):
        assert want in events, events
    assert report["writes_acked"] > 0
    assert report["searches_ok"] > 0
    assert len(report["members"]) == 3
