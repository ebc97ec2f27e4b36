"""Segment replication over binary transport frames.

index.replication.type=SEGMENT: replicas never index documents — writes
append only to their translog (durability + promotion source); searchable
state arrives as sealed segment bundles the primary publishes after
refresh (checkpoint -> diff -> binary fetch, the
SegmentReplicationTargetService.java:66 / RecoverySourceHandler.java:112
flow). The replica's SegmentBuilder must never run (segments_built == 0),
acked writes must survive primary failover, and a replica that was down
during replication (partition) must catch up via file-based recovery.
"""

from __future__ import annotations

import asyncio
import json

import pytest

from opensearch_tpu.transport.tcp import encode_frame, read_frame
from tests.test_tcp_cluster import TcpCluster, http


def test_binary_frame_roundtrip():
    """The wire codec ships raw bytes out-of-band (no base64)."""

    async def scenario():
        blob = bytes(range(256)) * 100
        frame = encode_frame({"t": "req", "id": 1, "action": "x",
                              "payload": {"a": 1, "_binary": blob}})
        # raw bytes embedded verbatim, not base64 (so ~len(blob) overhead 0)
        assert blob in frame
        reader = asyncio.StreamReader()
        reader.feed_data(frame)
        reader.feed_eof()
        decoded = await read_frame(reader)
        assert decoded["payload"]["a"] == 1
        assert decoded["payload"]["_binary"] == blob

        # plain frames still work
        reader = asyncio.StreamReader()
        reader.feed_data(encode_frame({"t": "res", "id": 2, "payload": {"b": 2}}))
        reader.feed_eof()
        assert (await read_frame(reader))["payload"]["b"] == 2

    asyncio.run(scenario())


def _segrep_cluster(tmp_path, n_docs: int):
    cluster = TcpCluster(tmp_path)

    async def boot():
        await cluster.start()
        await cluster.wait_leader()
        p0 = cluster.http_ports["n0"]
        status, resp = await http(p0, "PUT", "/seg", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 1,
                         "replication": {"type": "SEGMENT"}},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "n": {"type": "long"}}},
        })
        assert status == 200, resp
        await cluster.wait_health(p0, "green")
        nd = "".join(
            json.dumps(x) + "\n"
            for i in range(n_docs)
            for x in ({"index": {"_index": "seg", "_id": f"s{i}"}},
                      {"body": f"token{i % 97} filler words {i}", "n": i})
        )
        status, resp = await http(p0, "POST", "/_bulk?refresh=true", nd)
        assert status == 200 and not resp["errors"], str(resp)[:500]
        return p0

    return cluster, boot


def _find_copies(cluster, index="seg", shard=0):
    primary = replica = None
    for srv in cluster.servers.values():
        sh = srv.node.local_shards.get((index, shard))
        if sh is None:
            continue
        if sh.primary:
            primary = (srv.node.node_id, sh)
        else:
            replica = (srv.node.node_id, sh)
    return primary, replica


async def _wait(pred, timeout_s=15.0, interval=0.1):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    while loop.time() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return False


def test_segrep_replica_consumes_segments_no_reanalysis(tmp_path):
    cluster, boot = _segrep_cluster(tmp_path, n_docs=120)

    async def scenario():
        p0 = await boot()
        primary, replica = _find_copies(cluster)
        assert primary and replica
        _pid, pshard = primary
        _rid, rshard = replica

        # the replica converges to the primary's exact segment set
        ok = await _wait(lambda: (
            rshard.engine.segment_names() == pshard.engine.segment_names()
            and rshard.engine.segment_names()
        ))
        assert ok, (pshard.engine.segment_names(),
                    rshard.engine.segment_names())

        # THE segrep contract: the replica analyzed/built NOTHING — every
        # byte of its searchable state arrived as sealed segment files
        assert rshard.engine.stats.get("segments_built", 0) == 0
        assert pshard.engine.stats.get("segments_built", 0) > 0
        assert rshard.engine._buffer == []

        # replicated segment content is identical (doc order, sources)
        ph = pshard.engine._segments[0][0]
        rh = rshard.engine._segments[0][0]
        assert rh.doc_ids == ph.doc_ids
        assert rh.sources == ph.sources

        # and the replica serves searches from those segments
        snap = rshard.acquire_searcher()
        assert snap.num_docs == 120

        # translog durability on the replica: every acked op is there
        assert rshard.engine.max_seq_no == pshard.engine.max_seq_no

        await cluster.stop()

    asyncio.run(scenario())


def test_segrep_merge_propagates(tmp_path):
    """A force-merge on the primary (segment set SHRINKS) must propagate:
    the replica mirrors the merged set exactly."""
    cluster, boot = _segrep_cluster(tmp_path, n_docs=60)

    async def scenario():
        p0 = await boot()
        # several refreshes -> several segments
        for i in range(3):
            status, _ = await http(
                p0, "PUT", f"/seg/_doc/extra{i}?refresh=true",
                {"body": f"late doc {i}", "n": 1000 + i})
            assert status in (200, 201)
        status, resp = await http(p0, "POST",
                                  "/seg/_forcemerge?max_num_segments=1")
        assert status == 200, resp
        status, _ = await http(p0, "POST", "/seg/_refresh")

        primary, replica = _find_copies(cluster)
        _pid, pshard = primary
        _rid, rshard = replica
        assert len(pshard.engine.segment_names()) == 1
        ok = await _wait(lambda: (
            rshard.engine.segment_names() == pshard.engine.segment_names()
        ))
        assert ok, (pshard.engine.segment_names(),
                    rshard.engine.segment_names())
        assert rshard.engine.stats.get("segments_built", 0) == 0
        await cluster.stop()

    asyncio.run(scenario())


def test_segrep_failover_no_acked_write_loss(tmp_path):
    """Kill the node holding the PRIMARY: the promoted segrep replica must
    serve every acked write (segments + translog-tail replay)."""
    cluster, boot = _segrep_cluster(tmp_path, n_docs=40)

    async def scenario():
        p0 = await boot()
        # extra acked writes WITHOUT refresh: they exist only in translogs
        for i in range(10):
            status, resp = await http(
                p0, "PUT", f"/seg/_doc/tail{i}", {"body": "tail", "n": i})
            assert status in (200, 201) and resp["_shards"]["failed"] == 0

        primary, replica = _find_copies(cluster)
        primary_node_id = primary[0]
        survivor = [n for n in cluster.node_ids if n != primary_node_id][0]
        ps = cluster.http_ports[survivor]

        await cluster.servers[primary_node_id].aclose()
        del cluster.servers[primary_node_id]

        # survivors elect; replica promotes and replays its translog tail
        ok = await _wait(lambda: any(
            s.node.is_leader for s in cluster.servers.values()
        ), timeout_s=60.0)
        assert ok, "no re-election"

        loop = asyncio.get_running_loop()
        deadline = loop.time() + 20.0
        total = -1
        while loop.time() < deadline:
            try:
                await http(ps, "POST", "/seg/_refresh")
                status, resp = await http(
                    ps, "POST", "/seg/_search",
                    {"size": 0, "track_total_hits": True})
                if status == 200:
                    total = resp["hits"]["total"]["value"]
                    if total == 50:
                        break
            except (ConnectionError, OSError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0.25)
        assert total == 50, f"acked writes lost after failover: {total}/50"
        status, resp = await http(ps, "GET", "/seg/_doc/tail7")
        assert status == 200 and resp["_source"]["n"] == 7
        await cluster.stop()

    asyncio.run(scenario())


def test_segrep_partitioned_replica_catches_up(tmp_path):
    """Replica down during replication: on return it re-recovers the shard
    FILE-BASED (segments as bytes, zero re-analysis) and catches up."""
    cluster, boot = _segrep_cluster(tmp_path, n_docs=50)

    async def scenario():
        p0 = await boot()
        primary, replica = _find_copies(cluster)
        replica_node_id = replica[0]

        # partition: the replica's node goes dark
        await cluster.servers[replica_node_id].aclose()
        del cluster.servers[replica_node_id]

        # writes continue against the remaining copies (replica evicted)
        for i in range(20):
            status, resp = await http(
                p0, "PUT", f"/seg/_doc/during{i}?refresh=true",
                {"body": f"while away {i}", "n": 2000 + i})
            assert status in (200, 201), resp

        # the node returns (same data path — it kept its stale copy)
        from opensearch_tpu.server import ClusterServer

        srv = ClusterServer(
            replica_node_id, cluster.tmp_path / replica_node_id, "127.0.0.1",
            cluster.seeds[replica_node_id][1],
            cluster.http_ports[replica_node_id], cluster.seeds,
            loop=asyncio.get_running_loop(),
        )
        cluster.servers[replica_node_id] = srv
        await srv.start(bootstrap=cluster.node_ids)

        # the replica shard reappears and converges to the primary's set
        def caught_up() -> bool:
            pr, rp = _find_copies(cluster)
            if not pr or not rp:
                return False
            _, psh = pr
            _, rsh = rp
            return (rsh.engine.segment_names() == psh.engine.segment_names()
                    and rsh.engine.max_seq_no >= psh.engine.max_seq_no)

        ok = await _wait(caught_up, timeout_s=60.0)
        pr, rp = _find_copies(cluster)
        assert ok, (pr and pr[1].engine.segment_names(),
                    rp and rp[1].engine.segment_names())

        # steady-state recovery moved segment BYTES: at most the one
        # crash-recovery bootstrap build (translog replay on reboot) ran
        # locally — never a rebuild of replicated content. The zero-build
        # contract for a fresh replica is asserted in
        # test_segrep_replica_consumes_segments_no_reanalysis.
        _, rsh = rp
        assert rsh.engine.stats.get("segments_built", 0) <= 1
        snap = rsh.acquire_searcher()
        assert snap.num_docs == 70
        await cluster.stop()

    asyncio.run(scenario())
