"""Durable cluster state: full-cluster stop/start retains metadata + data.

PersistedState (term + accepted state) is
write-ahead persisted per node (gateway.GatewayStore — the
PersistedClusterStateService:137 analog); on reboot the node recovers the
state BEFORE elections (no double vote in an old term) and recreates its
local shards, whose data replays from translog/commits.
"""

from __future__ import annotations

import asyncio
import json

import pytest

from opensearch_tpu.cluster.state import ClusterState
from opensearch_tpu.gateway import GatewayStore
from tests.test_tcp_cluster import TcpCluster, http


def test_gateway_store_roundtrip(tmp_path):
    store = GatewayStore(tmp_path / "_state")
    assert store.load() is None
    state = ClusterState().with_(term=3, version=17)
    store.save(3, state)
    term, recovered = store.load()
    assert term == 3
    assert recovered.term == 3 and recovered.version == 17
    # overwrite is atomic-replace, not append
    store.save(4, state.with_(version=18))
    term, recovered = store.load()
    assert (term, recovered.version) == (4, 18)


def test_persisted_state_write_ahead(tmp_path):
    """Term bumps and accepts hit disk BEFORE memory — the double-vote
    guard (CoordinationState.handleStartJoin persists the term before the
    join leaves the node)."""
    from opensearch_tpu.cluster.coordination import (
        CoordinationState,
        PersistedState,
        StartJoinRequest,
    )

    store = GatewayStore(tmp_path / "_state")
    coord = CoordinationState("n0", PersistedState(store=store))
    coord.handle_start_join(StartJoinRequest(source_id="n1", term=5))
    # simulate crash: reload from disk only
    term, state = store.load()
    assert term == 5
    coord2 = CoordinationState("n0", PersistedState(term, state, store=store))
    with pytest.raises(Exception, match="not greater"):
        # a second start-join for the same term must be rejected after the
        # reboot — the vote in term 5 is already spent
        coord2.handle_start_join(StartJoinRequest(source_id="n2", term=5))


def test_full_cluster_restart_retains_data(tmp_path):
    cluster = TcpCluster(tmp_path)

    async def phase1():
        await cluster.start()
        leader = await cluster.wait_leader()
        p0 = cluster.http_ports["n0"]
        status, resp = await http(p0, "PUT", "/persist", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"n": {"type": "long"},
                                        "tag": {"type": "keyword"}}},
        })
        assert status == 200, resp
        await cluster.wait_health(p0, "green")
        nd = "".join(
            json.dumps(x) + "\n"
            for i in range(30)
            for x in ({"index": {"_index": "persist", "_id": f"p{i}"}},
                      {"n": i, "tag": f"t{i % 3}"})
        )
        status, resp = await http(p0, "POST", "/_bulk?refresh=true", nd)
        assert status == 200 and not resp["errors"], resp
        # flush so segments are committed; translog covers the rest either way
        await http(p0, "POST", "/persist/_flush")
        # FULL cluster stop
        await cluster.stop()

    asyncio.run(phase1())

    # every node persisted a non-trivial term + state
    for nid in cluster.node_ids:
        store = GatewayStore(tmp_path / nid / "_state")
        loaded = store.load()
        assert loaded is not None
        term, state = loaded
        assert term >= 1
        assert "persist" in state.indices

    async def phase2():
        cluster.servers.clear()
        await cluster.start()          # same data paths + ports, fresh procs
        await cluster.wait_leader()
        p1 = cluster.http_ports["n1"]
        await cluster.wait_health(p1, "green", timeout_s=30.0)

        # mappings survived
        status, resp = await http(p1, "GET", "/persist/_mapping")
        assert status == 200, resp
        props = resp["persist"]["mappings"]["properties"]
        assert props["n"]["type"] == "long"

        # every acked doc survived, searchable through any node
        await http(p1, "POST", "/persist/_refresh")
        for nid in cluster.node_ids:
            status, resp = await http(
                cluster.http_ports[nid], "POST", "/persist/_search",
                {"query": {"match_all": {}}, "size": 0,
                 "track_total_hits": True},
            )
            assert status == 200, resp
            assert resp["hits"]["total"]["value"] == 30, (nid, resp)
        status, resp = await http(p1, "GET", "/persist/_doc/p17")
        assert status == 200 and resp["_source"]["n"] == 17

        # and the cluster still takes writes in a FRESH term
        status, resp = await http(p1, "PUT", "/persist/_doc/p_new?refresh=true",
                                  {"n": 99, "tag": "t9"})
        assert status in (200, 201) and resp["_shards"]["failed"] == 0, resp
        await cluster.stop()

    asyncio.run(phase2())
