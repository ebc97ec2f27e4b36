"""Dynamic cluster settings + allocation depth
(ClusterSettings.java:205 two-phase apply, DiskThresholdDecider,
AwarenessAllocationDecider, BalancedShardsAllocator rebalancing)."""

from __future__ import annotations

import asyncio
import json

import pytest

from opensearch_tpu.cluster.allocation import AllocationSettings, reroute
from opensearch_tpu.cluster.state import (
    ClusterState,
    DiscoveryNode,
    IndexMeta,
    ShardRoutingEntry,
    VotingConfiguration,
)
from tests.test_tcp_cluster import TcpCluster, http


def _state(nodes, indices, routing=()):
    return ClusterState(
        term=1, version=1,
        nodes={n.node_id: n for n in nodes},
        indices={m.name: m for m in indices},
        routing=tuple(routing),
        last_committed_config=VotingConfiguration.of(*[n.node_id for n in nodes]),
        last_accepted_config=VotingConfiguration.of(*[n.node_id for n in nodes]),
    )


# -- unit: deciders ----------------------------------------------------------


def test_disk_low_watermark_blocks_new_allocation():
    nodes = [DiscoveryNode("a"), DiscoveryNode("b")]
    state = _state(nodes, [IndexMeta("i", 2, 0)])
    settings = AllocationSettings(disk_usage={"a": 92.0, "b": 10.0})
    out = reroute(state, settings)
    assert all(r.node_id == "b" for r in out.routing if r.node_id), out.routing


def test_disk_high_watermark_drains_replicas():
    """Evacuation is a real RELOCATION: the full node's replica keeps
    serving (RELOCATING) while the shadow target recovers; the shard-
    started swap moves it off — never a moment with fewer serving
    copies."""
    from opensearch_tpu.cluster.allocation import mark_shard_started

    nodes = [DiscoveryNode("a"), DiscoveryNode("b"), DiscoveryNode("c")]
    routing = [
        ShardRoutingEntry("i", 0, "a", True, "STARTED"),
        ShardRoutingEntry("i", 0, "b", False, "STARTED"),
    ]
    state = _state(nodes, [IndexMeta("i", 1, 1)], routing)
    settings = AllocationSettings(disk_usage={"b": 95.0})
    out = reroute(state, settings)
    # mid-move: source still serving, shadow target initializing on c
    source = next(r for r in out.routing if r.state == "RELOCATING")
    assert source.node_id == "b" and source.relocating_node == "c"
    shadow = next(r for r in out.routing if r.is_relocation_target)
    assert shadow.node_id == "c"
    # target catches up -> atomic swap completes the evacuation
    done = mark_shard_started(out, "i", 0, "c")
    replica = next(r for r in done.routing if not r.primary)
    assert replica.node_id == "c"          # drained off the full node
    assert replica.state == "STARTED"
    primary = next(r for r in done.routing if r.primary)
    assert primary.node_id == "a"          # primaries stay put
    # stable: another reroute with the same disk picture changes nothing
    again = reroute(done, settings)
    assert set(again.routing) == set(done.routing)


def test_cluster_exclude_filter_drains_node():
    """cluster.routing.allocation.exclude._name (graceful decommission):
    replicas relocate off; a primary hands its role to a started replica
    elsewhere, then the demoted copy moves; iterating publications
    empties the node."""
    from opensearch_tpu.cluster.allocation import mark_shard_started

    nodes = [DiscoveryNode("a"), DiscoveryNode("b"), DiscoveryNode("c")]
    routing = [
        ShardRoutingEntry("i", 0, "a", True, "STARTED"),
        ShardRoutingEntry("i", 0, "b", False, "STARTED"),
        ShardRoutingEntry("i", 1, "b", True, "STARTED"),
        ShardRoutingEntry("i", 1, "c", False, "STARTED"),
    ]
    state = _state(nodes, [IndexMeta("i", 2, 1)], routing)
    state = state.with_(settings={
        "cluster.routing.allocation.exclude._name": "b",
    })
    for _ in range(8):
        state = reroute(state, AllocationSettings.from_cluster(state))
        for r in [r for r in state.routing if r.state == "INITIALIZING"]:
            state = mark_shard_started(state, r.index, r.shard, r.node_id)
    assert not any(r.node_id == "b" for r in state.routing), state.routing
    assert all(r.state == "STARTED" for r in state.routing)
    # both shards still have primary + replica
    for s in (0, 1):
        copies = [r for r in state.routing if r.shard == s]
        assert len(copies) == 2 and sum(r.primary for r in copies) == 1


def test_drain_refuses_to_drop_sole_started_copy():
    """Decommission of the node holding the ONLY started copy of a shard
    (zero replicas): the drain must refuse — the copy stays put rather
    than being dropped (never trade acked writes for a clean exit).
    With no staying candidate the primary cannot swap or move."""
    nodes = [DiscoveryNode("a"), DiscoveryNode("b")]
    routing = [ShardRoutingEntry("solo", 0, "b", True, "STARTED")]
    state = _state(nodes, [IndexMeta("solo", 1, 0)], routing)
    state = state.with_(settings={
        "cluster.routing.allocation.exclude._name": "b",
    })
    for _ in range(4):
        state = reroute(state, AllocationSettings.from_cluster(state))
    entry = next(r for r in state.routing)
    assert entry.node_id == "b" and entry.state == "STARTED", state.routing


def test_awareness_spreads_copies_across_zones():
    nodes = [
        DiscoveryNode("a1", attrs=(("zone", "z1"),)),
        DiscoveryNode("a2", attrs=(("zone", "z1"),)),
        DiscoveryNode("b1", attrs=(("zone", "z2"),)),
    ]
    state = _state(nodes, [IndexMeta("i", 1, 1)])
    state = state.with_(settings={
        "cluster.routing.allocation.awareness.attributes": "zone",
    })
    out = reroute(state, AllocationSettings.from_cluster(state))
    zones = {
        dict(state.nodes[r.node_id].attrs)["zone"]
        for r in out.routing if r.node_id
    }
    assert zones == {"z1", "z2"}, out.routing


def test_rebalance_converges_to_even_spread():
    nodes = [DiscoveryNode("a"), DiscoveryNode("b"), DiscoveryNode("c")]
    # all six copies piled on a+b (as if c just joined)
    routing = []
    for s in range(3):
        routing.append(ShardRoutingEntry("i", s, "a", True, "STARTED"))
        routing.append(ShardRoutingEntry("i", s, "b", False, "STARTED"))
    state = _state(nodes, [IndexMeta("i", 3, 1)], routing)
    settings = AllocationSettings()
    # each round RELOCATES one replica; completing a relocation means the
    # target reports shard-started (mark_shard_started performs the atomic
    # routing swap) — iterate as successive publications do
    from opensearch_tpu.cluster.allocation import mark_shard_started

    for _ in range(6):
        state = reroute(state, settings)
        for r in [r for r in state.routing if r.state == "INITIALIZING"]:
            state = mark_shard_started(state, r.index, r.shard, r.node_id)
    assert not any(r.state == "RELOCATING" for r in state.routing)
    loads = {n.node_id: 0 for n in nodes}
    for r in state.routing:
        loads[r.node_id] += 1
    assert max(loads.values()) - min(loads.values()) <= 1, loads


# -- cluster API -------------------------------------------------------------


def test_cluster_settings_api_and_dynamic_apply(tmp_path):
    cluster = TcpCluster(tmp_path)

    async def scenario():
        await cluster.start()
        await cluster.wait_leader()
        p0 = cluster.http_ports["n0"]

        # reject unknown settings
        status, resp = await http(p0, "PUT", "/_cluster/settings",
                                  {"persistent": {"bogus.key": 1}})
        assert status == 400, resp
        # reject invalid values
        status, resp = await http(p0, "PUT", "/_cluster/settings", {
            "persistent": {"cluster.routing.allocation.disk.watermark.low":
                           "150%"},
        })
        assert status == 400, resp

        # accept + read back through ANOTHER node (state-replicated)
        status, resp = await http(p0, "PUT", "/_cluster/settings", {
            "persistent": {
                "cluster.routing.allocation.disk.watermark.low": "70%",
            },
            "transient": {"search.max_buckets": 1000},
        })
        assert status == 200 and resp["acknowledged"], resp

        async def settings_replicated():
            for _ in range(100):
                s, r = await http(cluster.http_ports["n2"], "GET",
                                  "/_cluster/settings?flat_settings=true")
                if (s == 200 and r["persistent"].get(
                        "cluster.routing.allocation.disk.watermark.low")
                        == "70%" and r["transient"].get(
                        "search.max_buckets") == "1000"):
                    return True
                await asyncio.sleep(0.1)
            return False

        assert await settings_replicated()

        # null deletes
        status, resp = await http(p0, "PUT", "/_cluster/settings", {
            "transient": {"search.max_buckets": None},
        })
        assert status == 200
        for _ in range(100):
            s, r = await http(p0, "GET",
                              "/_cluster/settings?flat_settings=true")
            if "search.max_buckets" not in r["transient"]:
                break
            await asyncio.sleep(0.1)
        assert "search.max_buckets" not in r["transient"]

        await cluster.stop()

    asyncio.run(scenario())


def test_persistent_survives_restart_transient_does_not(tmp_path):
    cluster = TcpCluster(tmp_path)

    async def phase1():
        await cluster.start()
        await cluster.wait_leader()
        p0 = cluster.http_ports["n0"]
        status, resp = await http(p0, "PUT", "/_cluster/settings", {
            "persistent": {
                "cluster.routing.allocation.node_concurrent_recoveries": 7,
            },
            "transient": {"search.max_buckets": 123},
        })
        assert status == 200, resp
        # wait for replication to all nodes before stopping
        for port in cluster.http_ports.values():
            for _ in range(100):
                s, r = await http(port, "GET", "/_cluster/settings")
                if s == 200 and r["persistent"]:
                    break
                await asyncio.sleep(0.1)
        await cluster.stop()

    asyncio.run(phase1())

    async def phase2():
        cluster.servers.clear()
        await cluster.start()
        await cluster.wait_leader()
        p0 = cluster.http_ports["n1"]
        status, r = await http(p0, "GET",
                               "/_cluster/settings?flat_settings=true")
        assert status == 200
        assert r["persistent"].get(
            "cluster.routing.allocation.node_concurrent_recoveries") == "7"
        assert r["transient"] == {}        # dropped at restart
        await cluster.stop()

    asyncio.run(phase2())


def test_disk_watermark_drains_in_live_cluster(tmp_path):
    cluster = TcpCluster(tmp_path)

    async def scenario():
        await cluster.start()
        await cluster.wait_leader()
        p0 = cluster.http_ports["n0"]
        status, resp = await http(p0, "PUT", "/disky", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 1},
        })
        assert status == 200, resp
        await cluster.wait_health(p0, "green")
        replica_node = next(
            r.node_id for r in
            next(iter(cluster.servers.values())).node.applied_state.routing
            if not r.primary
        )
        # the replica's node reports a full disk; the next publication
        # (triggered by the settings change) drains it
        cluster.servers[replica_node].node.disk_usage_pct = 97.0
        await asyncio.sleep(1.0)   # let a heartbeat carry the fs stats
        status, resp = await http(p0, "PUT", "/_cluster/settings", {
            "persistent": {
                "cluster.routing.allocation.disk.watermark.high": "90%",
            },
        })
        assert status == 200, resp

        async def drained():
            loop = asyncio.get_running_loop()
            deadline = loop.time() + 20.0
            while loop.time() < deadline:
                state = next(iter(cluster.servers.values())).node.applied_state
                rep = next((r for r in state.routing if not r.primary), None)
                if rep is not None and rep.node_id not in (None, replica_node):
                    return True
                await asyncio.sleep(0.2)
            return False

        assert await drained(), "replica never drained off the full node"
        await cluster.stop()

    asyncio.run(scenario())
