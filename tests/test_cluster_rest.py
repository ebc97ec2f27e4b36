"""The FULL REST surface against a real 3-node TCP cluster.

The bar: cluster mode serves search with aggregations,
scroll, PIT, doc CRUD (incl. update/mget/count/msearch) and the stats/cat
surface through ANY node, via the same 128-route trie router the
single-node server uses (one RestController + one action registry,
rest/RestController.java:285). Aggregation results must be EQUAL to a
single-node TpuNode over the same documents (the cross-node partial/reduce
layer is exact).
"""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from tests.test_tcp_cluster import TcpCluster, http


DOCS = []
_rng = np.random.default_rng(12)
for i in range(60):
    DOCS.append({
        "title": f"doc number {i} " + ("alpha" if i % 3 == 0 else "beta"),
        "n": i,
        "price": round(float(_rng.uniform(1, 100)), 2),
        "tag": ["red", "green", "blue"][i % 3],
    })


@pytest.fixture(scope="module")
def cluster_ports(tmp_path_factory):
    """One 3-node cluster for the whole module (boot cost amortized)."""
    tmp = tmp_path_factory.mktemp("crest")
    cluster = TcpCluster(tmp)
    loop = asyncio.new_event_loop()

    async def boot():
        await cluster.start()
        await cluster.wait_leader()
        status, resp = await http(
            cluster.http_ports["n0"], "PUT", "/items",
            {"settings": {"number_of_shards": 3, "number_of_replicas": 1},
             "mappings": {"properties": {
                 "title": {"type": "text"},
                 "n": {"type": "long"},
                 "price": {"type": "float"},
                 "tag": {"type": "keyword"},
             }}},
        )
        assert status == 200, resp
        await cluster.wait_health(cluster.http_ports["n0"], "green")
        nd = "".join(
            json.dumps(x) + "\n"
            for i, d in enumerate(DOCS)
            for x in ({"index": {"_index": "items", "_id": f"i{i}"}}, d)
        )
        status, resp = await http(
            cluster.http_ports["n1"], "POST", "/_bulk?refresh=true", nd)
        assert status == 200 and not resp["errors"], resp

    loop.run_until_complete(boot())
    ports = dict(cluster.http_ports)

    yield loop, ports

    loop.run_until_complete(cluster.stop())
    loop.close()


def _req(loop, port, method, path, body=None):
    return loop.run_until_complete(http(port, method, path, body))


def _single_node_reference(tmp_path):
    from opensearch_tpu.node import TpuNode

    node = TpuNode(tmp_path / "ref")
    node.create_index("items", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "title": {"type": "text"}, "n": {"type": "long"},
            "price": {"type": "float"}, "tag": {"type": "keyword"},
        }},
    })
    node.bulk([
        ("index", {"_index": "items", "_id": f"i{i}"}, d)
        for i, d in enumerate(DOCS)
    ], refresh=True)
    return node


def test_search_through_every_node(cluster_ports):
    loop, ports = cluster_ports
    for port in ports.values():
        status, resp = _req(loop, port, "POST", "/items/_search",
                            {"query": {"match": {"title": "alpha"}},
                             "size": 30})
        assert status == 200, resp
        assert resp["hits"]["total"]["value"] == 20
        for h in resp["hits"]["hits"]:
            assert "alpha" in h["_source"]["title"]


def test_aggregations_match_single_node(cluster_ports, tmp_path):
    loop, ports = cluster_ports
    ref = _single_node_reference(tmp_path)
    body = {
        "size": 0,
        "aggs": {
            "tags": {"terms": {"field": "tag"},
                     "aggs": {"avg_price": {"avg": {"field": "price"}},
                              "max_n": {"max": {"field": "n"}}}},
            "price_stats": {"stats": {"field": "price"}},
            "price_ext": {"extended_stats": {"field": "price"}},
            "distinct_tags": {"cardinality": {"field": "tag"}},
            "pctl": {"percentiles": {"field": "price",
                                     "percents": [50.0, 95.0]}},
            "n_hist": {"histogram": {"field": "n", "interval": 20}},
            "cheap": {"filter": {"range": {"price": {"lt": 50}}},
                      "aggs": {"cnt": {"value_count": {"field": "n"}}}},
        },
    }
    want = ref.search("items", json.loads(json.dumps(body)))["aggregations"]
    status, resp = _req(loop, ports["n2"], "POST", "/items/_search", body)
    assert status == 200, resp
    got = resp["aggregations"]

    assert got["distinct_tags"]["value"] == want["distinct_tags"]["value"]
    assert got["price_stats"] == pytest.approx(want["price_stats"])
    for k in ("count", "avg", "sum", "variance", "std_deviation"):
        assert got["price_ext"][k] == pytest.approx(want["price_ext"][k])
    assert got["pctl"]["values"] == pytest.approx(want["pctl"]["values"])
    assert [b["key"] for b in got["n_hist"]["buckets"]] == \
           [b["key"] for b in want["n_hist"]["buckets"]]
    assert [b["doc_count"] for b in got["n_hist"]["buckets"]] == \
           [b["doc_count"] for b in want["n_hist"]["buckets"]]
    assert got["cheap"]["doc_count"] == want["cheap"]["doc_count"]
    assert got["cheap"]["cnt"]["value"] == want["cheap"]["cnt"]["value"]
    gt = {b["key"]: b for b in got["tags"]["buckets"]}
    wt = {b["key"]: b for b in want["tags"]["buckets"]}
    assert set(gt) == set(wt)
    for key in wt:
        assert gt[key]["doc_count"] == wt[key]["doc_count"]
        assert gt[key]["avg_price"]["value"] == \
            pytest.approx(wt[key]["avg_price"]["value"])
        assert gt[key]["max_n"]["value"] == wt[key]["max_n"]["value"]


def test_sorted_search_and_paging(cluster_ports):
    loop, ports = cluster_ports
    seen = []
    for from_ in (0, 20, 40):
        status, resp = _req(loop, ports["n0"], "POST", "/items/_search", {
            "query": {"match_all": {}},
            "sort": [{"n": "desc"}], "from": from_, "size": 20,
        })
        assert status == 200, resp
        seen.extend(h["_source"]["n"] for h in resp["hits"]["hits"])
    assert seen == list(range(59, -1, -1))


def test_scroll_through_cluster(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n1"], "POST",
                        "/items/_search?scroll=1m",
                        {"query": {"match_all": {}},
                         "sort": [{"n": "asc"}], "size": 25})
    assert status == 200, resp
    scroll_id = resp["_scroll_id"]
    collected = [h["_source"]["n"] for h in resp["hits"]["hits"]]
    while True:
        status, resp = _req(loop, ports["n1"], "POST", "/_search/scroll",
                            {"scroll_id": scroll_id, "scroll": "1m"})
        assert status == 200, resp
        page = [h["_source"]["n"] for h in resp["hits"]["hits"]]
        if not page:
            break
        collected.extend(page)
        scroll_id = resp["_scroll_id"]
    assert collected == list(range(60))
    status, resp = _req(loop, ports["n1"], "DELETE", "/_search/scroll",
                        {"scroll_id": [scroll_id]})
    assert status == 200 and resp["succeeded"]


def test_pit_through_cluster(cluster_ports):
    loop, ports = cluster_ports
    status, pit = _req(loop, ports["n2"], "POST",
                       "/items/_search/point_in_time?keep_alive=1m")
    assert status == 200, pit
    pit_id = pit["pit_id"]

    # writes after the PIT must be invisible to PIT searches
    status, resp = _req(loop, ports["n0"], "PUT",
                        "/items/_doc/late?refresh=true", {
                            "title": "late alpha", "n": 999,
                            "price": 1.0, "tag": "red"})
    assert status in (200, 201), resp
    try:
        status, resp = _req(loop, ports["n2"], "POST", "/_search", {
            "pit": {"id": pit_id},
            "query": {"match_all": {}}, "size": 0,
            "track_total_hits": True,
        })
        assert status == 200, resp
        assert resp["hits"]["total"]["value"] == 60  # not 61
        status, resp = _req(loop, ports["n2"], "POST", "/_search", {
            "query": {"match_all": {}}, "size": 0, "track_total_hits": True,
        })
        assert resp["hits"]["total"]["value"] == 61
        status, resp = _req(loop, ports["n2"], "DELETE",
                            "/_search/point_in_time", {"pit_id": pit_id})
        assert status == 200 and resp["pits"][0]["successful"]
    finally:
        _req(loop, ports["n0"], "DELETE", "/items/_doc/late")
        _req(loop, ports["n0"], "POST", "/items/_refresh")


def test_update_mget_count_msearch(cluster_ports):
    loop, ports = cluster_ports
    # update via doc merge
    status, resp = _req(loop, ports["n0"], "POST", "/items/_update/i3",
                        {"doc": {"price": 42.5}})
    assert status == 200 and resp["result"] == "updated", resp
    status, resp = _req(loop, ports["n1"], "GET", "/items/_doc/i3")
    assert status == 200 and resp["_source"]["price"] == 42.5

    # mget across nodes
    status, resp = _req(loop, ports["n2"], "POST", "/_mget",
                        {"docs": [{"_index": "items", "_id": "i1"},
                                  {"_index": "items", "_id": "i2"}]})
    assert status == 200
    assert [d["_source"]["n"] for d in resp["docs"]] == [1, 2]

    # count
    status, resp = _req(loop, ports["n0"], "POST", "/items/_count",
                        {"query": {"term": {"tag": "red"}}})
    assert status == 200 and resp["count"] == 20

    # msearch NDJSON
    nd = (json.dumps({"index": "items"}) + "\n"
          + json.dumps({"query": {"term": {"tag": "red"}}, "size": 0}) + "\n"
          + json.dumps({"index": "items"}) + "\n"
          + json.dumps({"query": {"term": {"tag": "blue"}}, "size": 0}) + "\n")
    status, resp = _req(loop, ports["n1"], "POST", "/_msearch", nd)
    assert status == 200
    assert [r["hits"]["total"]["value"] for r in resp["responses"]] == [20, 20]


def test_stats_and_cat_through_cluster(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "GET", "/items/_stats")
    assert status == 200, resp
    assert resp["_all"]["primaries"]["docs"]["count"] == 60
    status, resp = _req(loop, ports["n1"], "GET", "/_cat/health?format=json")
    assert status == 200 and resp[0]["status"] in ("green", "yellow")
    status, resp = _req(loop, ports["n2"], "GET", "/_cluster/health")
    assert status == 200 and resp["number_of_nodes"] == 3


def test_recovery_apis_through_cluster(cluster_ports):
    """GET /{index}/_recovery and /_cat/recovery render the REAL recovery
    records aggregated from every node: the 3-shard/1-replica fixture index
    ran 3 store bootstraps (primaries) + 3 peer recoveries (replicas)."""
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "GET", "/items/_recovery")
    assert status == 200, resp
    shards = resp["items"]["shards"]
    assert len(shards) >= 6, shards
    types = {s["type"] for s in shards}
    assert "PEER" in types, types
    assert types & {"EMPTY_STORE", "EXISTING_STORE"}, types
    assert all(s["stage"] == "DONE" for s in shards), shards
    peer = next(s for s in shards if s["type"] == "PEER")
    assert peer["source"]["id"] and peer["target"]["id"]
    assert peer["translog"]["recovered"] == peer["translog"]["total"]

    status, rows = _req(loop, ports["n1"], "GET",
                        "/_cat/recovery?format=json")
    assert status == 200, rows
    assert any(r["type"] == "peer" and r["stage"] == "done" for r in rows), \
        rows
    assert all(r["bytes_percent"] == "100.0%" or r["stage"] != "done"
               for r in rows), rows

    # active_only filters the finished ones away
    status, resp = _req(loop, ports["n2"], "GET",
                        "/items/_recovery?active_only=true")
    assert status == 200
    assert all(not e["shards"] for e in resp.values()), resp


def test_errors_through_cluster(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "POST", "/missing/_search",
                        {"query": {"match_all": {}}})
    assert status == 404, resp
    status, resp = _req(loop, ports["n0"], "GET", "/items/_doc/nope")
    assert status == 404
    # unsupported-in-cluster shapes fail loudly, not wrongly
    status, resp = _req(loop, ports["n0"], "POST", "/items/_search",
                        {"size": 0, "aggs": {"x": {"top_hits": {"size": 1}}}})
    assert status == 400, resp


def test_pit_search_with_aggregations(cluster_ports):
    """PIT searches must carry aggregations (the ctx-search path must not
    drop them — review finding r3)."""
    loop, ports = cluster_ports
    status, pit = _req(loop, ports["n0"], "POST",
                       "/items/_search/point_in_time?keep_alive=1m")
    assert status == 200, pit
    try:
        status, resp = _req(loop, ports["n1"], "POST", "/_search", {
            "pit": {"id": pit["pit_id"]},
            "size": 0,
            "aggs": {"avg_n": {"avg": {"field": "n"}},
                     "tags": {"terms": {"field": "tag"}}},
        })
        assert status == 200, resp
        assert resp["aggregations"]["avg_n"]["value"] == pytest.approx(29.5)
        assert sum(b["doc_count"]
                   for b in resp["aggregations"]["tags"]["buckets"]) == 60
    finally:
        _req(loop, ports["n0"], "DELETE", "/_search/point_in_time",
             {"pit_id": pit["pit_id"]})


def test_histogram_gap_fill_across_nodes(cluster_ports):
    """min_doc_count=0 histograms must be contiguous after the cross-node
    merge even when nodes hold disjoint key ranges."""
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "POST", "/items/_search", {
        "size": 0,
        "aggs": {"h": {"histogram": {"field": "n", "interval": 5,
                                     "min_doc_count": 0}}},
    })
    assert status == 200, resp
    keys = [b["key"] for b in resp["aggregations"]["h"]["buckets"]]
    assert keys == [float(k) for k in range(0, 60, 5)]


def test_scroll_rejects_from(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "POST",
                        "/items/_search?scroll=1m",
                        {"query": {"match_all": {}}, "from": 5, "size": 5})
    assert status == 400, resp


def test_flush_missing_index_404(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "POST", "/nope_such/_flush")
    assert status == 404, resp


def test_pipeline_param_rejected_loudly(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "PUT",
                        "/items/_doc/px?pipeline=p1", {"n": 1})
    assert status == 400, resp
    status, resp = _req(loop, ports["n0"], "GET", "/_ingest/pipeline")
    assert status == 400, resp


def test_expired_scroll_context_is_gone(cluster_ports):
    import time

    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "POST",
                        "/items/_search?scroll=1s",
                        {"query": {"match_all": {}}, "size": 5})
    assert status == 200, resp
    sid = resp["_scroll_id"]
    time.sleep(1.6)
    status, resp = _req(loop, ports["n0"], "POST", "/_search/scroll",
                        {"scroll_id": sid})
    assert status == 404, resp


def test_flush_and_forcemerge_through_cluster(cluster_ports):
    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "POST", "/items/_flush")
    assert status == 200, resp
    status, resp = _req(loop, ports["n1"], "POST",
                        "/items/_forcemerge?max_num_segments=1")
    assert status == 200, resp
    status, resp = _req(loop, ports["n2"], "POST", "/items/_search",
                        {"query": {"match_all": {}}, "size": 0,
                         "track_total_hits": True})
    assert status == 200 and resp["hits"]["total"]["value"] == 60


# -- ISSUE 8: the closed telemetry loop, live over REST ---------------------


async def _http_text(port: int, path: str, timeout: float = 10.0) -> str:
    """Raw-text GET (the prometheus exposition is not JSON)."""

    async def _exchange():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            writer.write((f"GET {path} HTTP/1.1\r\nhost: x\r\n"
                          f"content-length: 0\r\n\r\n").encode())
            await writer.drain()
            await reader.readline()
            length = 0
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.decode().partition(":")
                if k.strip().lower() == "content-length":
                    length = int(v)
            return (await reader.readexactly(length)).decode()
        finally:
            writer.close()

    return await asyncio.wait_for(_exchange(), timeout)


def test_telemetry_loop_closes_over_rest(cluster_ports):
    """Acceptance: dynamic settings turn on the file exporter with a 0ms
    slow threshold, a search's trace is (a) tail-kept and present in the
    OTLP-JSON export with a coordinator->node->reduce tree, (b) reachable
    from the Prometheus exemplar on its latency bucket, and (c) visible in
    ONE cluster-wide _nodes/stats response carrying every node's ring."""
    from pathlib import Path

    from opensearch_tpu.telemetry.export import parse_otlp

    loop, ports = cluster_ports
    status, resp = _req(loop, ports["n0"], "PUT", "/_cluster/settings", {
        "persistent": {"telemetry": {"tracing": {
            "exporter": "file", "slow_threshold_ms": "0ms",
            "sample_ratio": 0.0}}},
    })
    assert status == 200, resp
    # a query through n0: with threshold 0ms every trace counts as slow
    status, resp = _req(loop, ports["n0"], "POST", "/items/_search",
                        {"query": {"match": {"title": "alpha"}}})
    assert status == 200 and resp["hits"]["hits"], resp

    # (c) ONE cluster-wide _nodes/stats with every node's ring + exporter
    status, stats = _req(loop, ports["n1"], "GET", "/_nodes/stats")
    assert status == 200, stats
    assert stats["_nodes"]["successful"] == 3, stats["_nodes"]
    assert set(stats["nodes"]) == {"n0", "n1", "n2"}
    for nid, entry in stats["nodes"].items():
        assert "spans" in entry["telemetry"], nid
        assert entry["telemetry"]["exporter"]["mode"] == "file", nid
    coord_spans = [s for s in stats["nodes"]["n0"]["telemetry"]["spans"]
                   if s["name"] == "search.coordinator"]
    assert coord_spans, "coordinator span missing from n0's ring"
    trace_id = coord_spans[-1]["trace_id"]

    # (a) the trace was tail-kept and exported as OTLP-JSON with the tree
    exporter_stats = stats["nodes"]["n0"]["telemetry"]["exporter"]
    assert exporter_stats["traces_kept_slow"] >= 1, exporter_stats
    export_path = Path(exporter_stats["sink"]["path"])
    assert export_path.exists(), export_path
    # the exporter worker drains asynchronously: poll briefly
    import time as _time

    exported = []
    for _ in range(40):
        exported = [s for line in export_path.read_text().splitlines()
                    for s in parse_otlp(json.loads(line))
                    if s.trace_id == trace_id]
        if any(s.name == "search.coordinator" for s in exported):
            break
        _time.sleep(0.05)
    names = {s.name for s in exported}
    assert "search.coordinator" in names, names
    assert "search.reduce" in names, names
    by_id = {s.span_id: s for s in exported}
    (root,) = [s for s in exported
               if s.parent_id is None or s.parent_id not in by_id]
    # the REST layer's http_request span roots the tree; the coordinator
    # and reduce spans hang under it
    assert root.name == "http_request"
    (coord_exported,) = [s for s in exported
                         if s.name == "search.coordinator"]
    assert coord_exported.parent_id == root.span_id
    (reduce_exported,) = [s for s in exported if s.name == "search.reduce"]
    assert reduce_exported.parent_id == coord_exported.span_id

    # (b) the prometheus exemplar on the took histogram links to a trace
    # (?exemplars=true: the suffix is OpenMetrics-only syntax, opted into
    # by the scrape job; the default exposition stays classic-parseable)
    plain = loop.run_until_complete(
        _http_text(ports["n0"], "/_prometheus/metrics"))
    assert " # {trace_id=" not in plain
    text = loop.run_until_complete(
        _http_text(ports["n0"], "/_prometheus/metrics?exemplars=true"))
    ex_lines = [ln for ln in text.splitlines()
                if "search_took_ms_bucket" in ln and " # {trace_id=" in ln]
    assert ex_lines, "no exemplar on the took histogram"
    ex_trace = ex_lines[0].split('trace_id="')[1].split('"')[0]
    ring_traces = {s["trace_id"]
                   for s in stats["nodes"]["n0"]["telemetry"]["spans"]}
    assert ex_trace in ring_traces, "exemplar trace not in the ring"

    # federated scrape: per-node labels, one request. Each node records
    # search.took_ms when IT coordinates, so route one search through
    # every node first.
    for nid in ("n1", "n2"):
        status, resp = _req(loop, ports[nid], "POST", "/items/_search",
                            {"query": {"match_all": {}}, "size": 1})
        assert status == 200, resp
    fed = loop.run_until_complete(
        _http_text(ports["n2"], "/_prometheus/metrics?cluster=true"))
    for nid in ("n0", "n1", "n2"):
        assert f'node="{nid}"' in fed, f"{nid} missing from federated view"
    assert 'opensearch_tpu_search_total{node="n0"}' in fed


def test_nodes_stats_metric_filter_cluster(cluster_ports):
    loop, ports = cluster_ports
    status, stats = _req(loop, ports["n0"], "GET",
                         "/_nodes/stats/knn_batch")
    assert status == 200, stats
    for entry in stats["nodes"].values():
        assert "knn_batch" in entry
        assert "telemetry" not in entry
    status, stats = _req(loop, ports["n0"], "GET",
                         "/_nodes/stats/shard_mesh")
    assert status == 200, stats
    assert all("shard_mesh" in e for e in stats["nodes"].values())
    status, resp = _req(loop, ports["n0"], "GET", "/_nodes/stats/bogus")
    assert status == 400, resp
