"""Extended aggregation families: metrics, buckets, composite, pipelines."""

import math

import pytest

from opensearch_tpu.node import TpuNode

DOCS = [
    {"id": "1", "tag": "a", "color": "red", "price": 10, "qty": 2,
     "created": "2024-01-05T00:00:00Z", "title": "quick brown fox"},
    {"id": "2", "tag": "a", "color": "blue", "price": 20, "qty": 1,
     "created": "2024-01-15T00:00:00Z", "title": "lazy dog"},
    {"id": "3", "tag": "b", "color": "red", "price": 30, "qty": 3,
     "created": "2024-02-01T00:00:00Z", "title": "quick fox"},
    {"id": "4", "tag": "b", "color": "green", "price": 40, "qty": 4,
     "created": "2024-02-20T00:00:00Z", "title": "brown bear"},
    {"id": "5", "tag": "c", "color": "red", "price": 50, "qty": 5,
     "created": "2024-03-10T00:00:00Z", "title": "quick quick fox"},
]

MAPPINGS = {
    "properties": {
        "tag": {"type": "keyword"},
        "color": {"type": "keyword"},
        "price": {"type": "long"},
        "qty": {"type": "long"},
        "created": {"type": "date"},
        "title": {"type": "text"},
    }
}


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = TpuNode(tmp_path_factory.mktemp("aggx"))
    n.create_index("sales", {"settings": {"number_of_shards": 2},
                             "mappings": MAPPINGS})
    for d in DOCS:
        doc = dict(d)
        n.index_doc("sales", doc.pop("id"), doc)
    n.refresh("sales")
    yield n
    n.close()


def _agg(node, body):
    return node.search("sales", {"size": 0, "aggs": body})["aggregations"]


def test_extended_stats(node):
    out = _agg(node, {"s": {"extended_stats": {"field": "price"}}})["s"]
    assert out["count"] == 5
    assert out["sum"] == 150.0
    assert out["avg"] == 30.0
    assert out["sum_of_squares"] == 100 + 400 + 900 + 1600 + 2500
    assert math.isclose(out["variance"], 200.0)
    assert math.isclose(out["std_deviation"], math.sqrt(200.0))
    b = out["std_deviation_bounds"]
    assert math.isclose(b["upper"], 30 + 2 * math.sqrt(200.0))


def test_percentiles_and_ranks(node):
    out = _agg(node, {"p": {"percentiles": {"field": "price",
                                            "percents": [50, 95]}}})["p"]
    assert out["values"]["50.0"] == 30.0
    out = _agg(node, {"p": {"percentile_ranks": {
        "field": "price", "values": [30]}}})["p"]
    assert out["values"]["30.0"] == 60.0  # 3 of 5 <= 30


def test_median_absolute_deviation(node):
    out = _agg(node, {"m": {"median_absolute_deviation": {"field": "price"}}})["m"]
    assert out["value"] == 10.0


def test_weighted_avg(node):
    out = _agg(node, {"w": {"weighted_avg": {
        "value": {"field": "price"}, "weight": {"field": "qty"}}}})["w"]
    expected = (10 * 2 + 20 * 1 + 30 * 3 + 40 * 4 + 50 * 5) / (2 + 1 + 3 + 4 + 5)
    assert math.isclose(out["value"], expected)


def test_top_hits_in_terms(node):
    out = _agg(node, {"tags": {
        "terms": {"field": "tag", "order": {"_key": "asc"}},
        "aggs": {"top": {"top_hits": {
            "size": 1, "sort": [{"price": {"order": "desc"}}]}}},
    }})["tags"]
    a_bucket = out["buckets"][0]
    assert a_bucket["key"] == "a"
    hits = a_bucket["top"]["hits"]
    assert hits["total"]["value"] == 2
    assert hits["hits"][0]["_id"] == "2"  # price 20 > 10
    assert hits["hits"][0]["_source"]["price"] == 20
    assert hits["hits"][0]["_index"] == "sales"


def test_scripted_metric(node):
    out = _agg(node, {"t": {"scripted_metric": {
        "init_script": "state.total = 0",
        "map_script": "state.total += doc['price'].value",
        "combine_script": "return state.total",
        "reduce_script": (
            "def s = 0; for (t in states) { s += t } return s"
        ),
    }}})["t"]
    assert out["value"] == 150


def test_matrix_stats(node):
    out = _agg(node, {"mx": {"matrix_stats": {"fields": ["price", "qty"]}}})["mx"]
    price = next(f for f in out["fields"] if f["name"] == "price")
    assert price["count"] == 5
    assert math.isclose(price["mean"], 30.0)
    assert price["correlation"]["qty"] >= 0.9  # strongly correlated by design


def test_multi_terms(node):
    out = _agg(node, {"mt": {"multi_terms": {
        "terms": [{"field": "tag"}, {"field": "color"}]}}})["mt"]
    keys = [tuple(b["key"]) for b in out["buckets"]]
    assert ("a", "red") in keys and ("b", "green") in keys
    top = out["buckets"][0]
    assert top["doc_count"] == 1


def test_rare_terms(node):
    out = _agg(node, {"r": {"rare_terms": {"field": "color"}}})["r"]
    keys = [b["key"] for b in out["buckets"]]
    assert keys == ["blue", "green"]  # count==1 each; red has 3


def test_significant_terms(node):
    out = node.search("sales", {
        "size": 0,
        "query": {"match": {"title": "quick"}},
        "aggs": {"sig": {"significant_terms": {
            "field": "color", "min_doc_count": 1}}},
    })["aggregations"]["sig"]
    assert out["doc_count"] == 3  # docs 1,3,5 match "quick"
    keys = [b["key"] for b in out["buckets"]]
    assert "red" in keys  # red: 3/3 fg vs 3/5 bg -> significant
    red = next(b for b in out["buckets"] if b["key"] == "red")
    assert red["doc_count"] == 3
    assert red["bg_count"] == 3
    assert red["score"] > 0


def test_sampler_and_diversified(node):
    out = _agg(node, {"s": {
        "sampler": {"shard_size": 3},
        "aggs": {"mx": {"max": {"field": "price"}}},
    }})["s"]
    assert out["doc_count"] == 3
    out = _agg(node, {"s": {
        "diversified_sampler": {"shard_size": 5, "field": "color",
                                "max_docs_per_value": 1},
        "aggs": {"c": {"value_count": {"field": "price"}}},
    }})["s"]
    assert out["doc_count"] == 3  # one red, one blue, one green


def test_adjacency_matrix(node):
    out = _agg(node, {"adj": {"adjacency_matrix": {"filters": {
        "cheap": {"range": {"price": {"lte": 20}}},
        "red": {"term": {"color": "red"}},
    }}}})["adj"]
    by_key = {b["key"]: b["doc_count"] for b in out["buckets"]}
    assert by_key["cheap"] == 2
    assert by_key["red"] == 3
    assert by_key["cheap&red"] == 1  # doc 1


def test_date_range_with_date_math(node):
    out = _agg(node, {"dr": {"date_range": {
        "field": "created",
        "ranges": [
            {"to": "2024-02-01"},
            {"from": "2024-02-01"},
            {"from": "2024-01-01||+1M/M", "key": "feb_onward"},
        ],
    }}})["dr"]
    assert out["buckets"][0]["doc_count"] == 2
    assert out["buckets"][1]["doc_count"] == 3
    assert out["buckets"][2]["key"] == "feb_onward"
    assert out["buckets"][2]["doc_count"] == 3


def test_composite_pagination(node):
    body = {"c": {"composite": {
        "size": 2,
        "sources": [{"t": {"terms": {"field": "tag"}}},
                    {"col": {"terms": {"field": "color"}}}],
    }}}
    out = _agg(node, body)["c"]
    assert len(out["buckets"]) == 2
    assert out["buckets"][0]["key"] == {"t": "a", "col": "blue"}
    after = out["after_key"]
    body["c"]["composite"]["after"] = after
    out2 = _agg(node, body)["c"]
    assert len(out2["buckets"]) == 2
    # no overlap between the pages
    keys1 = [tuple(b["key"].items()) for b in out["buckets"]]
    keys2 = [tuple(b["key"].items()) for b in out2["buckets"]]
    assert not set(keys1) & set(keys2)


def test_composite_with_sub_aggs(node):
    out = _agg(node, {"c": {
        "composite": {"size": 10, "sources": [{"t": {"terms": {"field": "tag"}}}]},
        "aggs": {"total": {"sum": {"field": "price"}}},
    }})["c"]
    by_tag = {b["key"]["t"]: b["total"]["value"] for b in out["buckets"]}
    assert by_tag == {"a": 30.0, "b": 70.0, "c": 50.0}


def test_auto_date_histogram(node):
    out = _agg(node, {"h": {"auto_date_histogram": {
        "field": "created", "buckets": 5}}})["h"]
    assert 1 <= len(out["buckets"]) <= 5
    assert sum(b["doc_count"] for b in out["buckets"]) == 5


def test_histogram_empty_bucket_fill(node):
    out = _agg(node, {"h": {"histogram": {
        "field": "price", "interval": 10, "min_doc_count": 0}}})["h"]
    keys = [b["key"] for b in out["buckets"]]
    assert keys == [10.0, 20.0, 30.0, 40.0, 50.0]
    out = _agg(node, {"h": {"histogram": {
        "field": "price", "interval": 10, "min_doc_count": 0,
        "extended_bounds": {"min": 0, "max": 70}}}})["h"]
    keys = [b["key"] for b in out["buckets"]]
    assert keys[0] == 0.0 and keys[-1] == 70.0


# -- pipeline aggregations --------------------------------------------------


def test_sibling_pipelines(node):
    out = _agg(node, {
        "months": {
            "date_histogram": {"field": "created", "calendar_interval": "month"},
            "aggs": {"sales": {"sum": {"field": "price"}}},
        },
        "avg_monthly": {"avg_bucket": {"buckets_path": "months>sales"}},
        "max_monthly": {"max_bucket": {"buckets_path": "months>sales"}},
        "total": {"sum_bucket": {"buckets_path": "months>sales"}},
        "stats_m": {"stats_bucket": {"buckets_path": "months>sales"}},
    })
    assert out["total"]["value"] == 150.0
    assert out["avg_monthly"]["value"] == 50.0
    assert out["max_monthly"]["value"] == 70.0
    assert out["stats_m"]["count"] == 3


def test_parent_pipelines(node):
    out = _agg(node, {"months": {
        "date_histogram": {"field": "created", "calendar_interval": "month"},
        "aggs": {
            "sales": {"sum": {"field": "price"}},
            "cum": {"cumulative_sum": {"buckets_path": "sales"}},
            "deriv": {"derivative": {"buckets_path": "sales"}},
            "diff": {"serial_diff": {"buckets_path": "sales", "lag": 1}},
        },
    }})["months"]
    buckets = out["buckets"]
    sales = [b["sales"]["value"] for b in buckets]
    assert sales == [30.0, 70.0, 50.0]
    assert [b["cum"]["value"] for b in buckets] == [30.0, 100.0, 150.0]
    assert "deriv" not in buckets[0]
    assert buckets[1]["deriv"]["value"] == 40.0
    assert buckets[2]["diff"]["value"] == -20.0


def test_moving_fn(node):
    out = _agg(node, {"months": {
        "date_histogram": {"field": "created", "calendar_interval": "month"},
        "aggs": {
            "sales": {"sum": {"field": "price"}},
            "mov": {"moving_fn": {
                "buckets_path": "sales", "window": 2,
                "script": "MovingFunctions.unweightedAvg(values)"}},
        },
    }})["months"]
    buckets = out["buckets"]
    assert buckets[0]["mov"]["value"] is None  # empty window
    assert buckets[1]["mov"]["value"] == 30.0
    assert buckets[2]["mov"]["value"] == 50.0  # avg(30, 70)


def test_bucket_script_and_selector(node):
    out = _agg(node, {"tags": {
        "terms": {"field": "tag", "order": {"_key": "asc"}},
        "aggs": {
            "sales": {"sum": {"field": "price"}},
            "per_doc": {"bucket_script": {
                "buckets_path": {"s": "sales", "n": "_count"},
                "script": "params.s / params.n"}},
            "keep_big": {"bucket_selector": {
                "buckets_path": {"s": "sales"},
                "script": "params.s > 40"}},
        },
    }})["tags"]
    keys = [b["key"] for b in out["buckets"]]
    assert keys == ["b", "c"]  # a (sum 30) dropped
    assert out["buckets"][0]["per_doc"]["value"] == 35.0


def test_bucket_sort(node):
    out = _agg(node, {"tags": {
        "terms": {"field": "tag", "order": {"_key": "asc"}},
        "aggs": {
            "sales": {"sum": {"field": "price"}},
            "srt": {"bucket_sort": {
                "sort": [{"sales": {"order": "desc"}}], "size": 2}},
        },
    }})["tags"]
    sales = [b["sales"]["value"] for b in out["buckets"]]
    assert sales == [70.0, 50.0]


# -- geo aggregations (geogrid / geo_distance / bounds / centroid) ----------


@pytest.fixture(scope="module")
def geo_node(tmp_path_factory):
    from opensearch_tpu.node import TpuNode

    node = TpuNode(tmp_path_factory.mktemp("geo") / "data")
    node.create_index("cities", {"mappings": {"properties": {
        "location": {"type": "geo_point"},
        "population": {"type": "long"},
    }}})
    cities = [
        ("nyc", 40.7128, -74.0060, 8_623_000),
        ("la", 34.0522, -118.2437, 4_000_000),
        ("chi", 41.8781, -87.6298, 2_716_000),
        ("sf", 37.7749, -122.4194, 884_000),
    ]
    node.bulk([
        ("index", {"_index": "cities", "_id": cid},
         {"location": {"lat": lat, "lon": lon}, "population": pop})
        for cid, lat, lon, pop in cities
    ], refresh=True)
    return node


def _geo_agg(geo_node, aggs):
    return geo_node.search("cities", {"size": 0, "aggs": aggs})["aggregations"]


def test_geo_distance_agg(geo_node):
    out = _geo_agg(geo_node, {"rings": {"geo_distance": {
        "field": "location", "origin": "35.7796, -78.6382",
        "ranges": [{"to": 1_000_000}, {"from": 1_000_000, "to": 5_000_000},
                   {"from": 5_000_000}],
    }}})["rings"]
    counts = [b["doc_count"] for b in out["buckets"]]
    assert counts == [1, 3, 0]
    assert out["buckets"][0]["key"] == "*-1000000.0"


def test_geo_distance_agg_km_unit(geo_node):
    out = _geo_agg(geo_node, {"rings": {"geo_distance": {
        "field": "location", "origin": "35.7796, -78.6382", "unit": "km",
        "ranges": [{"to": 1000}, {"from": 1000}],
    }}})["rings"]
    assert [b["doc_count"] for b in out["buckets"]] == [1, 3]


def test_geohash_and_geotile_grid(geo_node):
    out = _geo_agg(geo_node, {"cells": {"geohash_grid": {
        "field": "location", "precision": 3,
    }}})["cells"]
    assert sum(b["doc_count"] for b in out["buckets"]) == 4
    assert out["buckets"][0]["key"] and len(out["buckets"][0]["key"]) == 3
    # NYC at precision 3 is "dr5"
    assert any(b["key"] == "dr5" for b in out["buckets"])

    out = _geo_agg(geo_node, {"cells": {"geotile_grid": {
        "field": "location", "precision": 6,
    }}})["cells"]
    assert sum(b["doc_count"] for b in out["buckets"]) == 4
    z, x, y = out["buckets"][0]["key"].split("/")
    assert z == "6" and x.isdigit() and y.isdigit()


def test_geo_bounds_and_centroid(geo_node):
    out = _geo_agg(geo_node, {
        "box": {"geo_bounds": {"field": "location"}},
        "mid": {"geo_centroid": {"field": "location"}},
    })
    b = out["box"]["bounds"]
    assert b["top_left"]["lat"] == pytest.approx(41.8781)
    assert b["top_left"]["lon"] == pytest.approx(-122.4194)
    assert b["bottom_right"]["lat"] == pytest.approx(34.0522)
    assert b["bottom_right"]["lon"] == pytest.approx(-74.0060)
    assert out["mid"]["count"] == 4
    assert out["mid"]["location"]["lat"] == pytest.approx(38.6045, abs=1e-3)


def test_range_field_ipv6_and_open_bounds(geo_node):
    """IPv6 ordinals exceed 2^62 — open bounds must sit at
    the int64 edges, and single-address string values are one-point
    ranges."""
    node = geo_node
    node.create_index("netblocks", {"mappings": {"properties": {
        "block": {"type": "ip_range"},
    }}})
    node.bulk([
        ("index", {"_index": "netblocks", "_id": "v6"},
         {"block": {"gte": "2001:db8::1", "lte": "2001:db8::ffff"}}),
        ("index", {"_index": "netblocks", "_id": "v4single"},
         {"block": "192.168.0.7"}),
    ], refresh=True)
    # unbounded upper side must still intersect the v6 block
    r = node.search("netblocks", {"query": {"range": {"block": {
        "gte": "2001:db8::5"}}}})
    assert {h["_id"] for h in r["hits"]["hits"]} == {"v6"}
    # the single-address doc behaves as [addr, addr]
    r = node.search("netblocks", {"query": {"range": {"block": {
        "gte": "192.168.0.7", "lte": "192.168.0.7"}}}})
    assert {h["_id"] for h in r["hits"]["hits"]} == {"v4single"}
