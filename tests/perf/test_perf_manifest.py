"""BENCHMARK.json resolves to files: a configuration, a mix, a cell and a
metric are each added by new files and manifest entries alone."""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load_run():
    spec = importlib.util.spec_from_file_location(
        "perf_run_under_test", REPO / "perf" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = load_run()


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "perf/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert (REPO / p).is_dir()
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda c: c["name"])
def test_every_cell_resolves_to_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    spec = RUN.resolve(MANIFEST, cell["name"])
    assert spec.config["name"] == cell["config"]
    assert spec.config["chips"] == cell["chips"]
    assert spec.mix["loop"] in ("closed", "open")
    # every run reports setup_s, another end-to-end metric and a layer's
    names = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and spec.per_layer
    for m in spec.end_to_end:
        assert callable(RUN.load_reader("end_to_end", m["name"]))
    for m in spec.per_layer:
        assert callable(RUN.load_reader("layers", m["name"]))


@pytest.mark.parametrize("conf", MANIFEST["configs"], ids=lambda c: c["name"])
def test_every_configuration_is_a_file_of_its_own_and_is_used(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"] == f"perf/configs/{conf['name']}.json"
    body = json.loads((REPO / conf["file"]).read_text())
    assert body["name"] == conf["name"] and body["source"] == conf["source"]
    assert body["reduced"] == conf["reduced"]
    for key in ("docs", "dims", "index", "field", "index_body", "request",
                "limits", "guarantees", "assumed", "work"):
        assert key in body
    assert any(w["config"] == conf["name"] for w in MANIFEST["workloads"])
    assert 1 <= len(conf["source"]) <= 200 and 1 <= len(conf["why"]) <= 200


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_is_named_bounded_and_placed(metric):
    assert NAME.match(metric["name"])
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
        assert (REPO / "perf" / "end_to_end" / f"{metric['name']}.py").is_file()
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
        # each of its cells reports the end-to-end metric it should move
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        assert (REPO / "perf" / "layers" / f"{metric['name']}.py").is_file()
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"


def test_names_are_unique_and_at_most_half_the_cells_take_four_chips():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        RUN.resolve(MANIFEST, "no-such.cell")


def test_a_new_cell_is_files_and_entries_alone(tmp_path):
    """What a later PR does: a configuration file, a mix file, a reader file
    and manifest entries — resolved by the same code, none of it edited."""
    manifest = json.loads(json.dumps(MANIFEST))
    conf = json.loads((REPO / "perf/configs/sift1m-exact.json").read_text())
    conf.update(name="knn-exact-768", dims=768, docs=4_000_000, chips=4)
    conf["index_body"]["settings"]["number_of_shards"] = 4
    (tmp_path / "knn-exact-768.json").write_text(json.dumps(conf))
    manifest["configs"].append({
        "name": "knn-exact-768", "source": "x", "reduced": [], "why": "x",
        "file": str(tmp_path / "knn-exact-768.json")})
    manifest["workloads"].append({
        "name": "knn-exact-768.c32", "config": "knn-exact-768",
        "traffic": "c32", "chips": 4, "why": "x"})
    spec = RUN.resolve(manifest, "knn-exact-768.c32")
    assert spec.config["dims"] == 768 and spec.cell["chips"] == 4
    assert spec.mix["clients"] == 32
    # metrics without a `workloads` key follow the new cell by themselves
    assert {"qps", "recall_at_10", "setup_s"} <= {m["name"] for m in spec.end_to_end}
    assert {"batch.mean_merged", "device.idle_share"} <= {
        m["name"] for m in spec.per_layer}
