"""One cell end to end on the CPU at a few thousand documents — marked so
that it can never be taken for a device result — and what the command does
where it must refuse."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _perf_dry import DOCS, REPO, SEED, dry_run  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return tmp_path_factory.mktemp("perf_dry")


@pytest.fixture(scope="module")
def first(tmp):
    return dry_run(tmp, "sift1m-exact.seq", "--trace", "0")


def test_dry_run_prints_the_contracts_line_last_and_marks_it(first):
    proc, last = first
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["dry_run"] is True and last["device"]["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["docs"] == DOCS and last["seed"] == SEED
    assert last["first_fill"] is True and last["compiled_in_window"] == 0


def test_dry_run_reports_the_cells_end_to_end_metrics(first):
    _, last = first
    want = {m["name"]: m["unit"] for m in MANIFEST["end_to_end"]
            if "sift1m-exact.seq" in m.get("workloads", ["sift1m-exact.seq"])}
    assert {k: v["unit"] for k, v in last["metrics"].items()} == want
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert last["metrics"]["recall_at_10"]["value"] == 1.0
    assert last["attempted"] > 50 and last["failed"] == 0


def test_dry_run_is_correct_with_every_number_beside_its_limit(first):
    proc, last = first
    assert last["correct"] is True
    assert set(last["checks"]) == {"failed", "malformed", "count_gap",
                                   "score_gap", "rank_gap"}
    tail = proc.stderr.strip().splitlines()[-len(last["checks"]):]
    for line, (name, c) in zip(tail, last["checks"].items()):
        assert line.startswith(f"perf check {name} = ") and c["limit"] in line
    # one client: no launch ever serves two queries
    assert last["queries_per_launch"] == 1.0


def test_second_run_recovers_the_directory_and_reads_the_layers(tmp, first):
    proc, last = dry_run(tmp, "sift1m-exact.seq", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["first_fill"] is False          # the same configuration's data
    assert last["correct"] is True
    # a CPU has no device plane: the trace's readers return nothing and the
    # line leaves them out — never a 0% share, never busy_s of a CPU
    assert set(last["metrics"]) == {"batch.mean_merged"}
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert last["metrics"]["batch.mean_merged"]["value"] == 1.0


def test_the_lower_precision_control_comes_out_not_correct(tmp, first):
    proc, last = dry_run(tmp, "sift1m-exact.seq", "--trace", "0", "--control")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is False
    assert last["checks"]["score_gap"]["ok"] is False
    assert last["checks"]["failed"]["ok"] and last["checks"]["malformed"]["ok"]


def test_without_a_tpu_the_command_fails_and_prints_no_result(tmp):
    proc, last = dry_run(tmp, "sift1m-exact.seq", "--trace", "0", dry=False,
                         seed=5, timeout=300)
    assert proc.returncode != 0 and last is None
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_in_a_tree_with_only_the_benchmark_it_fails_and_prints_nothing(tmp):
    bare = tmp / "bare"
    bare.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", bare)
    for rel in MANIFEST["paths"]:
        shutil.copytree(REPO / rel, bare / rel, ignore=shutil.ignore_patterns(
            ".cache", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "sift1m-exact.seq",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(bare), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.mark.parametrize("extra", [["--docs", "100"], ["--fault", "alter_id"]])
def test_dry_run_only_and_unknown_options_are_refused_on_the_chip_path(
        tmp, extra):
    proc, last = dry_run(tmp, "sift1m-exact.seq", "--trace", "0", *extra,
                         dry=False, timeout=60)
    assert proc.returncode == 2 and last is None


def test_the_benchmarks_own_files_carry_no_fault_switch():
    for name in ("run.py", "launcher.py", "node.py"):
        text = (REPO / "perf" / name).read_text()
        assert "--fault" not in text and "plant_fault" not in text
