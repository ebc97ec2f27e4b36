"""The harness's look for a chip skipped, the rest of a run driven, with
the timed path broken underneath: `correct` must come out false. The fault
is planted in the node's process, where an answer is produced, by a
launcher of the tests' own (`_faulty_launcher.py`); the cell under 32
clients exists only in the tests' manifest (`data/manifest_c32.json`)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _perf_dry import C32_MANIFEST, dry_run  # noqa: E402


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return tmp_path_factory.mktemp("perf_faults")


@pytest.mark.parametrize("workload, fault, number", [
    ("sift1m-exact.seq", "alter_id", "rank_gap"),
    ("sift1m-exact.seq", "alter_score", "score_gap"),
    ("sift1m-exact.c32", "alter_id", "rank_gap"),
    ("sift1m-ivfpq.c32", "alter_score", "score_gap"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        tmp, workload, fault, number):
    proc, last = dry_run(tmp, workload, "--trace", "0", fault=fault,
                         manifest=C32_MANIFEST)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is False
    assert last["checks"][number]["ok"] is False
    # every other request was served sound: the fault is what failed it
    assert last["attempted"] > 20 and last["checks"]["count_gap"]["ok"]


def test_the_lower_precision_control_fails_the_concurrent_cell_too(tmp):
    proc, last = dry_run(tmp, "sift1m-exact.c32", "--trace", "0", "--control",
                         manifest=C32_MANIFEST)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is False and last["control"]
    assert last["checks"]["score_gap"]["ok"] is False
    assert last["checks"]["failed"]["ok"] and last["checks"]["malformed"]["ok"]


def test_the_concurrent_cell_reads_its_own_layers(tmp):
    proc, last = dry_run(tmp, "sift1m-exact.c32", "--trace", "1",
                         manifest=C32_MANIFEST)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["first_fill"] is False and last["correct"] is True
    # no device plane on a CPU: the roofline and the idle share stay out
    assert set(last["metrics"]) == {"c32.search_p99_ms", "batch.mean_merged"}
    assert last["metrics"]["batch.mean_merged"]["value"] >= 1.0


def test_the_ann_cell_runs_from_the_manifest_and_its_control_fails(tmp):
    """(4,096 documents in 1,024 lists read a recall far under the floor:
    that number is for the cell's own size; every other number holds.)"""
    proc, last = dry_run(tmp, "sift1m-ivfpq.c32", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert all(c["ok"] for name, c in last["checks"].items()
               if name != "recall_at_10")
    assert set(last["metrics"]) == {"c32.search_p99_ms", "batch.mean_merged"}
    proc, last = dry_run(tmp, "sift1m-ivfpq.c32", "--trace", "0", "--control")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is False and last["control"]
    assert last["checks"]["score_gap"]["ok"] is False
    assert last["checks"]["failed"]["ok"] and last["checks"]["malformed"]["ok"]


def test_another_seed_sends_other_queries_and_is_correct(tmp):
    proc, last = dry_run(tmp, "sift1m-exact.seq", "--trace", "0", seed=11)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["seed"] == 11 and last["correct"] is True
    assert last["first_fill"] is False        # the corpus is not the seed's
