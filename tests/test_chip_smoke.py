"""`chip_smoke.py` keeps working between chip runs: the explicit CPU dry
run drives the whole script (child node, HTTP, the three phases, the
reference comparison) at a tiny size, and the default command refuses to
pass without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _smoke(args: list[str], tmp_path: Path, timeout: int):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # keep the checkout's own cache out of a test run
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=timeout)


def test_cpu_dry_run_passes_and_cannot_pass_for_the_chip(tmp_path):
    proc = _smoke(["--cpu-dry-run", "--docs", "4096"], tmp_path, 600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the last line is the verdict, with exactly the keys the chip check
    # reads; the report goes before it
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["ok"] is False and verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    result = report
    assert result["device"] == verdict["device"]
    assert result["dry_run"] is True and result["platform"] == "cpu"
    assert result["dry_run_checks_passed"] is True
    assert result["ok"] is False  # "ok" is a pass on the chip, only
    assert result["claim"] is None
    assert set(result["phases"]) == {"A", "B", "C"}
    for phase in ("A", "B"):
        assert result["phases"][phase]["recall_at_10"] == 1.0
    assert result["phases"]["B"]["shards"] == 4
    for phase in result["phases"].values():
        # the held burst ran the phase's program wider than one query
        held = phase["batched_burst"]
        assert held["answers_checked"] == held["clients"] == 16
        assert held["coalesced_launches"] >= 1
        assert held["launches"] < held["clients"]
    # a cut on the chip keeps the recall floor; only this dry run drops it
    assert result["phases"]["C"]["recall_floor_enforced"] is False
    assert result["compile_cache_dir"] == str(tmp_path / "jax_cache")


def test_default_command_fails_without_a_tpu(tmp_path):
    """JAX_PLATFORMS=cpu in the caller's environment changes nothing: the
    child is started with JAX_PLATFORMS=tpu, dies at its first touch of
    JAX, and the smoke exits non-zero with no result line."""
    proc = _smoke([], tmp_path, 300)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
