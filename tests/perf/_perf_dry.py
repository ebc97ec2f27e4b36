"""Shared by the dry-run tests: run `perf/run.py --cpu-dry-run` as the
driver runs the command, in a scratch cache, and parse its last line. With
`fault` or `manifest` the command is `_perf_child.py`, which swaps the
launcher or the manifest underneath the same `main`."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
C32_MANIFEST = HERE / "data" / "manifest_c32.json"
DOCS = 4096
SEED = 2**31 + 77            # more than 32 signed bits hold


def dry_run(tmp: Path, workload: str, *extra: str, seed: int = SEED,
            dry: bool = True, timeout: int = 600, fault: str | None = None,
            manifest: Path | None = None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           # keep the checkout's own cache out of a test run
           "JAX_COMPILATION_CACHE_DIR": str(tmp / "jax_cache")}
    command = REPO / "perf" / "run.py"
    if fault or manifest:
        command = HERE / "_perf_child.py"
        env["PERF_TEST_FAULT"] = fault or ""
        env["PERF_TEST_MANIFEST"] = str(manifest or "")
    argv = [sys.executable, str(command), "--workload",
            workload, "--seed", str(seed), "--seconds", "2", "--cache-dir",
            str(tmp / "cache")]
    if dry:
        argv += ["--cpu-dry-run", "--docs", str(DOCS)]
    proc = subprocess.run([*argv, *extra], cwd=str(REPO), env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return proc, last
