"""Test-side stand-in for `perf/run.py`: the same `main`, with what a test
names in its environment swapped underneath it. The benchmark's own files
carry neither switch.

    PERF_TEST_MANIFEST  another manifest than BENCHMARK.json (a cell that
                        only the tests have)
    PERF_TEST_FAULT     start `_faulty_launcher.py` as the node's launcher
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from perf import node, run  # noqa: E402

if os.environ.get("PERF_TEST_MANIFEST"):
    run.MANIFEST = Path(os.environ["PERF_TEST_MANIFEST"])
if os.environ.get("PERF_TEST_FAULT"):
    node.LAUNCHER = HERE / "_faulty_launcher.py"

sys.exit(run.main())
