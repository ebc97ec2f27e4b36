"""Test-side stand-in for `perf/run.py`: the same `main` with
`_hybrid_faulty_launcher.py` as the node's launcher (`_perf_child.py`'s
switch, for a fault that `_faulty_launcher.py` does not know)."""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from perf import node, run  # noqa: E402

node.LAUNCHER = HERE / "_hybrid_faulty_launcher.py"

sys.exit(run.main())
