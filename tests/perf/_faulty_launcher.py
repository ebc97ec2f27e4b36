"""`perf/launcher.py` with the served path broken underneath it, where an
answer is produced: every seventh search comes back altered. Started by
`_perf_child.py` in the launcher's place; PERF_TEST_FAULT names the fault.

    alter_id     another document under the served score
    alter_score  the best hit's score one per cent higher
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from opensearch_tpu.node import TpuNode  # noqa: E402
from perf import launcher  # noqa: E402

FAULT = os.environ["PERF_TEST_FAULT"]
assert FAULT in ("alter_id", "alter_score"), FAULT
assert os.environ.get("JAX_PLATFORMS") == "cpu", "faults are for CPU tests"

served = TpuNode.search
count = {"n": 0}


def altered(self, *args, **kwargs):
    resp = served(self, *args, **kwargs)
    hits = resp.get("hits", {}).get("hits") or []
    count["n"] += 1
    if hits and count["n"] % 7 == 0:
        if FAULT == "alter_id":
            hits[-1] = {**hits[-1], "_id": str(int(hits[-1]["_id"]) + 1)}
        else:
            hits[0] = {**hits[0], "_score": hits[0]["_score"] * 1.01}
        resp = {**resp, "hits": {**resp["hits"], "hits": hits}}
    return resp


TpuNode.search = altered
sys.exit(launcher.main())
