"""`perf/launcher.py` with the hybrid path broken underneath it, where an
answer is produced: every seventh search comes back without its fusion,
the `knn` sub-query's hits served alone (rightly ranked and scored as a
plain knn search's are). Started by `_perf_hybrid_child.py` in the
launcher's place; for CPU tests only."""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from opensearch_tpu.node import TpuNode  # noqa: E402
from perf import launcher  # noqa: E402

assert os.environ.get("JAX_PLATFORMS") == "cpu", "faults are for CPU tests"

served = TpuNode.search
count = {"n": 0}


def unfused(body: dict) -> dict:
    knn = next(sub for sub in body["query"]["hybrid"]["queries"]
               if "knn" in sub)
    return {k: v for k, v in {**body, "query": knn}.items()
            if k != "search_pipeline"}


def altered(self, index=None, body=None, *args, **kwargs):
    count["n"] += 1
    if count["n"] % 7 == 0 and "hybrid" in (body or {}).get("query", {}):
        body = unfused(body)
    return served(self, index, body, *args, **kwargs)


TpuNode.search = altered
sys.exit(launcher.main())
