#!/usr/bin/env bash
# Repo gate: the tpulint invariant check + the fast tier-1 subset.
#
#   scripts/check.sh            # lint gate + lint/transport/cluster tests
#   scripts/check.sh --lint     # lint gate only (pre-commit speed)
#   scripts/check.sh --soak-tcp # + the elastic-topology soak on the REAL
#                               # TCP transport: node join, rebalance,
#                               # watermark evacuation and graceful drain
#                               # under live loopback traffic, invariants
#                               # only (~60s wall-clock budget)
#   scripts/check.sh --race-probe
#                               # + the runtime race confirmation: one
#                               # seeded soak cycle plus a threaded drill
#                               # of whatever the cross-module static pass
#                               # still cannot role, under lock/role
#                               # instrumentation (testing/race_probe.py),
#                               # asserting zero unconfirmed-unlocked
#                               # cross-role writes
#   scripts/check.sh --race-probe-tcp
#                               # + the same instrumentation over the REAL
#                               # TcpTransport reshape chain (soak_tcp's
#                               # join/evacuate/drain under live loopback
#                               # traffic, invariants-only)
#
# The lint gate runs three ways on purpose:
#   1. repo-wide lint vs the (EMPTY) baseline ratchet (json report),
#   2. --fix --dry-run, asserting zero pending mechanical rewrites,
#   3. the tier-1 subset that pins rule/fixture semantics.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tpulint (repo-wide, baseline must hold, role rules must run) =="
# one JSON report answers both questions: did anything regress past the
# (EMPTY) baseline, and did the thread-role rules actually run (the
# "rules" catalog in the same report — no --list-rules text grep)
python -m opensearch_tpu.lint --format json opensearch_tpu \
  | python -c 'import json,sys
r = json.load(sys.stdin)
ran = {c["id"] for c in r["rules"]}
missing = {"TPU018", "TPU019"} - ran
assert not missing, f"thread-role rules did not run: {sorted(missing)}"
print("%(files_checked)s files, %(total_violations)s violations in "
      "%(elapsed_seconds)ss; role rules ran" % r)
for v in r["new_violations"]:
    meta = v.get("meta", {})
    print("  NEW %s %s:%s domains=%s locks=%s" % (
        v["rule"], v["path"], v["line"],
        ",".join(meta.get("domains", [])), meta.get("locks", "")))
sys.exit(1 if r["regressions"] else 0)'

echo "== tpulint --fix --dry-run (zero pending rewrites) =="
python -m opensearch_tpu.lint --fix --dry-run opensearch_tpu > /dev/null
echo "ok"

if [[ "${1:-}" == "--lint" ]]; then
  exit 0
fi

echo "== tier-1 subset (lint semantics + transport/cluster/fault/soak) =="
JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow' \
  -p no:cacheprovider \
  tests/test_lint.py \
  tests/test_race_probe.py \
  tests/test_coordination.py \
  tests/test_cluster_data.py \
  tests/test_fault_injection.py \
  tests/test_soak.py

if [[ "${1:-}" == "--race-probe" ]]; then
  echo "== runtime race probe (one seeded soak cycle + threaded drill) =="
  JAX_PLATFORMS=cpu python -m opensearch_tpu.testing.race_probe \
    --seed 7 --cycles 1
fi

if [[ "${1:-}" == "--race-probe-tcp" ]]; then
  echo "== runtime race probe over the REAL TCP reshape chain (invariants-only) =="
  JAX_PLATFORMS=cpu python -m opensearch_tpu.testing.race_probe \
    --tcp --seconds 90
fi

if [[ "${1:-}" == "--soak-tcp" ]]; then
  echo "== elastic-topology soak on the real TCP transport (invariants-only) =="
  JAX_PLATFORMS=cpu python -m opensearch_tpu.testing.soak_tcp --seconds 60
fi
