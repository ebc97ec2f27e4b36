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
#   scripts/check.sh --bench    # + the bench-regression gates: a quick
#                               # bench.py --gate run must stay within a
#                               # CPU/TPU-aware tolerance of the last full
#                               # bench.py result on the same platform
#                               # (none on record: passes with a note), and
#                               # bench.py --mesh-gate holds the shard-mesh
#                               # cluster bench to BENCH_MESH.json the same
#                               # way, and bench.py --ann-gate holds the
#                               # batched IVF-PQ path to BENCH_ANN.json plus
#                               # the recall@10 >= 0.95 ratchet on BOTH the
#                               # XLA and fused-Pallas ADC paths (on TPU it
#                               # also asserts fused int8/bf16 QPS >= fp32 —
#                               # the inversion resolution; the CPU sim's
#                               # interpret path is recall-only), and
#                               # bench.py --fused-knn-gate holds the fused
#                               # exact-kNN path to BENCH_KNN_FUSED.json:
#                               # served fp32 recall@10 must be EXACTLY 1.0
#                               # under search.knn.kernel="pallas", reduced
#                               # precisions above the recall floor, and the
#                               # fused program >= 1.0x the legacy XLA exact
#                               # scorer within tolerance (on TPU the fused
#                               # qps rows are the real Pallas kernel), and
#                               # bench.py --tail-gate asserts the tail
#                               # control plane (lanes + wait auto-tuner +
#                               # residency routing) still buys >= 1.5x
#                               # interactive p99 under mixed flood at no
#                               # aggregate-QPS cost with zero interactive
#                               # sheds, so a PR that slows a hot path (or
#                               # buys speed with recall, or regresses the
#                               # tail) fails HERE, not in the next
#                               # round's headline
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

if [[ "${1:-}" == "--bench" ]]; then
  echo "== bench-regression gate (quick run vs the last bench.py result on this platform) =="
  python bench.py --gate
  echo "== shard-mesh gate (quick cluster run vs BENCH_MESH.json) =="
  python bench.py --mesh-gate
  echo "== otel-overhead gate (span export must cost <= 5% QPS) =="
  python bench.py --otel-overhead
  echo "== heat-overhead gate (touch accounting must cost <= 5% QPS) =="
  python bench.py --heat-overhead
  echo "== ANN gate (recall@10 >= 0.95 ratchet incl. fused-Pallas path + batched >= 1.3x + QPS floor) =="
  python bench.py --ann-gate
  echo "== fused exact-kNN gate (served fp32 recall@10 == 1.0 under kernel=pallas, fused >= 1.0x XLA within tolerance, QPS floor vs BENCH_KNN_FUSED.json) =="
  python bench.py --fused-knn-gate
  echo "== tail gate (interactive p99 >= 1.5x better with lanes+tuner+routing on, no aggregate-QPS regression, zero interactive sheds) =="
  python bench.py --tail-gate
  echo "== roofline gate (every family modeled, fractions in (0,1], accounted_flops == sum of per-launch model FLOPs) =="
  python bench.py --roofline
  # every gate child already asserts the device-ledger identity before
  # printing its result; this step proves it once more in THIS process
  # over a full publish/merge/delete cycle (ISSUE 10 acceptance)
  echo "== device-ledger identity (resident == allocated - freed) =="
  JAX_PLATFORMS=cpu python - <<'PY'
import tempfile
from opensearch_tpu.node import TpuNode
from opensearch_tpu.telemetry.device_ledger import default_ledger

node = TpuNode(tempfile.mkdtemp(prefix="ledger_check_"))
node.create_index("ck", {"mappings": {"properties": {
    "msg": {"type": "text"}, "n": {"type": "integer"}}}})
for i in range(64):
    node.index_doc("ck", str(i), {"msg": f"w{i} common", "n": i})
node.refresh("ck")
node.force_merge("ck")
assert default_ledger.structures("ck"), "no ledger rows after publish"
default_ledger.verify_identity()
node.delete_index("ck")
assert default_ledger.structures("ck") == [], "rows survived index delete"
default_ledger.verify_identity()
node.close()
print("device-ledger identity holds")
PY
fi
