"""The reference's YAML REST compliance suite against this engine
(OpenSearchClientYamlSuiteTestCase's suite run by
a from-scratch runner; the YAML files are read from the reference mount).

The pass rate is tracked in YAML_COMPAT.md; the assertion floor ratchets
up as coverage grows (a number, honestly measured, beats a green lie).
"""

from __future__ import annotations

from pathlib import Path

import pytest

from opensearch_tpu.testing.yaml_compat import (
    REFERENCE_SPEC,
    run_suites,
    summarize,
)

# the FULL reference suite: every directory under rest-api-spec/test
# (measuring 20 of 115 suites overstated compliance)
SUITES = sorted(
    p.name for p in (REFERENCE_SPEC / "test").iterdir() if p.is_dir()
) if REFERENCE_SPEC.exists() else []

# ratchet: raise as compliance grows; measured on the FULL suite now
# (r3 measured 20 suites at 0.85; the full denominator resets the floor)
FLOOR = 0.78


@pytest.mark.skipif(not REFERENCE_SPEC.exists(),
                    reason="reference rest-api-spec not mounted")
def test_yaml_compliance_pass_rate(tmp_path):
    results = run_suites(SUITES, tmp_path)
    summary = summarize(results)
    assert results, "no YAML tests discovered"

    lines = [
        "# YAML REST compliance",
        "",
        "The reference's implementation-agnostic YAML suite "
        "(`rest-api-spec/src/main/resources/rest-api-spec/test`, run in the "
        "reference by `OpenSearchClientYamlSuiteTestCase`) executed against "
        "this engine's REST layer by `opensearch_tpu/testing/yaml_compat.py` "
        "(`pytest tests/test_yaml_compat.py`).",
        "",
        "| suite | passed | failed | skipped |",
        "|---|---|---|---|",
    ]
    for suite in sorted(summary["suites"]):
        s = summary["suites"][suite]
        lines.append(
            f"| {suite} | {s['passed']} | {s['failed']} | {s['skipped']} |"
        )
    t = summary["total"]
    lines.append(
        f"| **total** | **{t['passed']}** | **{t['failed']}** | "
        f"**{t['skipped']}** |"
    )
    lines.append("")
    lines.append(f"**Pass rate (run tests): {t['pass_rate']:.1%}**")
    lines.append("")
    lines.append("Top failing tests (first 25):")
    for r in [r for r in results if r.status == "failed"][:25]:
        lines.append(f"- `{r.suite} :: {r.name}` — {r.detail[:120]}")
    Path("YAML_COMPAT.md").write_text("\n".join(lines) + "\n")

    assert t["pass_rate"] >= FLOOR, (
        f"YAML compliance regressed: {t['pass_rate']:.1%} < {FLOOR:.0%} "
        f"(see YAML_COMPAT.md)"
    )
