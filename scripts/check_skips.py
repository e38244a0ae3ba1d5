#!/usr/bin/env python3
"""CI gate against silent coverage loss, two checks:

1. skips — the tier-1 run's skip count must EQUAL the allowlisted number
   (currently zero — both former perpetual skips were made hermetic /
   collection-filtered). A new `pytest.skip` that creeps in fails CI
   instead of silently shrinking coverage; a legitimately environment-gated
   skip must be added to ALLOWED_SKIPS here, with a reason, in the same PR.
2. presence — every test module in EXPECTED_MODULES must contribute at
   least one testcase to the junit report, so a collection error, an
   accidental deselection, or a deleted file can't silently drop a whole
   module (new test files must be added here in the PR that creates them).

Usage:  pytest -q --junitxml=report.xml && python scripts/check_skips.py report.xml
"""
from __future__ import annotations

import sys
import xml.etree.ElementTree as ET

# (test id substring -> reason). Empty: the tier-1 selection never skips.
ALLOWED_SKIPS: dict[str, str] = {}

# every tests/test_*.py module must show up in the tier-1 report
EXPECTED_MODULES = (
    "test_analysis",
    "test_attention", "test_core", "test_distributed", "test_fused_decode",
    "test_ingress", "test_kernel_conformance", "test_kernels",
    "test_mixed_batch", "test_models", "test_paged_cache",
    "test_prefix_cache", "test_quant_quality", "test_sampler",
    "test_scheduler_fuzz", "test_serving", "test_solver_properties",
    "test_spec", "test_system", "test_telemetry", "test_tp_serving",
    "test_tpu_compile", "test_trace", "test_training",
)


def main(path: str) -> int:
    root = ET.parse(path).getroot()
    suites = root.iter("testsuite") if root.tag == "testsuites" else [root]
    skipped = []
    seen_modules = set()
    total = errors = failures = 0
    for s in suites:
        total += int(s.get("tests", 0))
        errors += int(s.get("errors", 0))
        failures += int(s.get("failures", 0))
        for case in s.iter("testcase"):
            # classname is a dotted path (e.g. "tests.test_spec[.Class]");
            # record every component so module membership checks work
            seen_modules.update((case.get("classname") or "").split("."))
            if case.find("skipped") is not None:
                skipped.append(f"{case.get('classname')}::{case.get('name')}")
    unexpected = [t for t in skipped
                  if not any(k in t for k in ALLOWED_SKIPS)]
    # stale allowlist entries are as much a bug as silent skips: an entry
    # whose test no longer skips (or no longer exists) must be removed
    unmatched = [k for k in ALLOWED_SKIPS
                 if not any(k in t for t in skipped)]
    missing = [m for m in EXPECTED_MODULES if m not in seen_modules]
    print(f"[check_skips] {total} tests, {failures} failures, "
          f"{errors} errors, {len(skipped)} skipped "
          f"(allowlist entries: {len(ALLOWED_SKIPS)}; "
          f"{len(seen_modules)} modules seen)")
    if unexpected or unmatched or missing:
        for t in unexpected:
            print(f"[check_skips]   unexpected skip: {t}")
        for k in unmatched:
            print(f"[check_skips]   stale allowlist entry: {k!r} "
                  f"({ALLOWED_SKIPS[k]})")
        for m in missing:
            print(f"[check_skips]   missing module: {m} contributed no "
                  "testcases (collection error or deselected?)")
        print("[check_skips] FAIL: every skip must match a reasoned "
              "allowlist entry in scripts/check_skips.py (and every entry "
              "must still skip), and every EXPECTED_MODULES file must "
              "contribute tests — or update the lists")
        return 1
    print("[check_skips] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "report.xml"))
