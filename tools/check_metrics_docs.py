#!/usr/bin/env python
"""Metrics-golden check: every Counter/Gauge/Histogram/Summary registered in
keto_tpu/observability.py must appear in the docs metrics table
(docs/architecture.md §5d). Run by the CI test job and by
tests/test_observability.py, so a new metric cannot land undocumented —
the table is the operator contract for dashboards and alerts.

Exit 1 lists the missing names; documented-but-unregistered names are
reported too (a stale table misleads the same dashboards).

Pure source inspection via the analysis plane's shared scanner
(keto_tpu/analysis/source_scan.py — the same walker under ketolint's
config-key pass), so it runs before deps are installed and cannot be
skewed by runtime registration.
"""

from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # `python tools/check_metrics_docs.py`

from keto_tpu.analysis.source_scan import scan_pattern  # noqa: E402

OBSERVABILITY = REPO / "keto_tpu" / "observability.py"
DOCS = REPO / "docs" / "architecture.md"

# prom.Counter( \n "metric_name"  — the registration shape used in
# observability.Metrics (name is always the first string literal)
_REGISTRATION = r"prom\.(?:Counter|Gauge|Histogram|Summary)\(\s*\"(keto_tpu_[a-z0-9_]+)\""
# docs table rows cite metrics as `keto_tpu_...` code spans
_DOCUMENTED = r"`(keto_tpu_[a-z0-9_]+)`"


def registered_metrics() -> set[str]:
    return scan_pattern(_REGISTRATION, [OBSERVABILITY])


def documented_metrics() -> set[str]:
    return scan_pattern(_DOCUMENTED, [DOCS])


def main() -> int:
    registered = registered_metrics()
    if not registered:
        print(f"ERROR: no metric registrations found in {OBSERVABILITY}")
        return 1
    documented = documented_metrics()
    missing = sorted(registered - documented)
    stale = sorted(documented - registered)
    rc = 0
    if missing:
        rc = 1
        print(
            f"ERROR: {len(missing)} metric(s) registered in "
            f"{OBSERVABILITY.name} but missing from the "
            f"{DOCS.name} metrics table:"
        )
        for name in missing:
            print(f"  - {name}")
    if stale:
        rc = 1
        print(
            f"ERROR: {len(stale)} metric name(s) documented in "
            f"{DOCS.name} but not registered in {OBSERVABILITY.name}:"
        )
        for name in stale:
            print(f"  - {name}")
    if rc == 0:
        print(f"ok: {len(registered)} metrics registered and documented")
    return rc


if __name__ == "__main__":
    sys.exit(main())
