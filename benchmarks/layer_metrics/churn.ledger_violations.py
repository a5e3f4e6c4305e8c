"""Violations the decision ledger's audit reported inside the run's window:
`ledger.violations` of /v1/debug/vars, after minus before. The audit
resolves a tick's lanes to keys by slot while slots change hands under it; a
key evicted and come back is a new bucket and no over-admission. At 10M
slots the keys it tracks are evicted within the first turnover, so on the
chip a 0 says little (PERF.md section 7); tests/test_churn_deployment.py (e)
holds the case (benchmarks/churn_math.py)."""

from churn_math import ledger_violations

LAYER = "housekeeping"
SOURCE = "program_counter"
UNIT = "count"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return ledger_violations(scrapes)
