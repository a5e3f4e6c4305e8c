"""Share of the scan rounds whose rows rode the scan's carry:
`engine.stats.scan_rounds_carried` over `scan_rounds`, diffs across the
run's window. A carried dispatch gathers a key's row once before its
rounds and scatters it once after them (`ops/decide.py _scan_carried`); the
others pay a row gather and a row scatter a round against the table. The
rounds of one call's repeated keys are nested, so they all ride the carry;
what does not is a group launch of different callers' windows. A daemon
without the counter (the parent of the change that added it) gives None
(benchmarks/hot_math.py)."""

from hot_math import stat_ratio

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return stat_ratio(scrapes, "scan_rounds_carried", "scan_rounds")
