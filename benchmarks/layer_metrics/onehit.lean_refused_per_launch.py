"""Launches the lean lane refused over the launches made: the five
`engine.stats.lean_refused_<reason>` counters (capacity, hits, gregorian,
range, tuples; `Engine._launch` bumps the one `lean_stage` names), summed,
over the `launch` phase's observations, diffs across the run's window. 0
where the deployment is on the lane, 1.0 where every launch pays the
refusal and rides compact or wide. A daemon without the counters gives None
(benchmarks/onehit_math.py)."""

from onehit_math import lean_refused_per_launch

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "launches"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return lean_refused_per_launch(scrapes)
