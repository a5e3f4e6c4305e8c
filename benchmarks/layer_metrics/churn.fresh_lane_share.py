"""Share of the requests decided that rode a `fresh` lane (a key the directory
had to give a slot, whose row the device program re-initialises):
`engine.directory.inserts` over `engine.stats.requests`, diffs across the
run's window. Every fresh lane is one insert of `native/keydir.cpp
lookup_batch`, counted there and not in `Engine._launch`. 1.0 where every
request names a key never seen (benchmarks/churn_math.py)."""

from churn_math import per_decision

LAYER = "host prep"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return per_decision(scrapes, "inserts")
