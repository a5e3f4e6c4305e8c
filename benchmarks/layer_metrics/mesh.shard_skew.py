"""How unevenly a window's lanes fall on the owner chips: the fullest
shard's lanes, summed over the windows (`engine.stats.lanes_max`), times
the shards, over the requests decided. 1.0 is an even split; the fullest
shard sets the padded width of the launch, so the chips wait for it."""

from mesh_math import shards, stat_diff

LAYER = "host prep"
SOURCE = "program_counter"
UNIT = "ratio"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    lanes_max = stat_diff(scrapes, "lanes_max")
    requests = stat_diff(scrapes, "requests")
    n = shards(scrapes)
    if lanes_max is None or not requests or not n:
        return None
    return lanes_max * n / requests
