"""Calls one pull of the front's loop took at once: `front.frames_pulled`
over `front.pulls` (pulls that took work), diffs across the run's window.
1.0 is one call a window; the engine can only merge what a pull hands it."""

from front_math import front_counter_delta

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "frames"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    frames = front_counter_delta(scrapes, "frames_pulled")
    pulls = front_counter_delta(scrapes, "pulls")
    if frames is None or not pulls:
        return None
    return frames / pulls
