"""Calls one pull of the front's loop took at once in the repeated-key
cell: `frames_per_pull`'s arithmetic. A pull of K calls is K chunks, each
with a leftover tail of its own."""

from layer_metrics.frames_per_pull import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "frames"
MOVES = "decisions_per_s"
