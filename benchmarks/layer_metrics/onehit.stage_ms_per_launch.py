"""What the host does to a launch's bytes before the jitted call (here the lean
conversion: `lean_stage`'s masks and two sorts over the live lanes, under the
engine lock), per launch in the cell whose every launch rides the lean lane:
`stage_ms_per_launch`'s arithmetic
(benchmarks/layer_metrics/stage_ms_per_launch.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.stage_ms_per_launch import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"
