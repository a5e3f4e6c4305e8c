"""XLA compiles (or loads from the persistent cache) inside the run's
window on the width ladder (2048..8192), where a launch could take a
(depth, width) shape the warm-up did not compile: `compiles_in_window`'s arithmetic, in the cell
that is not on that reader's list. The daemon's `profile.compile` events
name each program."""

from layer_metrics.compiles_in_window import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "compiles"
MOVES = "decisions_per_s"
