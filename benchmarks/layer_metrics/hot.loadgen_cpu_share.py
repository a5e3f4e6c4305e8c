"""CPU the load generators used over window x processes in the
repeated-key cell: `loadgen_cpu_share`'s arithmetic."""

from layer_metrics.loadgen_cpu_share import read  # noqa: F401

LAYER = "load generator"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"
