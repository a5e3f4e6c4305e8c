"""The allocator's peak since boot on the width ladder (2048..8192), MB:
`hbm_peak_mb`'s reading in the cell that is not on that reader's list."""

from layer_metrics.hbm_peak_mb import read  # noqa: F401

LAYER = "device program"
SOURCE = "program_counter"
UNIT = "MB"
MOVES = "decisions_per_s"
