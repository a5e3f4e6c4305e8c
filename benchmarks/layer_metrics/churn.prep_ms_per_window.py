"""Host preparation per engine window (the directory's inserts and evictions,
and a rebuild when one is due, are inside it) in the cell whose every
request is a new key: `prep_ms_per_window`'s arithmetic
(benchmarks/layer_metrics/prep_ms_per_window.py); that metric lists its
cells and this one is not among them."""

from layer_metrics.prep_ms_per_window import read  # noqa: F401

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"
