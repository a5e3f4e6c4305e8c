"""Time the serving path waited with its pipeline full before it could
launch the next window, per engine window, in the cell whose leftovers go
through the Python combiner (the only cell that reaches its pipeline):
`queue_wait_ms`'s arithmetic."""

from layer_metrics.queue_wait_ms import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"
