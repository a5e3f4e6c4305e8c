"""Time a call's frame sat in the native front's queue before a pull
worker took it, in the cell where both workers spend most of a call inside
`_leftover_items`: `front_wait_ms`'s arithmetic."""

from layer_metrics.front_wait_ms import read  # noqa: F401

LAYER = "wire front"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"
