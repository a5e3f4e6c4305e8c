"""Server-side residency of one call (last parsed byte to reply written)
in the repeated-key cell: `front_call_ms`'s arithmetic. With
`hot.front_wait_ms` it says how much of a call is queueing."""

from layer_metrics.front_call_ms import read  # noqa: F401

LAYER = "wire front"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"
