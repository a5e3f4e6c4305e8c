"""The combiner's queue wait per engine window in the cell whose every request
is a new key: `queue_wait_ms`'s arithmetic
(benchmarks/layer_metrics/queue_wait_ms.py); that metric lists its cells and
this one is not among them."""

from layer_metrics.queue_wait_ms import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"
