"""What a call waits in the native front before a pull worker takes it in the
cell whose every request is a new key: `front_wait_ms`'s arithmetic
(benchmarks/layer_metrics/front_wait_ms.py); that metric lists its cells and
this one is not among them."""

from layer_metrics.front_wait_ms import read  # noqa: F401

LAYER = "wire front"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"
