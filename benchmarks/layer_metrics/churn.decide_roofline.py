"""The decide programs' share of the HBM roofline (a fresh lane gathers and
scatters the same row as any other, so `peaks.decide_bytes` stands; no
kernel is new) in the cell whose every request is a new key:
`decide_roofline`'s arithmetic
(benchmarks/layer_metrics/decide_roofline.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.decide_roofline import read  # noqa: F401

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "decisions_per_s"
