"""CPU seconds the load-generator processes used inside the window, over
window x processes. Near 1.0 means a starved generator, not a fast server."""

LAYER = "load generator"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    lg = scrapes["loadgen"]
    return sum(lg["cpu_s"]) / (scrapes["window_s"] * lg["processes"])
