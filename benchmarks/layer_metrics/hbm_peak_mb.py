"""The allocator's peak on the fullest chip since the daemon started, MB:
the largest `peak_bytes_in_use` of `engine.device.memory` in /v1/debug/vars
(`device.memory_stats()`), read after the window. It holds the table and
whatever a decide program keeps beside it."""

from scrape_math import device_peak_bytes

LAYER = "device program"
SOURCE = "program_counter"
UNIT = "MB"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    peak = device_peak_bytes(scrapes["after"])
    return None if peak is None else peak / 1e6
