"""The allocator's peak on the fullest chip since the daemon started, MB:
the largest `peak_bytes_in_use` of `engine.device.memory` in /v1/debug/vars
(`device.memory_stats()`), read after the window. It holds the table and
whatever a decide program keeps beside it."""

LAYER = "device program"
SOURCE = "program_counter"
UNIT = "MB"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    memory = scrapes["after"]["vars"]["engine"]["device"].get("memory")
    peaks = [m.get("peak_bytes_in_use") for m in memory or []]
    peaks = [p for p in peaks if p is not None]
    if not peaks:
        return None
    return max(peaks) / 1e6
