"""What a window waits for the engine lock: the `lock_wait` phase's total in
/v1/debug/profile over `engine.stats.batches`, diffs across the run's
window. A tombstone rebuild runs inside one window's `prep` with the lock
held, so every sibling's wait for it lands here."""

from scrape_math import phase_ms_per_window

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_window(scrapes, "lock_wait")
