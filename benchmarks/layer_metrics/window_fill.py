"""Lanes decided over lanes offered: requests the engine decided inside the
run's window / (engine windows x the window width), from the
/v1/debug/vars diff. An engine window is one staged batch of at most
GUBER_MAX_BATCH_WIDTH lanes (`engine.stats.batches`), whoever formed it:
the native front's pull loop or the Python combiner."""

from scrape_math import engine_diff

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    d = engine_diff(scrapes)
    if not d["batches"]:
        return None
    return d["requests"] / (
        d["batches"] * int(scrapes["settings"]["GUBER_MAX_BATCH_WIDTH"]))
