"""What the native front itself costs a call: parsing it (HTTP/2 DATA and
protobuf into columns, `front_parse`) plus serialising and writing its reply
(`front_write`), per frame pulled, in microseconds; diffs across the run's
window."""

from front_math import front_counter_delta, phase_delta

LAYER = "wire front"
SOURCE = "program_span"
UNIT = "us"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    parse = phase_delta(scrapes, "front_parse")
    write = phase_delta(scrapes, "front_write")
    frames = front_counter_delta(scrapes, "frames_pulled")
    if parse is None or write is None or not frames:
        return None
    return (parse[1] + write[1]) / frames / 1e3
