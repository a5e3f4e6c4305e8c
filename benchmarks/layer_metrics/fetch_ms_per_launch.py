"""The copy of a launch's answers back to the host and their widening
(`np.asarray`, `widen_compact_out`), per launch: the `fetch` phase of
/v1/debug/profile, its total over its own count, diffs across the run's
window; observed, as `device_wait` is, for the launches fetched while a
capture runs."""

from front_math import phase_mean_ms

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_mean_ms(scrapes, "fetch")
