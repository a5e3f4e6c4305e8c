"""XLA compiles (or loads from the persistent cache) inside the run's
window: `engine.device.compiles.count` of /v1/debug/vars, after minus
before. Every shape is warmed before `Ready`, so anything but 0 is a
compile inside a request."""

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "compiles"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    def count(which):
        device = scrapes[which]["vars"]["engine"]["device"]
        return (device.get("compiles") or {}).get("count")

    after, before = count("after"), count("before")
    if after is None or before is None:
        return None
    return after - before
