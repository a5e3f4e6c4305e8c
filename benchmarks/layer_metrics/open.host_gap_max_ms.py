"""The longest scheduling gap of the benchmark's own parent process in the
window, ms: a thread that sleeps 10 ms at a time woke this
much late (`host_pressure.Watch`). It sends nothing and waits for nothing,
so a gap of a tenth of a second or more is the host holding every process
on it, the load generators and the daemon too; beside `open.send_lag_ms` it
says how much of the open loop's tail is the machine's."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    parent = (scrapes["loadgen"].get("host") or {}).get("parent")
    return None if parent is None else parent["gap_max_ms"]
