"""Device idle time split by what the host was doing: on a hand-made
trace, and on 300 ms of one traced run on the chip whose expected shares
were read off the same tuples by rasterising them at 1 us."""

import gzip
import json
import os

import pytest

import host_spans
from conftest import HERE

MS = 1e6  # ns
DEV = "/device:TPU:0"


def test_hand_made_trace():
    ops = {DEV: [(0 * MS, 2 * MS), (8 * MS, 10 * MS)]}
    spans = [
        # two pull-loop workers; both wait in 3-5 ms and 14-20 ms
        (1, "front.pull_wait", 3 * MS, 5 * MS),
        (1, "prep", 5 * MS, 8 * MS),
        (1, "front.pull_wait", 12 * MS, 20 * MS),
        (2, "front.pull_wait", 2.5 * MS, 6 * MS),
        (2, "readback", 6 * MS, 10 * MS),
        (2, "front.pull_wait", 14 * MS, 20 * MS),
        # a ticker: its unit covers 4-9 ms, with a nested one, and 15-16 ms
        (3, "bg:anomaly.check", 4 * MS, 9 * MS),
        (3, "bg:ledger.audit", 5 * MS, 8.5 * MS),
        (3, "bg:history.sample", 15 * MS, 16 * MS),
    ]
    got = host_spans.split_idle(ops, spans, busy_s=0.004, window_s=0.020)
    # idle 2-8 and 10-20 ms. housekeeping: 4-8 and 15-16. no work: 3-4,
    # 14-15, 16-20. the host's: 2-3, 10-14
    assert got == pytest.approx(
        {"housekeeping": 0.25, "no_work": 0.30, "host": 0.25})
    assert sum(got.values()) == pytest.approx(1 - 0.004 / 0.020)


def test_time_the_trace_does_not_cover_is_the_hosts():
    ops = {DEV: [(0.0, 2 * MS)]}
    spans = [(1, "front.pull_wait", 2 * MS, 10 * MS)]
    got = host_spans.split_idle(ops, spans, busy_s=0.002, window_s=0.020)
    assert got == pytest.approx(
        {"housekeeping": 0.0, "no_work": 0.40, "host": 0.50})


def test_a_capture_that_outlasts_its_window_is_cut_to_it():
    """The stall that the capture records can keep its own thread from
    waking: 25 ms of trace for 20 ms asked. The audit's last 5 ms are not
    in the window, and neither share may count them."""
    ops = {DEV: [(0.0, 12 * MS)]}
    spans = [(1, "readback", 0.0, 12 * MS),
             (3, "bg:ledger.audit", 10 * MS, 25 * MS)]
    got = host_spans.split_idle(ops, spans, busy_s=0.012, window_s=0.020)
    assert got == pytest.approx(
        {"housekeeping": 0.40, "no_work": 0.0, "host": 0.0})


def test_two_devices_are_averaged():
    ops = {DEV: [(0.0, 10 * MS)], "/device:TPU:1": [(0.0, 4 * MS)]}
    spans = [(1, "bg:ledger.audit", 0.0, 10 * MS)]
    got = host_spans.split_idle(ops, spans, busy_s=0.007, window_s=0.010)
    assert got == pytest.approx(
        {"housekeeping": 0.30, "no_work": 0.0, "host": 0.0})


def test_a_trace_without_the_daemons_spans_gives_none():
    ops = {DEV: [(0.0, 2 * MS)]}
    assert host_spans.split_idle(ops, [], 0.002, 0.020) is None
    assert host_spans.split_idle({}, [(1, "prep", 0.0, MS)], 0.0, 0.02) is None
    assert not host_spans.is_ours("$profile.py:120 capture")
    assert host_spans.is_ours("bg:ledger.audit") and host_spans.is_ours("post")


def test_no_jax_trace_no_share():
    scrapes = {"after": {"profile": {"capture": {
        "last_path": "/nowhere", "last_mode": "wall_sampler"}}}}
    trace = {"window_s": 2.0, "busy_s": 1.0}
    assert host_spans.read_share(scrapes, trace, "host") is None
    assert host_spans.read_share(scrapes, None, "host") is None


def test_recorded_chip_trace():
    """300 ms of the device's `XLA Ops` line and the daemon's own host
    spans from one traced run on a TPU v5 lite (PERF.md section 6, PR 25),
    cut around the start of the longest background unit."""
    with gzip.open(os.path.join(HERE, "host_spans_fixture.json.gz"),
                   "rt") as f:
        fixture = json.load(f)
    want = fixture["expected"]
    got = host_spans.split_idle(
        {DEV: [tuple(o) for o in fixture["ops"]]},
        [tuple(s) for s in fixture["spans"]],
        want["busy_s"], fixture["window_s"])
    for which in ("no_work", "housekeeping", "host"):
        assert got[which] == pytest.approx(want[which], abs=2e-3), which
    # the three shares are the idle share, split
    assert sum(got.values()) == pytest.approx(
        1 - want["busy_s"] / fixture["window_s"])
    assert got["housekeeping"] > 0.05  # the slice holds a stall
