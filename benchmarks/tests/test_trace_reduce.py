"""The reduction from device events to busy share, op time and launches,
on a hand-made trace and on a small one recorded on the chip."""

import gzip
import json
import os

import pytest

import trace_reduce
from conftest import HERE

MS = 1e6  # ns


def test_hand_made_trace():
    dev = "/device:TPU:0"
    events = [
        # launch 1: a fusion of 2 ms with a nested child, then a copy
        (dev, "XLA Modules", "jit_decide(1)", 0 * MS, 3.5 * MS),
        (dev, "XLA Ops", "fusion.1", 0 * MS, 2 * MS),
        (dev, "XLA Ops", "child.1", 0.5 * MS, 1 * MS),
        (dev, "XLA Ops", "copy.2", 2.5 * MS, 1 * MS),
        # 4.5 ms idle, then launch 2
        (dev, "XLA Modules", "jit_decide(1)", 8 * MS, 2 * MS),
        (dev, "XLA Ops", "fusion.1", 8 * MS, 2 * MS),
    ]
    got = trace_reduce.reduce_events(events, window_s=0.020)
    assert got["devices"] == 1 and got["launches"] == 2
    assert got["busy_s"] == pytest.approx(0.005)
    ops = dict(map(tuple, got["breakdown"]["device_ops"]))
    assert ops == pytest.approx({"fusion.1": 0.004, "copy.2": 0.001})
    gaps = got["breakdown"]["idle_gaps"]
    assert gaps[0] == ["after copy.2", pytest.approx(0.0045)]
    assert 1 - got["busy_s"] / got["window_s"] == pytest.approx(0.75)


def test_two_devices_are_averaged():
    events = [(f"/device:TPU:{d}", "XLA Ops", "fusion", 0.0, (d + 1) * MS)
              for d in range(2)]
    got = trace_reduce.reduce_events(events, window_s=0.010)
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(0.0015)


def test_no_device_events_is_no_busy_time():
    got = trace_reduce.reduce_events([], window_s=1.0)
    assert got["busy_s"] == 0.0 and got["launches"] == 0.0


def test_recorded_chip_trace():
    """300 ms of the device plane of one traced run of node10m.batch1000 on
    a TPU v5 lite; the expected numbers were read off the same events by
    rasterising them at 1 us (see PERF.md section 6)."""
    path = os.path.join(HERE, "trace_fixture.json.gz")
    with gzip.open(path, "rt") as f:
        fixture = json.load(f)
    got = trace_reduce.reduce_events(
        [tuple(e) for e in fixture["events"]], fixture["window_s"])
    want = fixture["expected"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-3)
    assert got["launches"] == want["launches"]
    assert [n for n, _ in got["breakdown"]["device_ops"][:3]] == [
        n[:120] for n in want["top_ops"]]
