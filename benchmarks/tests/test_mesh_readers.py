"""The `mesh.*` readers (and `call_p99_ms.mesh`) on a recorded pair of
scrapes of a four-shard node (mesh_scrape_fixture.json: an in-process node
on the CPU, so counters and host clocks only) and on a parent-shaped pair:
`ShardedEngine` from before it owned a profiler and counted `lanes_max`."""

import copy
import json
import os

import pytest

import run
from conftest import HERE, REPO

CELL = "mesh40m.batch1000"
TRACE = {"window_s": 2.0, "busy_s": 0.53, "launches": 505.0}  # PR 32's capture


@pytest.fixture()
def scrapes():
    with open(os.path.join(HERE, "mesh_scrape_fixture.json")) as f:
        s = json.load(f)
    s["device_kind"] = "TPU v5 lite"
    s["latency_ms"] = {"p50": 32.3, "p99": 67.1}
    return s


@pytest.fixture()
def parent(scrapes):
    """What the parent's daemon answers: `engine.stats` without `lanes_max`
    (prep_ns and pack_ns are there), and the Instance's fallback profiler,
    which no engine stamp feeds."""
    old = copy.deepcopy(scrapes)
    for side in ("before", "after"):
        del old[side]["vars"]["engine"]["stats"]["lanes_max"]
        for phase in old[side]["profile"]["phases"].values():
            phase.update(n=0, total_ns=0, max_ns=0, p50_ns=0, p99_ns=0)
    return old


def names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]
                if m.get("workloads") == [CELL]]


def read(name, scrapes, trace=None):
    return run.load_reader(name).read(scrapes, trace)


def diff(scrapes, key):
    return scrapes["after"]["vars"]["engine"]["stats"][key] \
        - scrapes["before"]["vars"]["engine"]["stats"][key]


def phase(scrapes, name):
    a, b = (scrapes[k]["profile"]["phases"][name] for k in ("after", "before"))
    return a["total_ns"] - b["total_ns"], a["n"] - b["n"]


def test_the_cell_has_its_ten_readers():
    assert sorted(names()) == sorted([
        "mesh.route_ms_per_window", "mesh.pack_ms_per_window",
        "mesh.shard_skew", "mesh.readback_ms_per_window",
        "mesh.demux_ms_per_window", "mesh.device_ms_per_window",
        "mesh.decide_roofline", "mesh.device_idle_share",
        "mesh.idle_share.host", "call_p99_ms.mesh"])


def test_route_and_pack_are_the_private_clocks_over_the_windows(scrapes):
    windows = diff(scrapes, "batches")
    assert windows == 40
    route = read("mesh.route_ms_per_window", scrapes)
    pack = read("mesh.pack_ms_per_window", scrapes)
    assert route == pytest.approx(diff(scrapes, "prep_ns") / windows / 1e6)
    assert pack == pytest.approx(diff(scrapes, "pack_ns") / windows / 1e6)
    # both lie inside the prep phase, window for window
    prep_ns, n = phase(scrapes, "prep")
    assert n == windows and 0 < route + pack <= prep_ns / windows / 1e6


def test_readback_and_demux_are_the_phases_over_the_windows(scrapes):
    for name, ph in (("mesh.readback_ms_per_window", "readback"),
                     ("mesh.demux_ms_per_window", "demux")):
        ns, n = phase(scrapes, ph)
        assert n == 40
        assert read(name, scrapes) == pytest.approx(ns / 40 / 1e6) and ns > 0


def test_shard_skew_is_the_fullest_shard_over_an_even_share(scrapes):
    skew = read("mesh.shard_skew", scrapes)
    assert skew == pytest.approx(
        diff(scrapes, "lanes_max") * 4 / diff(scrapes, "requests"))
    assert 1.0 <= skew <= 1.2  # ~1000 keys over four shards by hash
    # every lane on one shard reads the number of shards
    scrapes["after"]["vars"]["engine"]["stats"]["lanes_max"] = \
        scrapes["before"]["vars"]["engine"]["stats"]["lanes_max"] \
        + diff(scrapes, "requests")
    assert read("mesh.shard_skew", scrapes) == pytest.approx(4.0)


def test_the_trace_readers(scrapes):
    assert read("mesh.device_ms_per_window", scrapes, TRACE) == \
        pytest.approx(0.53 / 505 * 1e3)
    assert read("mesh.device_idle_share", scrapes, TRACE) == \
        pytest.approx(1 - 0.53 / 2.0)
    # one chip decided a quarter of each launch's lanes, at 156 bytes a lane
    lanes = 505 * diff(scrapes, "requests") / diff(scrapes, "rounds") / 4
    want = 100 * lanes * 156 / 819e9 / 0.53
    got = read("mesh.decide_roofline", scrapes, TRACE)
    assert got == pytest.approx(want) and 0 < got < 0.01
    assert read("call_p99_ms.mesh", scrapes, TRACE) == 67.1
    # no capture on record in the fixture: nothing to split
    assert read("mesh.idle_share.host", scrapes, TRACE) is None


@pytest.mark.parametrize("name,reads", [
    ("mesh.route_ms_per_window", True), ("mesh.pack_ms_per_window", True),
    ("mesh.shard_skew", False), ("mesh.readback_ms_per_window", False),
    ("mesh.demux_ms_per_window", False), ("mesh.device_ms_per_window", True),
    ("mesh.decide_roofline", True), ("mesh.device_idle_share", True),
    ("mesh.idle_share.host", False), ("call_p99_ms.mesh", True)])
def test_on_the_parent_a_reader_reads_or_gives_none_and_never_raises(
        parent, name, reads):
    value = read(name, parent, TRACE)
    assert (value is not None) is reads, value


@pytest.mark.parametrize("name", [
    "mesh.device_ms_per_window", "mesh.decide_roofline",
    "mesh.device_idle_share", "mesh.idle_share.host"])
def test_without_a_trace_the_trace_readers_give_none(scrapes, name):
    assert read(name, scrapes, None) is None
    assert read(name, scrapes, {"window_s": 0.0, "busy_s": 0.0,
                                "launches": 0.0}) is None
