"""The readers of the launch cycle (PR 40): span_tree's nesting and self
time and `idle_share.host.launching` on a hand-made trace and on a small
recorded one (cycle_spans_fixture.json.gz: 60 ms of the device's `XLA Ops`
line and every span of the daemon's from one traced run of
`node10m.batch1000` on a TPU v5 lite, PERF.md section 6, PR 40; its
expected values were read off the same tuples by rasterising them at
1 us), and the eight new
per-layer readers on a recorded pair of scrapes with the new fields
(cycle_scrape_fixture.json) and on a daemon from before them
(scrape_fixture.json)."""

import gzip
import json
import os

import pytest

import cycle_math
import host_spans
import run
import span_tree
from conftest import HERE, REPO

MS = 1e6  # ns
DEV = "/device:TPU:0"
NEW = ("stage_ms_per_launch", "launch_ms_per_launch",
       "device_wait_ms_per_launch", "fetch_ms_per_launch",
       "link_bytes_per_decision", "lock_hold_share", "loop_ms_per_pull",
       "idle_share.host.launching")
FROM_A_CAPTURE = NEW[-2:]
CELLS_THEN = ("node10m.batch1000", "node10m.herd100", "mesh40m.batch1000",
              "hot10m.repeats1000")


def load_json(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


@pytest.fixture()
def recorded():
    with gzip.open(os.path.join(HERE, "cycle_spans_fixture.json.gz"),
                   "rt") as f:
        fixture = json.load(f)
    fixture["ops"] = {dev: [tuple(o) for o in iv]
                      for dev, iv in fixture["ops"].items()}
    fixture["spans"] = [tuple(s) for s in fixture["spans"]]
    return fixture


def read(name, scrapes, trace=None):
    return run.load_reader(name).read(scrapes, trace)


# ---- span_tree on a hand-made trace


def hand_made():
    return [
        (1, "pull", 0 * MS, 10 * MS),
        (1, "alloc", 0.5 * MS, 1 * MS),
        (1, "lock_wait", 1 * MS, 2 * MS),
        (1, "prep", 2 * MS, 3 * MS),
        (1, "dispatch", 3 * MS, 5 * MS),
        (1, "stage", 3 * MS, 4 * MS),
        (1, "launch", 4 * MS, 5 * MS),
        (1, "readback", 5 * MS, 8 * MS),
        (1, "device_wait", 5 * MS, 7 * MS),
        (1, "fetch", 7 * MS, 7.5 * MS),
        (1, "front.pull_wait", 10 * MS, 20 * MS),
        # the combiner: blocked, then forming, then the engine's chain
        (2, "combiner.wait", 0 * MS, 12 * MS),
        (2, "combiner.form", 12 * MS, 13 * MS),
        (2, "prep", 13 * MS, 16 * MS),
        (2, "dispatch", 16 * MS, 18 * MS),
        (2, "launch", 17 * MS, 18 * MS),
    ]


def test_a_span_hangs_from_the_innermost_one_that_contains_it():
    trees = span_tree.forest(hand_made())
    assert sorted(trees) == [1, 2]
    pull, wait = trees[1]
    assert (pull.name, wait.name) == ("pull", "front.pull_wait")
    assert [c.name for c in pull.children] == [
        "alloc", "lock_wait", "prep", "dispatch", "readback"]
    dispatch, readback = pull.children[3], pull.children[4]
    assert [c.name for c in dispatch.children] == ["stage", "launch"]
    assert [c.name for c in readback.children] == ["device_wait", "fetch"]
    # self time: the duration less what the children cover
    assert pull.self_time == pytest.approx(10 * MS - 7.5 * MS)
    assert dispatch.self_time == pytest.approx(0.0)
    assert readback.self_time == pytest.approx(0.5 * MS)
    assert span_tree.self_time_mean_ms(trees, "pull") == pytest.approx(2.5)
    assert span_tree.self_time_mean_ms(trees, "leftover") is None
    # the combiner's spans follow one another: none is another's child
    assert [n.name for n in trees[2]] == [
        "combiner.wait", "combiner.form", "prep", "dispatch"]
    assert span_tree.threads_with(trees, "combiner.form") == [2]
    assert span_tree.threads_with(trees, "launch") == [1, 2]


def test_coverage_is_the_union_of_a_threads_spans():
    trees = span_tree.forest(hand_made())
    assert span_tree.coverage(trees[1], 0, 20 * MS) == pytest.approx(1.0)
    assert span_tree.coverage(trees[2], 0, 20 * MS) == pytest.approx(0.9)
    assert span_tree.coverage(trees[1], 0, 20 * MS, names={"pull"}) \
        == pytest.approx(0.5)
    assert span_tree.coverage(trees[1], 0, 20 * MS,
                              names={"stage", "fetch"}) \
        == pytest.approx(1.5 / 20)


def test_launching_is_the_part_of_host_with_somebody_on_the_way():
    ops = {DEV: [(4.5 * MS, 7 * MS), (17.5 * MS, 19 * MS)]}
    # what host_spans.load keeps of a capture
    old = [s for s in hand_made() if host_spans.is_ours(s[1])]
    host = host_spans.split_idle(ops, old, busy_s=0.004,
                                 window_s=0.020)["host"]
    got = cycle_math.launching_share(ops, old, busy_s=0.004,
                                     window_s=0.020)
    # idle 0-4.5, 7-17.5, 19-20; the one pull worker waits from 10 on
    # (no work, whatever the combiner's thread does meanwhile); prep or
    # dispatch open in 2-4.5 of what is left
    assert host == pytest.approx((4.5 + 3) / 20)
    assert got == pytest.approx(2.5 / 20)
    assert got <= host
    # a background unit over the launching spans takes them, as it takes
    # everything: housekeeping comes first
    audit = old + [(3, "bg:ledger.audit", 0.0, 4 * MS)]
    assert cycle_math.launching_share(ops, audit, 0.004, 0.020) \
        == pytest.approx(0.5 / 20)
    assert cycle_math.launching_share({}, old, 0.004, 0.020) is None
    assert not span_tree.is_ours("$profile.py:120 capture")
    assert span_tree.is_ours("bg:ledger.audit")
    assert span_tree.is_ours("leftover.serve")


# ---- and on a recorded one


def test_recorded_trace(recorded):
    want = recorded["expected"]
    trees = span_tree.forest(recorded["spans"])
    pulls = span_tree.named(trees, "pull")
    assert len(pulls) == want["pulls"] > 0
    # a pull has a dozen children, each edge rasterised to 1 us
    assert span_tree.self_time_mean_ms(trees, "pull") == pytest.approx(
        want["pull_self_ms"], abs=0.03)
    for pull in pulls:
        assert 0 <= pull.self_time <= pull.duration
        assert all(pull.start <= c.start and c.end <= pull.end
                   for c in pull.children)
    # the funnels' spans hang from their phase
    for child, parent in (("stage", "dispatch"), ("launch", "dispatch"),
                          ("device_wait", "readback"),
                          ("fetch", "readback")):
        inside = {id(c) for p in span_tree.named(trees, parent)
                  for c in p.children}
        whole = [n for n in span_tree.named(trees, child)
                 if n.start > 0]  # not cut by the slice's edge
        assert whole and all(id(n) in inside for n in whole), child
    old = [s for s in recorded["spans"] if host_spans.is_ours(s[1])]
    host = host_spans.split_idle(recorded["ops"], old, want["busy_s"],
                                 recorded["window_s"])["host"]
    got = cycle_math.launching_share(recorded["ops"], old, want["busy_s"],
                                     recorded["window_s"])
    assert host == pytest.approx(want["host"], abs=2e-3)
    assert got == pytest.approx(want["launching"], abs=2e-3)
    assert 0 < got <= host


# ---- the eight readers


def test_every_reader_gives_none_on_a_daemon_from_before_them():
    scrapes = load_json("scrape_fixture.json")
    for trace in (None, {"window_s": 2.0, "busy_s": 1.0}):
        for name in NEW:
            assert read(name, scrapes, trace) is None, name
    # a daemon that writes the old spans only: a capture is there, the
    # new spans are not
    scrapes["after"]["profile"]["capture"].update(
        last_path="a capture", last_mode="jax_trace")
    old = [s for s in hand_made() if host_spans.is_ours(s[1])
           and s[2] >= 10 * MS]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(span_tree, "load", lambda path: old)
        mp.setattr(host_spans, "load", lambda path: (
            {DEV: [(0.0, 2 * MS)]}, old))
        for name in FROM_A_CAPTURE:
            assert read(name, scrapes, {"window_s": 0.02,
                                        "busy_s": 0.002}) is None, name


def test_the_scrape_readers_on_a_daemon_with_the_new_fields():
    scrapes = load_json("cycle_scrape_fixture.json")
    a, b = scrapes["after"], scrapes["before"]

    def total(phase, key="total_ns"):
        return (a["profile"]["phases"][phase][key]
                - b["profile"]["phases"][phase][key])

    def stat(key):
        return (a["vars"]["engine"]["stats"][key]
                - b["vars"]["engine"]["stats"][key])

    launches = total("launch", "n")
    assert launches == total("stage", "n") > 0
    # the wait is told from the copy while a capture runs, and only then:
    # the fixture's window holds one of 0.4 s
    fetched_apart = total("device_wait", "n")
    assert 0 < fetched_apart == total("fetch", "n") < launches
    for name, phase, n in (
            ("stage_ms_per_launch", "stage", launches),
            ("launch_ms_per_launch", "launch", launches),
            ("device_wait_ms_per_launch", "device_wait", fetched_apart),
            ("fetch_ms_per_launch", "fetch", fetched_apart)):
        assert read(name, scrapes) == pytest.approx(
            total(phase) / n / 1e6), name
    # the parts lie inside their phase
    assert total("stage") + total("launch") <= total("dispatch")
    assert total("device_wait") + total("fetch") <= total("readback")
    assert read("link_bytes_per_decision", scrapes) == pytest.approx(
        (stat("staged_bytes") + stat("fetched_bytes")) / stat("requests"))
    # every call repeats six keys: rounds of a few live lanes ride
    # 64-lane stacks, so a decision moves far more than a lean lane's
    # 4 bytes up and 16 back
    assert read("link_bytes_per_decision", scrapes) > 20
    # over the run's window, which the callers send for: a second scrape
    # that comes late (behind a slow capture) adds no holds
    assert read("lock_hold_share", scrapes) == pytest.approx(
        total("lock_hold") / 1e9 / scrapes["window_s"])
    assert 0 < read("lock_hold_share", scrapes) <= 1
    a["at"] += 20.0
    assert read("lock_hold_share", scrapes) == pytest.approx(
        total("lock_hold") / 1e9 / scrapes["window_s"])
    sites = a["profile"]["lock_hold_sites"]
    assert sum(h["total_ns"] for h in sites.values()) == \
        a["profile"]["phases"]["lock_hold"]["total_ns"]
    # no capture inside the window, so no launch fetched apart: no mean
    a["profile"]["phases"]["fetch"] = b["profile"]["phases"]["fetch"]
    assert read("fetch_ms_per_launch", scrapes) is None
    assert read("launch_ms_per_launch", scrapes) is not None


def test_the_capture_readers_on_a_recorded_trace(recorded, monkeypatch):
    scrapes = load_json("cycle_scrape_fixture.json")
    scrapes["after"]["profile"]["capture"].update(
        last_path="the fixture", last_mode="jax_trace")
    monkeypatch.setattr(span_tree, "load", lambda path: recorded["spans"])
    monkeypatch.setattr(host_spans, "load", lambda path: (
        recorded["ops"],
        [s for s in recorded["spans"] if host_spans.is_ours(s[1])]))
    trace = {"window_s": recorded["window_s"],
             "busy_s": recorded["expected"]["busy_s"]}
    assert read("loop_ms_per_pull", scrapes, trace) == pytest.approx(
        recorded["expected"]["pull_self_ms"], abs=0.03)
    launching = read("idle_share.host.launching", scrapes, trace)
    assert launching == pytest.approx(recorded["expected"]["launching"],
                                      abs=2e-3)
    # run.py's host_spans readers, on the same capture
    for name in ("idle_share.host", "hot.idle_share.host",
                 "mesh.idle_share.host"):
        assert launching <= read(name, scrapes, trace)
    # a wall-sampler capture holds no spans
    scrapes["after"]["profile"]["capture"]["last_mode"] = "wall_sampler"
    for name in FROM_A_CAPTURE:
        assert read(name, scrapes, trace) is None


def test_the_manifest_lists_the_eight_in_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        # the four cells the benchmark had when PR 40 added them; a cell
        # added since comes under its own prefix or is appended here
        assert set(CELLS_THEN) <= set(entries[name]["workloads"]) <= cells
        assert not name.startswith(("hot.", "mesh."))
