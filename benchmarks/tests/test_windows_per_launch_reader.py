"""`windows_per_launch` (PR 44): found by name and agreeing with its manifest
entry, which is looked up by name and not by position; on recorded scrapes
taken before the daemon recorded the `launch` phase it gives None and does
not raise (a parent's daemon), and with the phase it is the engine's
windows over the launches, diffs across the window."""

import copy
import json
import os

import pytest

import run
from conftest import HERE, REPO

NAME = "windows_per_launch"


def _scrapes(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_the_reader_is_found_by_name_and_agrees_with_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    entry = entries[0]
    # the five cells the benchmark had when PR 44 added it, and whichever
    # later cells were appended to its list
    cells = [w["name"] for w in manifest["workloads"]]
    assert entry["workloads"][:5] == cells[:5]
    assert set(entry["workloads"]) <= set(cells)
    assert entry["better"] == "higher"
    reader = run.load_reader(NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == \
        (entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"]
                              if m["name"] != NAME}
    moved = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert len(moved) == 1 and "workloads" not in moved[0]  # every cell's


def test_a_daemon_without_the_launch_phase_gives_none():
    scrapes = _scrapes("scrape_fixture.json")
    assert "launch" not in scrapes["after"]["profile"]["phases"]
    assert run.load_reader(NAME).read(scrapes, None) is None


@pytest.mark.parametrize("launches", [1, 2, 7])
def test_it_is_windows_over_launches(launches):
    s = copy.deepcopy(_scrapes("cycle_scrape_fixture.json"))
    windows = s["after"]["vars"]["engine"]["stats"]["batches"] \
        - s["before"]["vars"]["engine"]["stats"]["batches"]
    assert windows > 0
    phases = s["after"]["profile"]["phases"], s["before"]["profile"]["phases"]
    phases[0]["launch"]["n"] = phases[1]["launch"]["n"] + launches
    assert run.load_reader(NAME).read(s, None) == \
        pytest.approx(windows / launches)
    phases[0]["launch"]["n"] = phases[1]["launch"]["n"]  # no launch: None
    assert run.load_reader(NAME).read(s, None) is None
