"""`hot.carried_round_share` (PR 39): found by name and agreeing with the
manifest; on the recorded scrapes of the cell's traffic, taken before the
daemon counted `scan_rounds_carried`, it gives None and does not raise (a
parent's daemon), and with the counter it is the counters' ratio.
"""

import copy
import json
import os

import pytest

import run
from conftest import HERE, REPO

NAME = "hot.carried_round_share"
CELL = "hot10m.repeats1000"


@pytest.fixture()
def scrapes():
    with open(os.path.join(HERE, "hot_scrape_fixture.json")) as f:
        return json.load(f)


def test_the_reader_is_found_by_name_and_agrees_with_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert entry["workloads"] == [CELL]
    reader = run.load_reader(NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == \
        (entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"]
                              if m is not entry}


def test_a_daemon_without_the_counter_gives_none(scrapes):
    assert "scan_rounds_carried" not in \
        scrapes["after"]["vars"]["engine"]["stats"]
    assert run.load_reader(NAME).read(scrapes, None) is None


@pytest.mark.parametrize("carried, share", [(282, 1.0), (94, 94 / 282),
                                            (0, 0.0)])
def test_with_the_counter_it_is_the_share_of_scan_rounds(
        scrapes, carried, share):
    s = copy.deepcopy(scrapes)
    rounds = s["after"]["vars"]["engine"]["stats"]["scan_rounds"] \
        - s["before"]["vars"]["engine"]["stats"]["scan_rounds"]
    assert rounds == 282
    s["before"]["vars"]["engine"]["stats"]["scan_rounds_carried"] = 7
    s["after"]["vars"]["engine"]["stats"]["scan_rounds_carried"] = 7 + carried
    assert run.load_reader(NAME).read(s, None) == pytest.approx(share)
