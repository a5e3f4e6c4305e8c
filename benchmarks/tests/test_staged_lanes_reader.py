"""`staged_lanes_per_decision` (PR 41): found by name and agreeing with its
manifest entry, which is looked up by name and not by position; on the
recorded scrapes of the hot cell's traffic, taken before the daemon counted
`staged_lanes`, it gives None and does not raise (a parent's daemon), and
with the counter it is the counters' ratio."""

import copy
import json
import os

import pytest

import run
from conftest import HERE, REPO

NAME = "staged_lanes_per_decision"
CELLS = ["node10m.batch1000", "node10m.herd100", "hot10m.repeats1000"]


@pytest.fixture()
def scrapes():
    with open(os.path.join(HERE, "hot_scrape_fixture.json")) as f:
        return json.load(f)


def _stats(scrapes, edge):
    return scrapes[edge]["vars"]["engine"]["stats"]


def test_the_reader_is_found_by_name_and_agrees_with_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["workloads"][:len(CELLS)] == CELLS  # later cells after
    assert entry["better"] == "lower"
    reader = run.load_reader(NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == \
        (entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"]
                              if m["name"] != NAME}
    one_chip = {w["name"] for w in manifest["workloads"] if w["chips"] == 1}
    assert set(CELLS) <= one_chip  # ShardedEngine does not count it


def test_a_daemon_without_the_counter_gives_none(scrapes):
    assert "staged_lanes" not in _stats(scrapes, "after")
    assert run.load_reader(NAME).read(scrapes, None) is None


@pytest.mark.parametrize("per_decision", [1.0, 6.5, 144.0])
def test_with_the_counter_it_is_lanes_over_requests(scrapes, per_decision):
    s = copy.deepcopy(scrapes)
    requests = _stats(s, "after")["requests"] \
        - _stats(s, "before")["requests"]
    assert requests > 0
    _stats(s, "before")["staged_lanes"] = 11
    _stats(s, "after")["staged_lanes"] = 11 + int(per_decision * requests)
    assert run.load_reader(NAME).read(s, None) == pytest.approx(
        int(per_decision * requests) / requests)
