"""The readers of what the daemon's profiler reports from PR 25 on, on a
recorded pair of scrapes (scrape_fixture.json: an in-process node on the
CPU, so counters and host clocks only) and on a daemon that reports none
of it, as the parent of that change does."""

import copy
import json
import os

import pytest

import run
from conftest import HERE

NEW = ("front_wait_ms", "front_call_ms", "front_io_us", "frames_per_pull",
       "housekeeping_ms_per_s", "hbm_peak_mb", "compiles_in_window",
       "idle_share.no_work", "idle_share.housekeeping", "idle_share.host")


@pytest.fixture()
def scrapes():
    with open(os.path.join(HERE, "scrape_fixture.json")) as f:
        return json.load(f)


def read(name, scrapes, trace=None):
    return run.load_reader(name).read(scrapes, trace)


def test_front_phases_are_means_per_frame(scrapes):
    a, b = scrapes["after"]["profile"], scrapes["before"]["profile"]
    frames = a["front"]["frames_pulled"] - b["front"]["frames_pulled"]
    assert frames == 160  # 4 callers x 40 calls between the scrapes

    def total(phase):
        return a["phases"][phase]["total_ns"] - b["phases"][phase]["total_ns"]

    assert read("front_wait_ms", scrapes) == pytest.approx(
        total("front_wait") / frames / 1e6)
    assert read("front_call_ms", scrapes) == pytest.approx(
        total("front_call") / frames / 1e6)
    assert read("front_io_us", scrapes) == pytest.approx(
        (total("front_parse") + total("front_write")) / frames / 1e3)
    # a call's stay contains its wait in the queue, and both contain the
    # front's own work
    assert 0 < read("front_wait_ms", scrapes) < read("front_call_ms", scrapes)
    assert read("front_io_us", scrapes) / 1e3 < read("front_call_ms", scrapes)


def test_frames_per_pull(scrapes):
    a, b = (scrapes[k]["profile"]["front"] for k in ("after", "before"))
    want = (a["frames_pulled"] - b["frames_pulled"]) / (a["pulls"] - b["pulls"])
    assert read("frames_per_pull", scrapes) == pytest.approx(want)
    assert 1.0 <= want <= 4.0  # four callers: a pull takes at most four
    scrapes["after"]["profile"]["front"] = b  # no pull inside the window
    assert read("frames_per_pull", scrapes) is None


def test_housekeeping_sums_the_sites_over_the_window(scrapes):
    a, b = (scrapes[k]["profile"]["bg_sites"] for k in ("after", "before"))
    # a site first seen inside the window counts from zero
    assert "history.sample" in a and "history.sample" not in b
    ns = sum(s["total_ns"] - b.get(site, {"total_ns": 0})["total_ns"]
             for site, s in a.items())
    assert read("housekeeping_ms_per_s", scrapes) == pytest.approx(
        ns / 1e6 / scrapes["window_s"])
    assert ns > 0


def test_device_facts(scrapes):
    # the fixture's node ran on the CPU, whose allocator reports nothing
    assert read("hbm_peak_mb", scrapes) is None
    # 1,360,900,608 bytes: what the daemon reported on the TPU v5 lite in
    # both cells (my chip run, PR 25); a second, emptier device does not
    # lower it
    scrapes["after"]["vars"]["engine"]["device"]["memory"] = [
        {"device": "TPU_0", "bytes_in_use": 641_000_000,
         "peak_bytes_in_use": 1_360_900_608, "bytes_limit": 16_000_000_000},
        {"device": "TPU_1", "bytes_in_use": 1, "peak_bytes_in_use": 2,
         "bytes_limit": 16_000_000_000}]
    assert read("hbm_peak_mb", scrapes) == pytest.approx(1360.900608)
    a, b = (scrapes[k]["vars"]["engine"]["device"]["compiles"]["count"]
            for k in ("after", "before"))
    assert read("compiles_in_window", scrapes) == a - b


@pytest.mark.parametrize("name", NEW)
def test_a_daemon_from_before_the_change_gives_nothing(scrapes, name):
    """The parent's /v1/debug/profile has the six cycle phases, lock_sites
    and a capture block; its engine.device has no memory and no compiles.
    Every new reader then returns None and raises nothing."""
    old = copy.deepcopy(scrapes)
    for side in ("before", "after"):
        prof = old[side]["profile"]
        prof["phases"] = {p: s for p, s in prof["phases"].items()
                          if not p.startswith("front_")}
        del prof["front"], prof["bg_sites"]
        prof["capture"] = {"count": 1, "min_interval_s": 60.0,
                           "last_path": None, "last_mode": "wall_sampler"}
        dev = old[side]["vars"]["engine"]["device"]
        del dev["memory"], dev["compiles"]
    trace = {"window_s": 2.0, "busy_s": 1.0, "launches": 100.0}
    assert read(name, old, trace) is None
