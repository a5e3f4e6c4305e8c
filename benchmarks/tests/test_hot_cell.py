"""The hot-tenant deployment's files (configs/node-1chip-10m-hot.json,
workloads/hot10m.repeats1000.json, the `hot.*` readers and
`call_p99_ms.hot`): found by name, agreeing with the manifest; the readers
on a recorded pair of scrapes of this cell's traffic
(hot_scrape_fixture.json: an in-process node on the CPU, so counters and
host clocks only) and on a parent-shaped pair, a daemon from before it
counted leftovers and scan groups; a traced rehearsal of the cell."""

import copy
import json
import os
import subprocess
import sys

import pytest

import run
from conftest import BENCH, HERE, REPO, listed, reads_on_a_cpu

CONFIG = "node-1chip-10m-hot"
CELL = "hot10m.repeats1000"
TRACE = {"window_s": 2.0, "busy_s": 0.9, "launches": 300.0}
READERS = {  # name: does it read on the parent's scrapes
    "hot.leftover_share": False, "hot.leftover_ms_per_call": False,
    "hot.rounds_per_call": True, "hot.scan_depth": False,
    "hot.scan_fill": False, "hot.compiles_in_window": True,
    "hot.device_ms_per_call": False, "hot.decide_roofline": False,
    "hot.device_idle_share": True, "hot.idle_share.host": False,
    "hot.ready_s": True, "call_p99_ms.hot": True,
    # the accepted layers this cell runs too, under its own names (their
    # lists of cells are not this PR's to edit)
    "hot.lock_wait_ms_per_call": True, "hot.prep_ms_per_call": True,
    "hot.readback_ms_per_call": True, "hot.queue_wait_ms": True,
    "hot.front_wait_ms": True, "hot.front_call_ms": True,
    "hot.frames_per_pull": True, "hot.hbm_peak_mb": True,
    "hot.restore_s": True, "hot.housekeeping_ms_per_s": True,
    "hot.loadgen_cpu_share": True,
    # no capture on record in the fixture: on the chip the parent reads
    "hot.idle_share.no_work": False, "hot.idle_share.housekeeping": False}
NEEDS_TRACE = ("hot.device_ms_per_call", "hot.decide_roofline",
               "hot.device_idle_share", "hot.idle_share.host",
               "hot.idle_share.no_work", "hot.idle_share.housekeeping")
# what the change's profiler leaves behind after a capture: the front's
# counters between its two edges (obs/profile.py _jax_trace)
CAPTURE = {"count": 1, "last_path": None, "last_mode": "jax_trace",
           "last_rates": {"launches_per_s_out": 150.0,
                          "launches_per_s_in": 150.0,
                          "frames_pulled_in": 44, "items_pulled_in": 44000}}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture()
def scrapes():
    with open(os.path.join(HERE, "hot_scrape_fixture.json")) as f:
        s = json.load(f)
    s["device_kind"] = "TPU v5 lite"
    s["latency_ms"] = {"p50": 400.0, "p99": 900.5}
    s["boot"] = {"ready_s": 41.5, "restore_s": 16.0}
    s["loadgen"] = {"cpu_s": [0.5] * 8, "processes": 8}
    s["after"]["vars"]["engine"]["device"]["memory"] = [
        {"peak_bytes_in_use": 772_700_000}]  # the CPU reports none
    return s


@pytest.fixture()
def captured(scrapes):
    """The same scrapes after a capture by this change's profiler."""
    s = copy.deepcopy(scrapes)
    s["after"]["profile"]["capture"] = dict(CAPTURE)
    return s


@pytest.fixture()
def parent(scrapes):
    """What the parent's daemon answers: no `peerlink_leftover_*` family,
    no `scan_*` in `engine.stats`, no `leftover` phase, and after a
    capture the launch rates alone."""
    old = copy.deepcopy(scrapes)
    old["after"]["profile"]["capture"] = dict(CAPTURE, last_rates={
        k: v for k, v in CAPTURE["last_rates"].items()
        if k.startswith("launches_")})
    for side in ("before", "after"):
        for family in list(old[side]["metrics"]):
            if "leftover" in family:
                del old[side]["metrics"][family]
        stats = old[side]["vars"]["engine"]["stats"]
        for key in list(stats):
            if key.startswith("scan_"):
                del stats[key]
        del old[side]["profile"]["phases"]["leftover"]
        old[side]["profile"]["schema_version"] = 2
    return old


def read(name, scrapes, trace=None):
    return run.load_reader(name).read(scrapes, trace)


def diff(scrapes, *path):
    a, b = scrapes["after"], scrapes["before"]
    for key in path:
        a, b = a[key], b[key]
    return a - b


def test_the_cell_its_configuration_and_its_readers_are_found_by_name(
        manifest):
    cell, conf, mix, _ = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeats1000", 1)
    assert conf["name"] == mix["config"] == CONFIG
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert (entry["source"], entry["reduced"]) == \
        (conf["source"], conf["reduced"])
    # the readers PR 38 brought are the cell's own still, in its order;
    # later PRs' readers (its own or shared with other cells) come after
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine[:len(READERS)] == list(READERS)
    assert set(READERS) <= listed(manifest, "per_layer", CELL)
    for m in manifest["per_layer"]:
        if CELL not in m["workloads"]:
            continue
        reader = run.load_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == \
            (m["layer"], m["unit"], m["moves"], m["source"])


def test_the_daemon_settings_are_the_node_files_but_for_the_ladder():
    _, conf, _, _ = run.load_cell(CELL)
    _, node, _, _ = run.load_cell("node10m.batch1000")

    def settings(c):
        out = dict(c["daemon_env"])
        for key in c["reduced"]:
            if isinstance(c[key], dict):
                out.update({k: v for k, v in c[key].items()
                            if k.startswith("GUBER_")})
        return out

    hot, base = settings(conf), settings(node)
    assert {k for k in hot if hot[k] != base.get(k)} == {
        "GUBER_MIN_BATCH_WIDTH"}
    assert int(hot["GUBER_MIN_BATCH_WIDTH"]) in (512, 1024, 2048)
    assert conf["resident_keys"] == node["resident_keys"]
    assert conf["rehearse"] == node["rehearse"]


def test_the_mix_reads_the_skew_the_configuration_states():
    _, conf, mix, _ = run.load_cell(CELL)
    _, _, base, _ = run.load_cell("node10m.batch1000")
    population = conf["population"]
    # YCSB's constant, the node file's: the skew is the source's own
    assert mix["key_model"]["zipf_exponent"] == \
        population["zipf_exponent"] == \
        base["key_model"]["zipf_exponent"] == 0.99
    assert mix["key_model"]["distinct_in_call"] is False
    assert population["repeats_in_call"] is True
    labels = ("config", "traffic", "who", "why", "key_model")
    assert {k: v for k, v in mix.items() if k not in labels} == \
        {k: v for k, v in base.items() if k not in labels}
    assert {k: v for k, v in mix["key_model"].items()
            if k not in ("zipf_exponent", "distinct_in_call")} == \
        {k: v for k, v in base["key_model"].items()
         if k not in ("zipf_exponent", "distinct_in_call")}


def test_the_counters_of_the_fixture_add_up(scrapes):
    items = diff(scrapes, "profile", "front", "items_pulled")
    calls = diff(scrapes, "profile", "front", "frames_pulled")
    assert (items, calls) == (3000, 10)
    assert diff(scrapes, "vars", "engine", "stats", "requests") == items
    left = diff(scrapes, "metrics", "peerlink_leftover_items_total")
    # 3000 residents and 300-request calls repeat more than 8M and 1000
    assert 0.3 * items < left < 0.5 * items
    assert diff(scrapes, "profile", "phases", "leftover", "n") == calls


def test_the_counter_readers(scrapes):
    stats = ("vars", "engine", "stats")
    calls = diff(scrapes, "profile", "front", "frames_pulled")
    assert read("hot.leftover_share", scrapes) == pytest.approx(
        diff(scrapes, "metrics", "peerlink_leftover_items_total") / 3000)
    assert read("hot.leftover_ms_per_call", scrapes) == pytest.approx(
        diff(scrapes, "profile", "phases", "leftover", "total_ns")
        / calls / 1e6)
    assert read("hot.rounds_per_call", scrapes) == pytest.approx(
        diff(scrapes, *stats, "rounds") / calls)
    depth = read("hot.scan_depth", scrapes)
    assert depth == pytest.approx(diff(scrapes, *stats, "scan_rounds")
                                  / diff(scrapes, *stats, "scan_dispatches"))
    assert 8 < depth <= 32
    fill = read("hot.scan_fill", scrapes)
    assert fill == pytest.approx(diff(scrapes, *stats, "scan_lanes_live")
                                 / diff(scrapes, *stats, "scan_lanes"))
    assert 0 < fill < 0.1  # a round holds a few keys of 64 lanes
    # a background ticker's eager slice, no decide program: the ladder
    # was warmed
    assert read("hot.compiles_in_window", scrapes) == diff(
        scrapes, "vars", "engine", "device", "compiles", "count") <= 1
    assert read("hot.ready_s", scrapes) == 41.5
    assert read("call_p99_ms.hot", scrapes) == 900.5


def test_the_readers_of_the_accepted_layers(scrapes):
    """Each is a sibling's arithmetic (re-exported), or a sibling's phase
    over the calls where the sibling divides by engine windows."""
    phases = ("profile", "phases")
    calls = diff(scrapes, "profile", "front", "frames_pulled")
    windows = diff(scrapes, "vars", "engine", "stats", "batches")
    for phase in ("lock_wait", "prep", "readback"):
        assert read(f"hot.{phase}_ms_per_call", scrapes) == pytest.approx(
            diff(scrapes, *phases, phase, "total_ns") / calls / 1e6)
        assert read(f"hot.{phase}_ms_per_call", scrapes) > 0
    for name in ("queue_wait_ms", "front_wait_ms", "front_call_ms",
                 "frames_per_pull", "hbm_peak_mb", "restore_s",
                 "housekeeping_ms_per_s", "loadgen_cpu_share"):
        assert read("hot." + name, scrapes) == read(name, scrapes), name
    assert read("hot.queue_wait_ms", scrapes) == pytest.approx(
        diff(scrapes, *phases, "queue_wait", "total_ns") / windows / 1e6)
    assert read("hot.front_call_ms", scrapes) > \
        read("hot.front_wait_ms", scrapes) > 0
    assert read("hot.frames_per_pull", scrapes) == 1.0  # one caller
    assert read("hot.hbm_peak_mb", scrapes) == 772.7
    assert read("hot.restore_s", scrapes) == 16.0
    assert read("hot.loadgen_cpu_share", scrapes) == pytest.approx(
        4.0 / (scrapes["window_s"] * 8))


def test_the_trace_readers(captured):
    """Device time over the work counted between the capture's own edges,
    not over the window's counters scaled to its length."""
    rates = CAPTURE["last_rates"]
    assert read("hot.device_ms_per_call", captured, TRACE) == pytest.approx(
        0.9 / rates["frames_pulled_in"] * 1e3)
    assert read("hot.device_idle_share", captured, TRACE) == \
        pytest.approx(0.55)
    # every request the pull loop took inside the capture, at 156 B
    want = 100 * rates["items_pulled_in"] * 156 / 819e9 / 0.9
    got = read("hot.decide_roofline", captured, TRACE)
    assert got == pytest.approx(want) and 0 < got < 100
    # the window's own counters move neither
    captured["after"]["profile"]["front"]["frames_pulled"] += 1000
    assert read("hot.device_ms_per_call", captured, TRACE) == pytest.approx(
        0.9 / rates["frames_pulled_in"] * 1e3)
    # no capture's path on record in the fixture: nothing to split
    for which in ("host", "no_work", "housekeeping"):
        assert read("hot.idle_share." + which, captured, TRACE) is None


@pytest.mark.parametrize("name", ["hot.device_ms_per_call",
                                  "hot.decide_roofline"])
def test_without_the_captures_counts_the_device_readers_give_none(
        scrapes, captured, name):
    assert read(name, scrapes, TRACE) is None  # no capture was made
    captured["after"]["profile"]["capture"]["last_mode"] = "wall_sampler"
    assert read(name, captured, TRACE) is None
    captured["after"]["profile"]["capture"] = dict(
        CAPTURE, last_rates=dict(CAPTURE["last_rates"], frames_pulled_in=0,
                                 items_pulled_in=0))
    assert read(name, captured, TRACE) is None  # an idle capture


@pytest.mark.parametrize("name,reads", sorted(READERS.items()))
def test_on_the_parent_a_reader_reads_or_gives_none_and_never_raises(
        parent, name, reads):
    value = read(name, parent, TRACE)
    assert (value is not None) is reads, value


@pytest.mark.parametrize("name", NEEDS_TRACE)
def test_without_a_trace_the_trace_readers_give_none(captured, name):
    assert read(name, captured, None) is None
    assert read(name, captured, {"window_s": 0.0, "busy_s": 0.0,
                                 "launches": 0.0}) is None


def test_traced_rehearsal_of_the_hot_cell_is_well_formed(manifest):
    """The daemon on the CPU at a tiny table and a one-width ladder of 64
    (a 1000-item chunk is 16 spans there, launched in scan groups that a
    leftover cuts), the same load generators, scrapes, checker and result
    line as on the chip."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 238), "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {ln.get("step", "result"): ln
           for ln in map(json.loads, r.stdout.splitlines())}
    check, result = out["check"], out["result"]
    assert check["sound"] is True, check
    assert check["compared"]["audit_mismatches"]["value"] == 0
    assert check["compared"]["failed_decisions"]["value"] == 0
    assert check["compared"]["audited_answers"]["value"] >= 1000
    assert result["correct"] is False and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    device = result["device"]
    assert device["platform"] == "cpu" and device["count"] == 1
    assert 0 < device["busy_s"] <= device["window_s"]
    assert set(result["end_to_end"]) == listed(manifest, "end_to_end", CELL)
    # every reader the manifest lists for the cell but the roofline, whose
    # peaks know no CPU, and the allocator's peak, which a CPU does not report
    assert set(result["metrics"]) == set(filter(
        reads_on_a_cpu, listed(manifest, "per_layer", CELL)))
    assert out["reader_skipped"]["name"] == "hot.decide_roofline"
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # 32,768 residents repeat more than 8M (0.24 there), 64-item spans
    # see fewer of a call's repeats than a 1000-item chunk
    assert 0.1 < m["hot.leftover_share"] < 0.6
    assert m["hot.rounds_per_call"] > 16
    assert 2 < m["hot.scan_depth"] <= 32
    assert 0 < m["hot.scan_fill"] < 0.2
    assert m["hot.leftover_ms_per_call"] > 0
    assert 0 <= m["hot.device_idle_share"] <= 1
    assert m["hot.device_ms_per_call"] > 0
    assert m["hot.idle_share.no_work"] + m["hot.idle_share.housekeeping"] \
        + m["hot.idle_share.host"] == pytest.approx(
            m["hot.device_idle_share"])
    assert m["hot.lock_wait_ms_per_call"] > 0
    assert m["hot.front_call_ms"] > m["hot.front_wait_ms"] >= 0
    # nothing of the run is left behind
    assert subprocess.run(["pgrep", "-f", "[g]ubernator_tpu.cmd.daemon"],
                          capture_output=True).stdout == b""
