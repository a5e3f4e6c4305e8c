"""The one-hit deployment's files (configs/node-1chip-10m-onehit.json,
workloads/onehit10m.batch1000.json, the `onehit.*` readers): found by name
in the manifest, never by position or by another PR's list; the configuration equal to `node-1chip-10m`'s but for
the key model's `hits` (and what says so), the mix equal to
`node10m.batch1000`'s; every pooled request one hit over at most 128
tuples; the new readers on a recorded pair of scrapes with a `kernel`
section and the `lean_*` counters put in (cycle_scrape_fixture.json keeps
neither), and on the pair as a daemon from before the counters answers
(the parent), where none raises and the counters' readers give None; a
traced rehearsal of the cell on the CPU."""

import copy
import json
import os
import subprocess
import sys

import pytest

import onehit_math
import peaks
import run
from conftest import BENCH, HERE, REPO, listed, reads_on_a_cpu
from traffic import Traffic

CONFIG = "node-1chip-10m-onehit"
CELL = "onehit10m.batch1000"
NODE_CELL = "node10m.batch1000"
TRACE = {"window_s": 2.0, "busy_s": 0.9, "launches": 300.0}
NEW = ("onehit.lean_window_share", "onehit.lean_tuples",
       "onehit.lean_refused_per_launch", "onehit.decide_roofline",
       "onehit.device_ms_per_window")
OF_THE_COUNTERS = ("onehit.lean_tuples", "onehit.lean_refused_per_launch")
SIBLINGS = ("windows_per_launch", "stage_ms_per_launch",
            "link_bytes_per_decision", "lock_hold_share",
            "device_idle_share", "compiles_in_window")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mine(manifest):
    """This cell's per-layer entries, by what they say of themselves."""
    return [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]


@pytest.fixture()
def parent():
    """A pair of scrapes as the parent's daemon answers them: the launch
    funnel's phases and link counters, no `lean_*` in `engine.stats`."""
    with open(os.path.join(HERE, "cycle_scrape_fixture.json")) as f:
        s = json.load(f)
    s["device_kind"] = "TPU v5 lite"
    s["latency_ms"] = {"p50": 14.8, "p99": 39.5, "max": 120.0}
    s["boot"] = {"ready_s": 32.5, "restore_s": 16.0}
    s["loadgen"] = {"cpu_s": [0.5] * 8, "processes": 8}
    s["settings"] = {"GUBER_MAX_BATCH_WIDTH": "8192"}
    for side in ("before", "after"):
        assert not [k for k in s[side]["vars"]["engine"]["stats"]
                    if k.startswith("lean_")]
        s[side]["vars"]["engine"]["device"] = {
            "memory": [{"peak_bytes_in_use": 765_120_000}],
            "compiles": {"count": 3, "seconds": 0.0}}
    return s


def _launches(s):
    phases = [s[side]["profile"]["phases"]["launch"]["n"]
              for side in ("after", "before")]
    return phases[0] - phases[1]


@pytest.fixture()
def onehit(parent):
    """The same pair from this change's daemon serving the cell: every
    window of the run's window on the lean scan program, eight tuples, no
    refusal."""
    s = copy.deepcopy(parent)
    n = _launches(s)
    for side, windows in (("before", 100), ("after", 100 + 4 * n)):
        s[side]["vars"]["kernel"] = {"windows": {
            "packed_wide@8192": 1, "scan_lean@8192": windows}}
        s[side]["vars"]["engine"]["stats"].update(
            lean_tuples=8, lean_refused_capacity=0, lean_refused_hits=0,
            lean_refused_gregorian=0, lean_refused_range=0,
            lean_refused_tuples=0)
    return s


def read(name, scrapes, trace=None):
    return run.load_reader(name).read(scrapes, trace)


def requests_of(call):
    """A pooled call's requests, decoded by the program's own protobuf."""
    from gubernator_tpu.service.pb import gubernator_pb2 as pb

    return pb.GetRateLimitsReq.FromString(call.body).requests


# ---- found by name


def test_the_cell_its_configuration_and_its_readers_are_found_by_name(
        manifest, mine):
    cell, conf, mix, _ = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "batch1000", 1)
    assert conf["name"] == mix["config"] == CONFIG
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert (entry["source"], entry["reduced"]) == \
        (conf["source"], conf["reduced"])
    assert len(entry["source"]) <= 200
    # no other configuration has this source or this file
    assert [c["name"] for c in manifest["configs"]
            if c["source"] == entry["source"]
            or c["file"] == entry["file"]] == [CONFIG]
    names = [m["name"] for m in mine]
    assert names[:len(NEW)] == list(NEW)
    assert set(names) == set(NEW) | {"onehit." + n for n in SIBLINGS}
    assert len(names) == len(set(names)) == 11
    # the manifest holds at most 128 per-layer metrics (PR 46 was refused
    # at 139): the cell lists what says which lane it rode, not a copy of
    # every accepted reader
    assert len(manifest["per_layer"]) <= 128
    for m in manifest["per_layer"]:
        if CELL not in m["workloads"]:
            continue
        reader = run.load_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == \
            (m["layer"], m["unit"], m["moves"], m["source"])
    # every layer named is one the benchmark had
    had = {m["layer"] for m in manifest["per_layer"] if m not in mine}
    assert {m["layer"] for m in mine} <= had
    # the cell reports the four end-to-end metrics every cell reports
    assert listed(manifest, "end_to_end", CELL) == {
        "decisions_per_s", "call_p50_ms", "daemon_rss_mb", "setup_s"}
    assert {m["moves"] for m in mine} == {"decisions_per_s", "call_p50_ms"}


def test_the_configuration_is_the_node_file_but_for_one_line_of_the_key_model():
    _, conf, _, _ = run.load_cell(CELL)
    _, node, _, _ = run.load_cell(NODE_CELL)
    says_so = ("name", "source", "why", "assumed", "key_model", "guarantees")
    assert set(conf) == set(node) | {"why"}
    for key in set(node) - set(says_so):
        assert conf[key] == node[key], key
    assert conf["key_model"] == dict(node["key_model"], hits=[1])
    assert node["key_model"]["hits"] == [1, 2, 3]
    assert {k: v for k, v in conf["guarantees"].items()
            if k not in ("wire", "held_by")} == node["guarantees"]
    assert "changes no answer" in conf["guarantees"]["wire"]
    assert "lost_hits" in conf["guarantees"]["held_by"]
    assert "GUBER_STAGING" not in conf["daemon_env"]
    assert set(node["assumed"]) < set(conf["assumed"])
    km = conf["key_model"]
    assert len(km["limits"]) * len(km["algorithms"]) == 8
    # under the lane's ceiling, and the rehearsal's table too
    assert conf["table"]["slots"] <= (1 << 24) - 1


def test_the_mix_is_batch1000s_value_for_value():
    _, _, mix, _ = run.load_cell(CELL)
    _, _, base, _ = run.load_cell(NODE_CELL)
    labels = ("config", "who", "why")
    assert set(mix) == set(base)
    assert {k: v for k, v in mix.items() if k not in labels} == \
        {k: v for k, v in base.items() if k not in labels}
    assert all(mix[k] != base[k] for k in labels)


def test_every_pooled_request_is_one_hit_over_at_most_128_tuples():
    _, conf, mix, _ = run.load_cell(CELL)
    small = dict(mix, pool_calls_per_client=4, requests_per_call=500)
    traffic = Traffic(small, conf["key_model"], 32768, 2**31 + 46)
    tuples, requests = set(), 0
    for client in range(2):
        for call in traffic.build_pool(client):
            for r in requests_of(call):
                assert r.hits == 1
                tuples.add((r.limit, r.duration, r.algorithm, r.behavior))
                requests += 1
    assert requests == 2 * 4 * 500
    assert len(tuples) == 8 <= 128
    # the accepted key model sends what the lane refuses
    _, node, _, _ = run.load_cell(NODE_CELL)
    pool = Traffic(small, node["key_model"], 32768,
                   2**31 + 46).build_pool(0)
    assert {r.hits for r in requests_of(pool[0])} == {1, 2, 3}


# ---- the new readers


def test_the_new_readers_on_a_daemon_that_serves_the_cell(onehit):
    assert read("onehit.lean_window_share", onehit) == 1.0
    assert read("onehit.lean_tuples", onehit) == 8
    assert read("onehit.lean_refused_per_launch", onehit) == 0.0
    stats = [onehit[side]["vars"]["engine"]["stats"]
             for side in ("after", "before")]
    requests = stats[0]["requests"] - stats[1]["requests"]
    batches = stats[0]["batches"] - stats[1]["batches"]
    n = _launches(onehit)
    lanes = TRACE["launches"] * requests / n
    assert onehit_math.lanes_in_capture(onehit, TRACE) == pytest.approx(lanes)
    roof = read("onehit.decide_roofline", onehit, TRACE)
    assert roof == pytest.approx(
        100 * lanes * (2 * 64 + 4 + 24) / 819e9 / TRACE["busy_s"])
    assert 0 < roof < 100
    assert read("onehit.device_ms_per_window", onehit, TRACE) == \
        pytest.approx(TRACE["busy_s"] * 1e3
                      / (TRACE["launches"] * batches / n))
    # a launch is `windows_per_launch` windows: the two device readers
    # and the accepted ones differ by that factor and nothing else
    per_launch = read("onehit.windows_per_launch", onehit)
    assert per_launch == pytest.approx(batches / n)
    assert read("device_ms_per_window", onehit, TRACE) == pytest.approx(
        read("onehit.device_ms_per_window", onehit, TRACE) * per_launch)
    # no capture, no device metric
    for name in ("onehit.decide_roofline", "onehit.device_ms_per_window"):
        assert read(name, onehit, None) is None
        assert read(name, onehit, dict(TRACE, launches=0.0)) is None


def test_a_deployment_off_the_lane_reads_as_such(onehit):
    """`node10m.batch1000`'s daemon with the counters: every launch
    refused for `hits`, every window on the compact scan program."""
    s = copy.deepcopy(onehit)
    n = _launches(s)
    for side, refused in (("before", 7), ("after", 7 + n)):
        s[side]["vars"]["kernel"]["windows"] = {
            "scan_compact@8192": 4 * refused, "scan_lean@8192": 100}
        s[side]["vars"]["engine"]["stats"].update(
            lean_refused_hits=refused, lean_tuples=0)
    assert read("onehit.lean_window_share", s) == 0.0
    assert read("onehit.lean_refused_per_launch", s) == 1.0
    assert read("onehit.lean_tuples", s) == 0
    # a hot key's solo rounds on the lane beside compact groups
    s["after"]["vars"]["kernel"]["windows"]["carry_lean@2048"] = n
    assert read("onehit.lean_window_share", s) == pytest.approx(1 / 5)
    # nothing launched: nothing to divide by
    s["after"] = copy.deepcopy(s["before"])
    assert read("onehit.lean_window_share", s) is None
    assert read("onehit.lean_refused_per_launch", s) is None


def test_the_readers_of_the_accepted_layers_are_their_siblings(onehit):
    for name in SIBLINGS:
        trace = TRACE if name == "device_idle_share" else None
        assert read("onehit." + name, onehit, trace) == \
            read(name, onehit, trace), name
    for name in ("windows_per_launch", "stage_ms_per_launch",
                 "link_bytes_per_decision", "lock_hold_share",
                 "compiles_in_window"):
        assert read("onehit." + name, onehit) is not None, name
    assert read("onehit.device_idle_share", onehit, TRACE) == \
        pytest.approx(0.55)


def test_on_the_parent_no_reader_raises_and_the_counters_readers_give_none(
        parent, mine):
    assert "kernel" not in parent["after"]["vars"]  # the fixture keeps none
    for m in mine:
        value = read(m["name"], parent, TRACE)  # must not raise
        if m["name"] in OF_THE_COUNTERS + ("onehit.lean_window_share",):
            assert value is None, m["name"]
    # the parent's daemon does show `kernel.windows` (it has since r5), so
    # the share reads there; the counters are this change's
    shown = copy.deepcopy(parent)
    for side, n in (("before", 10), ("after", 30)):
        shown[side]["vars"]["kernel"] = {"windows": {"scan_compact@8192": n}}
    assert read("onehit.lean_window_share", shown) == 0.0
    for name in OF_THE_COUNTERS:
        assert read(name, shown, TRACE) is None
    assert read("onehit.decide_roofline", shown, TRACE) > 0
    # a mesh daemon: `lean_windows` of its own, none of these
    shown["after"]["vars"]["engine"]["stats"]["lean_windows"] = 5
    shown["before"]["vars"]["engine"]["stats"]["lean_windows"] = 0
    for name in OF_THE_COUNTERS:
        assert read(name, shown, TRACE) is None


def test_the_roofline_needs_the_chips_peaks(onehit):
    onehit["device_kind"] = "cpu"
    with pytest.raises(KeyError):
        read("onehit.decide_roofline", onehit, TRACE)
    assert peaks.STAGING_IN_BYTES == 4  # the lean lane is reckoned in


# ---- a traced rehearsal


def test_traced_rehearsal_rides_the_lean_lane_and_prints_every_metric(
        manifest):
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 46), "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
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
    assert set(result["end_to_end"]) == listed(manifest, "end_to_end", CELL)
    # every reader the manifest lists for the cell but the roofline, whose
    # peaks know no CPU, and the allocator's peak, which a CPU does not report
    assert set(result["metrics"]) == set(filter(
        reads_on_a_cpu, listed(manifest, "per_layer", CELL)))
    assert out["reader_skipped"]["name"] == "onehit.decide_roofline"
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["onehit.lean_window_share"] == 1.0
    assert m["onehit.lean_refused_per_launch"] == 0.0
    assert 1 <= m["onehit.lean_tuples"] <= 8
    assert m["onehit.windows_per_launch"] >= 1.0
    # 64 lanes of 4 B and the 4 KiB table up, 64 x 16 B back, a window of
    # at most 64 requests: far under compact's 20 B a lane up
    assert m["onehit.link_bytes_per_decision"] < 4 + 16 + 4096 / 32 + 8
    assert m["onehit.device_ms_per_window"] > 0
    assert m["onehit.compiles_in_window"] == 0
    # nothing of the run is left behind
    assert subprocess.run(["pgrep", "-f", "[g]ubernator_tpu.cmd.daemon"],
                          capture_output=True).stdout == b""
