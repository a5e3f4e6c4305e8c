"""The churn deployment's files (configs/node-1chip-10m-churn.json,
workloads/churn10m.newkeys1000.json, the `churn.*` readers and
`call_p99_ms.churn`): found by name in the manifest, never by position or
by another PR's list; the mix equal to the configuration's `population`;
the pool too large to wrap; the readers of the directory's counters on a
recorded pair of scrapes with an `engine.directory` section put in, and on
the pair as recorded (a daemon from before it showed one: the parent),
where each gives None and none raises; a traced rehearsal of the cell on
the CPU in a scratch checkout whose pool and warm-up are cut (the cell's
own 45M pooled keys are 8 x ~2 GB of load generators: the chip's host
builds them, this sandbox should not)."""

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run
from conftest import BENCH, HERE, REPO, listed, reads_on_a_cpu
from keymodel import NEW_BASE
from traffic import Traffic

CONFIG = "node-1chip-10m-churn"
CELL = "churn10m.newkeys1000"
NODE_CELL = "node10m.batch1000"
TRACE = {"window_s": 2.0, "busy_s": 0.9, "launches": 300.0}
# the readers of what this deployment adds to the program: on a daemon
# without `engine.directory` they give None
OF_THE_DIRECTORY = ("churn.fresh_lane_share", "churn.evictions_per_decision",
                    "churn.rebuilds_in_window", "churn.rebuild_ms_per_s",
                    "churn.rebuild_max_ms")
RATE_CEILING = 585_000  # decisions/s the pool must outlast (ISSUE 43)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mine(manifest):
    """This cell's per-layer entries, by what they say of themselves."""
    return [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]


@pytest.fixture()
def scrapes():
    with open(os.path.join(HERE, "hot_scrape_fixture.json")) as f:
        s = json.load(f)
    s["device_kind"] = "TPU v5 lite"
    s["latency_ms"] = {"p50": 16.0, "p99": 35.5, "max": 682.5}
    s["boot"] = {"ready_s": 36.7, "restore_s": 19.2}
    s["loadgen"] = {"cpu_s": [0.5] * 8, "processes": 8}
    s["settings"] = {"GUBER_MAX_BATCH_WIDTH": "8192"}
    return s


@pytest.fixture()
def churning(scrapes):
    """The same pair from a daemon that shows its directory's counters: a
    full table, every request of the window a new key, two rebuilds."""
    s = copy.deepcopy(scrapes)
    decided = s["after"]["vars"]["engine"]["stats"]["requests"] \
        - s["before"]["vars"]["engine"]["stats"]["requests"]
    s["before"]["vars"]["engine"]["directory"] = {
        "evictions": 11_000, "inserts": 21_000, "rebuilds": 1,
        "rebuild_ns": 600_000_000, "rebuild_max_ns": 600_000_000}
    s["after"]["vars"]["engine"]["directory"] = {
        "evictions": 11_000 + decided, "inserts": 21_000 + decided,
        "rebuilds": 3, "rebuild_ns": 1_900_000_000,
        "rebuild_max_ns": 654_300_000}
    for side, n in (("before", 0), ("after", 0)):
        s[side]["vars"]["ledger"] = {"violations": n}
    return s


def read(name, scrapes, trace=None):
    return run.load_reader(name).read(scrapes, trace)


def settings(conf):
    out = dict(conf["daemon_env"])
    for key in conf["reduced"]:
        if isinstance(conf[key], dict):
            out.update({k: v for k, v in conf[key].items()
                        if k.startswith("GUBER_")})
    return out


# ---- found by name


def test_the_cell_its_configuration_and_its_readers_are_found_by_name(
        manifest, mine):
    cell, conf, mix, _ = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "newkeys1000", 1)
    assert conf["name"] == mix["config"] == CONFIG
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert (entry["source"], entry["reduced"]) == \
        (conf["source"], conf["reduced"])
    # no other configuration has this source or this file
    assert [c["name"] for c in manifest["configs"]
            if c["source"] == entry["source"]
            or c["file"] == entry["file"]] == [CONFIG]
    names = {m["name"] for m in mine}
    assert len(mine) == len(names) == 38
    assert all(n.startswith("churn.") or n == "call_p99_ms.churn"
               for n in names)
    # its own readers and those of later PRs that list it beside others
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
    assert {m["moves"] for m in mine} == {
        "decisions_per_s", "call_p50_ms", "daemon_rss_mb", "setup_s"}


def test_the_configuration_is_the_node_file_with_a_full_table():
    _, conf, _, _ = run.load_cell(CELL)
    _, node, _, _ = run.load_cell(NODE_CELL)
    assert settings(conf) == settings(node)
    for key in ("daemon_env", "compile_ladder", "pipeline_depth", "table",
                "key_model", "chips"):
        assert conf[key] == node[key], key
    assert conf["resident_keys"] == conf["table"]["slots"] == \
        int(conf["daemon_env"]["GUBER_CACHE_SIZE"]) == 10_000_000
    assert conf["reduced"] == ["compile_ladder", "pipeline_depth"]
    for k, v in node["guarantees"].items():
        assert conf["guarantees"][k] == v
    assert {"new_key", "eviction", "capacity"} <= set(conf["guarantees"])
    assert conf["guarantees"]["durability"].startswith("none")
    # the rehearsal's table is full from boot too, so it evicts
    rehearse = conf["rehearse"]
    assert rehearse["resident_keys"] == \
        int(rehearse["daemon_env"]["GUBER_CACHE_SIZE"])
    assert rehearse["daemon_env"]["GUBER_MIN_BATCH_WIDTH"] == \
        rehearse["daemon_env"]["GUBER_MAX_BATCH_WIDTH"] == "64"


def test_the_mix_is_the_population_the_configuration_states():
    _, conf, mix, _ = run.load_cell(CELL)
    _, _, base, _ = run.load_cell(NODE_CELL)
    population = conf["population"]
    assert mix["key_model"]["new_key_share"] == \
        population["new_key_share"] == 1.0
    assert population["residents_requested"] is False
    assert mix["key_model"] == {"zipf_exponent": 0.99,
                                "distinct_in_call": True, "hot_set": None,
                                "new_key_share": 1.0}
    assert (mix["warm_seconds"], mix["pool_calls_per_client"]) == (25, 5632)
    labels = ("config", "traffic", "who", "why", "key_model", "warm_seconds",
              "pool_calls_per_client")
    assert {k: v for k, v in mix.items() if k not in labels} == \
        {k: v for k, v in base.items() if k not in labels}


def test_the_pool_cannot_wrap_and_its_ids_stay_in_their_stride(manifest):
    _, _, mix, _ = run.load_cell(CELL)
    pooled = mix["clients"] * mix["pool_calls_per_client"] \
        * mix["requests_per_call"]
    sent_s = 0.3 + mix["warm_seconds"] + manifest["run_seconds"] + 1.7
    assert pooled >= RATE_CEILING * sent_s
    # a client's never-seen ids start at NEW_BASE x (client + 1)
    assert mix["pool_calls_per_client"] * mix["requests_per_call"] < NEW_BASE
    assert NEW_BASE > 10_000_000  # and lie above every resident's id


def test_every_pooled_key_is_new_and_sent_once():
    _, conf, mix, _ = run.load_cell(CELL)
    small = dict(mix, pool_calls_per_client=3, requests_per_call=200)
    traffic = Traffic(small, conf["key_model"], 4096, 2**31 + 43)
    ids = []
    for client in range(2):
        pool = traffic.build_pool(client)
        assert len(pool) == 3
        for call in pool:
            assert len(call.limits) == 200
            ids.append(call.audit_ids)
    ids = np.concatenate(ids)
    assert len(ids) and ids.min() >= NEW_BASE  # no resident is asked for
    assert len(np.unique(ids)) == len(ids)


# ---- the readers of the directory's counters


def test_the_directory_readers_on_a_churning_daemon(churning):
    assert read("churn.fresh_lane_share", churning) == 1.0
    assert read("churn.evictions_per_decision", churning) == 1.0
    assert read("churn.rebuilds_in_window", churning) == 2
    assert read("churn.rebuild_ms_per_s", churning) == pytest.approx(
        1300.0 / churning["window_s"])
    assert read("churn.rebuild_max_ms", churning) == pytest.approx(654.3)
    assert read("churn.ledger_violations", churning) == 0
    assert read("churn.call_max_ms", churning) == 682.5
    assert read("call_p99_ms.churn", churning) == 35.5
    assert read("churn.harvests_before_peak", churning) == \
        churning["after"]["profile"]["bg_sites"]["keyspace.harvest"]["n"]
    # a table with room left: inserts without evictions, no rebuild yet
    roomy = copy.deepcopy(churning)
    for side in ("before", "after"):
        roomy[side]["vars"]["engine"]["directory"].update(
            evictions=0, rebuilds=0, rebuild_ns=0, rebuild_max_ns=0)
    assert read("churn.evictions_per_decision", roomy) == 0.0
    assert read("churn.fresh_lane_share", roomy) == 1.0
    assert read("churn.rebuilds_in_window", roomy) == 0
    assert read("churn.rebuild_max_ms", roomy) is None


def test_the_readers_of_the_accepted_layers_are_their_siblings(churning):
    for name in ("prep_ms_per_window", "readback_ms_per_window",
                 "window_fill", "queue_wait_ms", "front_wait_ms",
                 "front_call_ms", "frames_per_pull", "housekeeping_ms_per_s",
                 "ready_s", "restore_s", "compiles_in_window",
                 "loadgen_cpu_share", "hbm_peak_mb", "lock_hold_share",
                 "stage_ms_per_launch", "launch_ms_per_launch",
                 "link_bytes_per_decision", "staged_lanes_per_decision"):
        assert read("churn." + name, churning) == read(name, churning), name
    for name in ("device_ms_per_window", "decide_roofline",
                 "device_idle_share"):
        assert read("churn." + name, churning, TRACE) == \
            read(name, churning, TRACE) is not None, name
    windows = churning["after"]["vars"]["engine"]["stats"]["batches"] \
        - churning["before"]["vars"]["engine"]["stats"]["batches"]
    waited = churning["after"]["profile"]["phases"]["lock_wait"]["total_ns"] \
        - churning["before"]["profile"]["phases"]["lock_wait"]["total_ns"]
    assert read("churn.lock_wait_ms_per_window", churning) == pytest.approx(
        waited / windows / 1e6)


def test_on_the_parent_no_reader_raises_and_the_new_ones_give_none(
        scrapes, mine):
    assert "directory" not in scrapes["after"]["vars"]["engine"]
    for m in mine:
        value = read(m["name"], scrapes, TRACE)  # must not raise
        if m["name"] in OF_THE_DIRECTORY:
            assert value is None, m["name"]
    assert read("churn.ledger_violations", scrapes) is None  # no section
    assert read("churn.harvests_before_peak", scrapes) is not None


# ---- a traced rehearsal


@pytest.fixture()
def scratch_checkout(tmp_path):
    """The benchmark and the program in a scratch checkout, with the cell's
    pool and warm-up cut to a rehearsal's size (2 s of warm traffic, 512
    pooled calls a client: 4M keys. A CPU daemon alone on this sandbox
    decides ~300k a second since PR 44, so the 2M keys this was first cut
    to wrapped within its 8 s, and a key asked again after its eviction is
    a mismatch the oracle is right to count). Nothing else of the cell
    differs."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "gubernator_tpu"), root / "gubernator_tpu")
    path = root / "benchmarks/workloads" / (CELL + ".json")
    mix = json.loads(path.read_text())
    mix.update(warm_seconds=2, pool_calls_per_client=512)
    path.write_text(json.dumps(mix))
    return root


def test_traced_rehearsal_of_the_churn_cell_evicts_and_prints_every_metric(
        scratch_checkout, manifest):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")))
    r = subprocess.run(
        [sys.executable, str(scratch_checkout / "benchmarks/run.py"),
         "--workload", CELL, "--seed", str(2**31 + 43), "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=scratch_checkout, env=env, capture_output=True, text=True,
        timeout=900)
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
    assert out["reader_skipped"]["name"] == "churn.decide_roofline"
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["churn.evictions_per_decision"] > 0.9
    assert m["churn.fresh_lane_share"] > 0.9
    # 32,768 slots turn over several times a second: rebuilds every ~16k
    assert m["churn.rebuilds_in_window"] >= 1
    assert m["churn.rebuild_max_ms"] > 0 and m["churn.rebuild_ms_per_s"] > 0
    assert m["churn.harvests_before_peak"] >= 1
    assert m["churn.call_max_ms"] >= m["call_p99_ms.churn"] > 0
    # `churn.ledger_violations` is printed and not held to 0 here: at this
    # size a slot changes hands several times inside one audit tick and the
    # ledger gives its lanes to the last holder (false violations, which
    # 10M slots do not reach: PERF.md section 7)
    assert m["churn.idle_share.no_work"] + m["churn.idle_share.housekeeping"] \
        + m["churn.idle_share.host"] == pytest.approx(
            m["churn.device_idle_share"])
    # nothing of the run is left behind
    assert subprocess.run(["pgrep", "-f", "[g]ubernator_tpu.cmd.daemon"],
                          capture_output=True).stdout == b""
