"""The open loop (PR 45): the schedule is traffic, a call is timed from the
instant it was due, the window takes a call by that instant, and the closed
loop is what it was. The sender is driven against a stub server that sleeps
(one worker: calls queue behind each other, as behind a stalled daemon)."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from concurrent import futures

import grpc
import numpy as np
import pytest

import host_pressure
import loadgen
import open_math
import run
from conftest import BENCH, REPO, listed, reads_on_a_cpu
from traffic import Call, Traffic

CELL = "node10m.open80"
SEED = 2**31 + 45
N = 3  # requests a stub call
# one answer: status 0, limit 10, remaining 5, reset_time 1
ANSWER = b"\x0a\x06\x10\x0a\x18\x05\x20\x01" * N
# sha256[:16] at the parent (932602f) of what the closed loop sends: client
# 1's pool of four calls in each accepted cell at its configuration's
# rehearsal size
PARENT_POOLS = {
    "node10m.batch1000": "960069dd0dd41770",
    "node10m.herd100": "a61a873ecfa0cd74",
    "mesh40m.batch1000": "960069dd0dd41770",
    "hot10m.repeats1000": "3060a299c5b59af8",
    "churn10m.newkeys1000": "a976f55a1f43d051",
}


def open_mix():
    return run.load_cell(CELL)[2]


# ---- the schedule


def test_the_schedule_is_a_pure_function_of_mix_seed_and_client():
    _, conf, mix, _ = run.load_cell(CELL)

    def offsets(seed, client, n, **changed):
        return Traffic({**mix, **changed}, conf["key_model"], 32768,
                       seed).arrival_offsets_ns(client, n)

    a = offsets(SEED, 3, 4000)
    assert np.array_equal(a, offsets(SEED, 3, 4000))
    assert np.array_equal(a[:1000], offsets(SEED, 3, 1000))  # a prefix
    assert not np.array_equal(a, offsets(SEED, 4, 4000))
    assert not np.array_equal(a, offsets(SEED + 1, 3, 4000))
    assert np.all(np.diff(a) >= 0) and a[0] > 0
    # 8 clients share the rate: one client's mean gap is 8 calls' worth
    want_s = mix["clients"] * mix["requests_per_call"] / mix["rate_per_s"]
    assert np.diff(a).mean() / 1e9 == pytest.approx(want_s, rel=0.06)
    # exponential gaps: the standard deviation is the mean
    assert np.diff(a).std() / 1e9 == pytest.approx(want_s, rel=0.1)
    half = offsets(SEED, 3, 4000, rate_per_s=mix["rate_per_s"] / 2)
    assert half[-1] == pytest.approx(2 * a[-1], rel=1e-6)


def test_the_clients_together_offer_the_mixs_rate():
    _, conf, mix, _ = run.load_cell(CELL)
    traffic = Traffic(mix, conf["key_model"], 32768, SEED)
    due = [loadgen._due_ns(traffic, c, 100.0, 150.0)
           for c in range(mix["clients"])]
    assert all(np.all((d >= 100e9) & (d < 150e9)) for d in due)
    offered = sum(map(len, due)) * mix["requests_per_call"] / 50.0
    assert offered == pytest.approx(mix["rate_per_s"], rel=0.03)


# ---- the sender and the window, against a server that sleeps


@pytest.fixture()
def stub():
    """A one-worker gRPC server whose every answer takes `stub.delay_s`."""
    state = type("Stub", (), {"delay_s": 0.05, "calls": 0})()

    def answer(request, context):
        state.calls += 1
        time.sleep(state.delay_s)
        return ANSWER

    server = grpc.server(futures.ThreadPoolExecutor(max_workers=1))
    server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
        "pb.gubernator.V1", {"GetRateLimits": grpc.unary_unary_rpc_method_handler(
            answer)}),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    state.address = f"127.0.0.1:{port}"
    yield state
    server.stop(0)


POOL = [Call(body=b"\x0a\x00" * N, limits=np.full(N, 10, np.int64),
             audit_pos=np.asarray([1], np.int32),
             audit_ids=np.asarray([7], np.uint64))]


def drive(stub, offsets_ms, timeout_s=5.0):
    """The sender over `offsets_ms` from 0.2 s hence; -> (start ns, log)."""
    clock = loadgen._Clock()
    start = clock.now_ns() + 200_000_000
    due = start + np.asarray(offsets_ms, np.int64) * 1_000_000
    out = []
    loadgen._open_loop(stub.address, POOL, due, timeout_s, clock, out)
    return start, out


def reduce(start, log, ws_ms, we_ms):
    return loadgen._reduce({0: POOL}, {0: log}, (start + ws_ms * 1e6) / 1e9,
                           (start + we_ms * 1e6) / 1e9, 0.0)


def test_a_call_queued_behind_a_slow_one_is_charged_from_its_due_instant(stub):
    start, log = drive(stub, [0, 10, 20])
    assert [due - start for *_, due in log] == [0, 10_000_000, 20_000_000]
    lat_ms = [lat / 1e6 for _, _, _, lat, _, _ in log]
    # the server takes them one at a time, 50 ms each: the third was sent
    # on time and waited for two others
    assert lat_ms[0] == pytest.approx(50, abs=25)
    assert lat_ms[1] == pytest.approx(90, abs=25)
    assert lat_ms[2] == pytest.approx(130, abs=25)
    for _, sent, recv, lat, got, due in log:
        assert got == ANSWER and lat == recv - due
        assert 0 <= sent - due < 20e6  # all three went out when due
    r = reduce(start, log, -1, 1000)
    assert (r["calls"], r["decisions"], r["failed"]) == (3, 3 * N, 0)
    assert sorted(r["lat_ns"].tolist()) == sorted(x[3] for x in log)
    assert r["lag_ns"].tolist() == [x[1] - x[5] for x in log]
    assert open_math.in_flight_max(r["due_ns"], r["lat_ns"]) == 3
    # the audit rows keep the send and receive instants, for check.py
    assert r["audits"][:, 1].tolist() == [x[1] for x in log]
    assert r["audits"][:, 2].tolist() == [x[2] for x in log]


def test_a_call_sent_late_is_charged_from_its_due_instant(stub):
    # due 150 ms before the sender starts: it goes out at once, late
    start, log = drive(stub, [-350])
    (_, sent, recv, lat, got, due), = log
    assert got == ANSWER and due == start - 350_000_000
    assert sent - due >= 150e6
    assert lat == recv - due and lat >= (sent - due) + 50e6 - 5e6
    r = reduce(start, log, -1000, 1000)
    assert r["lag_ns"].tolist() == [sent - due]
    w = open_math.window(r["due_ns"], r["lat_ns"], r["lag_ns"],
                         (start - 1e9) / 1e9, 2.0)
    # the latency is the generator's part plus the server's
    assert w["send_lag_ms"]["max"] + w["answer_ms"]["max"] == \
        pytest.approx(lat / 1e6, abs=1e-6)
    assert w["answer_ms"]["max"] == pytest.approx(50, abs=25)


def test_a_call_due_in_the_window_and_answered_after_it_is_counted(stub):
    start, log = drive(stub, [0, 10, 20])
    # the window closes at 30 ms: all three were due in it, none was back
    assert all(recv - start > 30e6 for _, _, recv, _, _, _ in log)
    r = reduce(start, log, -1, 30)
    assert (r["calls"], r["attempted"], r["decisions"]) == (3, 3 * N, 3 * N)
    # by the due instant, not the sending or the answer
    r = reduce(start, log, 9, 19)
    assert r["calls"] == 1 and r["due_ns"].tolist() == [start + 10_000_000]
    # the closed loop's rule on the same instants censors every one
    closed = [x[:5] for x in log]
    assert loadgen._reduce({0: POOL}, {0: closed}, (start - 1e6) / 1e9,
                           (start + 30e6) / 1e9, 0.0)["calls"] == 0


def test_an_unanswered_call_fails_its_decisions(stub):
    stub.delay_s = 1.0
    start, log = drive(stub, [0], timeout_s=0.2)
    (_, _, recv, lat, got, due), = log
    assert got.startswith(loadgen.UNANSWERED)
    assert lat == recv - due and lat >= 0.2e9
    r = reduce(start, log, -1, 1000)
    assert (r["calls"], r["attempted"], r["failed"], r["decisions"],
            r["unanswered"]) == (1, N, N, 0, 1)
    assert r["malformed_all"] == N  # `shape_violations` of the check


def test_in_flight_max_counts_due_and_not_yet_answered():
    due = np.asarray([0, 10, 20, 100], np.int64)
    lat = np.asarray([15, 100, 5, 1], np.int64)
    # at 10: two; the first is back at 15, the third comes at 20: two
    assert open_math.in_flight_max(due, lat) == 2
    assert open_math.in_flight_max(due, np.asarray([30, 30, 30, 1])) == 3
    # an answer at the instant another call is due leaves first
    assert open_math.in_flight_max(np.asarray([0, 10]),
                                   np.asarray([10, 5])) == 1
    assert open_math.in_flight_max(np.asarray([], np.int64),
                                   np.asarray([], np.int64)) == 0


def test_the_window_splits_a_call_and_says_when_its_slowest_was_due():
    ms = 1_000_000
    # a 10 s window from t = 100 s; one call a second, 5 ms each, but the
    # call due at 6.5 s was sent 300 ms late and then took 700 ms, and the
    # last two are still out when the window closes
    due = (100_000 + np.arange(10) * 1000 + 500) * ms
    lag = np.zeros(10, np.int64)
    lat = np.full(10, 5 * ms)
    lag[6], lat[6] = 300 * ms, 1000 * ms
    lat[8:] = 2000 * ms
    w = open_math.window(due, lat, lag, 100.0, 10.0)
    assert w["send_lag_ms"] == {"p50": 0.0, "p99": 300.0, "max": 300.0,
                                "max_due_s": 6.5}
    assert w["answer_ms"]["max"] == 2000.0 and w["answer_ms"]["p50"] == 5.0
    assert w["slowest_call"] == {"ms": 2000.0, "due_s": 8.5,
                                 "send_lag_ms": 0.0}
    assert w["in_flight_at_close"] == 2
    assert w["in_flight_max"] == 2
    # the window's fifths by due instant: a backlog that grows shows as a
    # median that grows
    assert w["p50_by_fifth_ms"] == [5.0, 5.0, 5.0, 502.5, 2000.0]
    lat[6] = 3000 * ms  # now the late call is the slowest
    assert open_math.window(due, lat, lag, 100.0, 10.0)["slowest_call"] == {
        "ms": 3000.0, "due_s": 6.5, "send_lag_ms": 300.0}


def test_host_pressure_reads_what_the_kernel_offers_and_diffs_it():
    a = host_pressure.counters()
    assert all(isinstance(v, float) or isinstance(v, int) for v in a.values())
    b = dict(a, extra=1.0)
    d = host_pressure.diff(a, b)
    assert set(d) == set(a) and all(v == 0 for v in d.values())
    if os.path.exists("/proc/stat"):
        assert {"stat_steal_ms", "stat_iowait_ms"} <= set(a)


def test_background_units_are_the_tickers_diff_between_two_scrapes():
    import scrape_math

    before = {"profile": {"bg_sites": {
        "ledger.audit": {"n": 1, "total_ns": 10**9}}}}
    after = {"profile": {"bg_sites": {
        "ledger.audit": {"n": 9, "total_ns": 9 * 10**9},
        "keyspace.harvest": {"n": 1, "total_ns": 520 * 10**6},
        "history.sample": {"n": 0, "total_ns": 0}}}}
    assert scrape_math.background_units(before, after) == {
        "ledger.audit": [8, 8000.0], "keyspace.harvest": [1, 520.0]}
    assert scrape_math.background_units({"profile": {}}, {"profile": {}}) == {}


def test_the_watch_keeps_a_wake_up_that_came_late():
    watch = host_pressure.Watch(time.time())
    time.sleep(0.1)
    t0 = time.time()
    sum(range(40_000_000))  # one C loop: holds the GIL, the watch cannot wake
    held_ms = (time.time() - t0) * 1e3
    time.sleep(0.1)
    got = watch.stop(0.0, 60.0)
    assert got["gaps"] >= 1 and got["gap_max_ms"] >= 0.5 * held_ms
    assert got["gap_max_ms"] <= held_ms + 100
    at, ms = got["longest"][0]
    assert 0.05 <= at <= 0.6 and ms == pytest.approx(got["gap_max_ms"], abs=0.1)
    # a window that holds none of it
    quiet = host_pressure.Watch(time.time())
    time.sleep(0.05)
    assert quiet.stop(10.0, 20.0) == {"gaps": 0, "gap_sum_ms": 0,
                                      "gap_max_ms": 0.0, "longest": []}


# ---- load_cell


@pytest.fixture()
def checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


@pytest.mark.parametrize("changed", [
    {"rate_per_s": None}, {"rate_per_s": 0}, {"rate_per_s": -1},
    {"rate_per_s": "fast"}, {"loop": "half-open"}, {"loop": "poisson"},
    {"loop": None}])
def test_load_cell_refuses_an_open_loop_without_a_rate_and_a_third_loop(
        checkout, changed):
    assert run.load_cell(CELL, repo=str(checkout))[2]["loop"] == "open"
    path = checkout / "benchmarks/workloads" / (CELL + ".json")
    path.write_text(json.dumps({**json.loads(path.read_text()), **changed}))
    with pytest.raises(run.RunFailed):
        run.load_cell(CELL, repo=str(checkout))


def test_the_cell_is_batch1000_but_for_its_loop():
    mix, base = open_mix(), run.load_cell("node10m.batch1000")[2]
    assert {k for k in set(mix) | set(base) if mix.get(k) != base.get(k)} == {
        "traffic", "who", "why", "loop", "rate_per_s", "rehearse",
        "clients", "pool_calls_per_client"}
    assert mix["loop"] == "open"
    # more connections, so that a stall leaves less on each (the mix's why);
    # as many pooled calls in all
    assert mix["clients"] * mix["pool_calls_per_client"] == \
        base["clients"] * base["pool_calls_per_client"]
    assert mix["clients"] % mix["processes"] == 0
    assert mix["rate_per_s"] % 10_000 == 0
    assert set(mix["rehearse"]) == {"rate_per_s"}


# ---- the closed loop is what it was


@pytest.mark.parametrize("cell", sorted(PARENT_POOLS))
def test_the_accepted_cells_pools_are_the_parents_bytes(cell):
    _, conf, mix, _ = run.load_cell(cell)
    traffic = Traffic({**mix, "pool_calls_per_client": 4}, conf["key_model"],
                      conf["rehearse"]["resident_keys"], SEED)
    h = hashlib.sha256()
    for c in traffic.build_pool(1):
        for part in (c.body, c.limits.tobytes(), c.audit_pos.tobytes(),
                     c.audit_ids.tobytes()):
            h.update(part)
    assert h.hexdigest()[:16] == PARENT_POOLS[cell]


def test_the_open_cells_pool_is_batch1000s():
    _, conf, base, _ = run.load_cell("node10m.batch1000")
    pools = [Traffic({**m, "pool_calls_per_client": 2}, conf["key_model"],
                     32768, SEED).build_pool(0) for m in (base, open_mix())]
    assert [c.body for c in pools[0]] == [c.body for c in pools[1]]


# ---- the rest of a run, rehearsed


def rehearse(*extra):
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(SEED), "--seconds", "4", "--rehearse", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return {ln.get("step", "result"): ln
            for ln in map(json.loads, r.stdout.splitlines())}


def test_traced_rehearsal_of_the_open_cell_prints_every_metric():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = rehearse("--trace", "1")
    check, window, result = out["check"], out["window"], out["result"]
    assert check["sound"] is True, check
    assert check["compared"]["audit_mismatches"]["value"] == 0
    assert check["compared"]["audited_answers"]["value"] >= 1000
    assert result["correct"] is False and result["rehearsal"] is True
    assert result["failed"] == 0 and window["unanswered"] == 0
    # what was offered is what the schedule holds, and all of it came back
    rate = open_mix()["rehearse"]["rate_per_s"]
    assert window["offered_decisions"] == result["attempted"] == \
        window["decisions"]
    assert window["offered_decisions"] == pytest.approx(4 * rate, rel=0.5)
    assert 0 <= window["send_lag_ms"]["p50"] <= window["send_lag_ms"]["max"]
    assert window["in_flight_max"] >= 1
    assert set(result["end_to_end"]) == listed(manifest, "end_to_end", CELL)
    assert set(result["metrics"]) == set(filter(
        reads_on_a_cpu, listed(manifest, "per_layer", CELL)))
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["open.answered_share"] == 1.0
    assert m["open.send_lag_ms"] == window["send_lag_ms"]["p99"]
    assert m["open.in_flight_max"] == window["in_flight_max"]
    assert m["open.answer_p99_ms"] == window["answer_ms"]["p99"]
    assert m["open.host_gap_max_ms"] == window["host"]["parent"]["gap_max_ms"]
    assert 0 <= window["slowest_call"]["due_s"] < 4
    assert window["slowest_call"]["ms"] == window["latency_ms"]["max"]
    assert len(window["p50_by_fifth_ms"]) == 5
    assert m["call_p90_ms.open"] == window["latency_ms"]["p90"]
    assert result["device"]["memory_peak_bytes"] > 0
    # each number compared beside its limit comes last in the line
    assert list(result)[-1] == "compared" and result["compared"] == \
        check["compared"]
    assert subprocess.run(["pgrep", "-f", "[g]ubernator_tpu.cmd.daemon"],
                          capture_output=True).stdout == b""


def test_rehearsal_of_the_open_cell_turns_false_under_the_control():
    out = rehearse("--trace", "0", "--control", "lost_hits")
    assert out["check"]["sound"] is False
    assert out["check"]["compared"]["audit_mismatches"]["value"] > 0
    assert out["check"]["compared"]["failed_decisions"]["value"] == 0
    assert out["result"]["correct"] is False
