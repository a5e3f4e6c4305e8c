"""Capacity & keyspace cartography: the metrics-history ring, the
keyspace cartographer's device-table harvest, the headroom forecaster,
and the `capacity` anomaly detector.

Closes with the acceptance drill: fill a small table past its occupancy
floor at a steady rate, watch the forecaster project time-to-full inside
the horizon, the `capacity` anomaly fire, and the triggered bundle carry
the history run-up showing the growth.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.cluster.harness import LocalCluster
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.obs.anomaly import AnomalyEngine
from gubernator_tpu.obs.bundle import BundleWriter, build_bundle
from gubernator_tpu.obs.history import MetricsHistory
from gubernator_tpu.obs.keyspace import (
    KeyspaceCartographer,
    concentration,
    hbm_bytes,
    headroom_forecast,
)
from gubernator_tpu.service.config import InstanceConfig
from gubernator_tpu.service.http_gateway import HttpGateway
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.service.metrics import Metrics
from gubernator_tpu.types import RateLimitReq


def _rl(key, hits=1, limit=1_000_000, duration=60_000, name="cap"):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration)


class _StubInstance:
    """Bare-minimum instance for ring tests: one mutable counter dict."""

    def __init__(self):
        self.deadline_expired_stats = {}

    backend = None


# ------------------------------------------------------------- the ring


class TestMetricsHistory:
    def test_fixed_interval_ring(self):
        h = MetricsHistory(_StubInstance(), tick_s=5.0, retention_s=60.0)
        t0 = 1000.0
        assert h.record(t0, h.collect(t0)) is True
        # inside one tick: rejected, the ring keeps its cadence
        assert h.record(t0 + 2.0, h.collect(t0 + 2.0)) is False
        assert h.record(t0 + 5.0, h.collect(t0 + 5.0)) is True
        assert h.sample_count() == 2
        tail = h.tail()
        assert [s["t"] for s in tail] == [t0, t0 + 5.0]

    def test_retention_prunes_oldest(self):
        h = MetricsHistory(_StubInstance(), tick_s=5.0, retention_s=30.0)
        for i in range(20):
            h.record(1000.0 + i * 5.0, h.collect(1000.0 + i * 5.0))
        ts = [s["t"] for s in h.tail()]
        assert ts[-1] == 1000.0 + 19 * 5.0
        assert ts[0] >= ts[-1] - 30.0
        assert h.ticks == 20  # ticks counts appends, not retained samples

    def test_window_snap(self):
        h = MetricsHistory(_StubInstance(), tick_s=5.0, retention_s=600.0)
        for i in range(10):
            h.record(1000.0 + i * 5.0, h.collect(1000.0 + i * 5.0))
        # newest sample at/older than the floor
        assert h.window_snap(1022.0)["t"] == 1020.0
        assert h.window_snap(1020.0)["t"] == 1020.0
        # floor before the ring: a young ring serves the oldest it has
        assert h.window_snap(900.0)["t"] == 1000.0
        assert MetricsHistory(_StubInstance()).window_snap(0.0) is None

    def test_series_and_counter_deltas(self):
        stub = _StubInstance()
        h = MetricsHistory(stub, tick_s=5.0, retention_s=600.0)
        h.record(1000.0, h.collect(1000.0))
        stub.deadline_expired_stats["ingress"] = 40
        h.record(1005.0, h.collect(1005.0))
        series = h.series("deadline_expired")
        assert series == [(1000.0, 0.0), (1005.0, 40.0)]

    def test_disabled_hatch(self):
        h = MetricsHistory(_StubInstance(), tick_s=5.0,
                           retention_s=7200.0, enabled=False)
        # retention clamps to the anomaly engine's burn-window floor
        assert h.retention_s <= 900.0
        h.record(1000.0, h.collect(1000.0))
        body = h.endpoint_body()
        assert body["enabled"] is False
        assert body["samples"] == []  # ring still serves the engine,
        assert body["sample_count"] == 1  # the endpoint stays dark
        h.start()
        assert h._thread is None  # no background ticker when disabled


# ------------------------------------------------- concentration & hbm


class TestAnalysis:
    def test_concentration_shares(self):
        counts = np.zeros(64, np.int64)
        counts[:4] = [70, 20, 7, 3]
        c = concentration(counts)
        assert c["tracked_hits"] == 100 and c["nonzero_slots"] == 4
        assert c["top1_share"] == pytest.approx(0.70)
        assert c["top10_share"] == pytest.approx(1.0)

    def test_zipf_exponent_recovers_power_law(self):
        ranks = np.arange(1, 101, dtype=np.float64)
        counts = (1e6 / ranks ** 1.3).astype(np.int64)
        c = concentration(counts)
        assert c["zipf_exponent"] == pytest.approx(1.3, abs=0.05)

    def test_zipf_needs_three_points(self):
        assert concentration(np.array([5, 3]))["zipf_exponent"] is None
        empty = concentration(np.zeros(8, np.int64))
        assert empty["tracked_hits"] == 0
        assert empty["zipf_exponent"] is None

    def test_hbm_bytes_truth(self):
        eng = Engine(capacity=256)
        hbm = hbm_bytes(eng)
        # i64[C, 8]: ground truth is capacity * 8 columns * 8 bytes
        assert hbm["arrays"]["state"] == 256 * 8 * 8
        assert hbm["total_bytes"] >= hbm["arrays"]["state"]
        assert hbm["per_device"][0]["state_bytes"] == 256 * 8 * 8


# ------------------------------------------------------------ forecaster


class TestHeadroomForecast:
    def _ring(self, counts, tick=5.0):
        stub = _StubInstance()
        h = MetricsHistory(stub, tick_s=tick, retention_s=7200.0)
        for i, kc in enumerate(counts):
            s = h.collect(1000.0 + i * tick)
            s["key_count"] = float(kc)
            h.record(1000.0 + i * tick, s)
        return h

    def test_projects_time_to_full(self):
        # +10 keys per 5 s over a 1000-slot table, currently at 700
        h = self._ring([660, 670, 680, 690, 700])
        eng = Engine(capacity=256)
        eng.capacity = 1000  # forecast only reads .capacity
        fc = headroom_forecast(h, eng)
        assert fc["projectable"] is True
        assert fc["growth_keys_per_s"] == pytest.approx(2.0)
        assert fc["fill_fraction"] == pytest.approx(0.7)
        assert fc["time_to_full_s"] == pytest.approx(150.0, rel=0.01)
        # pressure watermark 0.9 * 1000 = 900 -> 100 keys / 2 per s
        assert fc["time_to_pressure_s"] == pytest.approx(100.0, rel=0.01)

    def test_flat_table_not_projected(self):
        h = self._ring([500, 500, 500, 500])
        eng = Engine(capacity=256)
        eng.capacity = 1000
        fc = headroom_forecast(h, eng)
        assert fc["projectable"] is True
        assert fc["time_to_full_s"] is None
        assert fc["time_to_pressure_s"] is None

    def test_needs_min_samples(self):
        h = self._ring([10, 20])
        fc = headroom_forecast(h, Engine(capacity=256))
        assert fc["projectable"] is False and fc["samples"] == 2

    def test_past_watermark_reports_zero(self):
        h = self._ring([940, 950, 960])
        eng = Engine(capacity=256)
        eng.capacity = 1000
        fc = headroom_forecast(h, eng)
        assert fc["time_to_pressure_s"] == 0.0
        assert fc["time_to_full_s"] == pytest.approx(20.0, rel=0.01)


# --------------------------------------------------------- cartographer


class TestCartographer:
    def test_harvest_finds_planted_hot_keys(self):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256)))
        try:
            inst.get_rate_limits([_rl("whale", hits=500)])
            inst.get_rate_limits([_rl("warm", hits=40)])
            inst.get_rate_limits([_rl(f"cold{i}") for i in range(10)])
            rep = inst.keyspace.harvest()
            assert rep is not None and rep["keys_resolvable"] is True
            assert rep["occupancy"]["key_count"] == 12
            assert rep["occupancy"]["capacity"] == 256
            assert rep["occupancy"]["free_slots"] == 244
            top = rep["top_keys"]
            assert top[0]["key"] == "cap_whale" and top[0]["hits"] == 500
            assert top[1]["key"] == "cap_warm" and top[1]["hits"] == 40
            total = 500 + 40 + 10
            assert top[0]["share"] == pytest.approx(500 / total, abs=1e-4)
            assert rep["hit_mass"]["tracked_hits"] == total
            assert rep["hit_mass"]["top1_share"] == pytest.approx(
                500 / total, abs=1e-4)
            assert rep["hbm"]["arrays"]["state"] == 256 * 8 * 8
        finally:
            inst.close()

    def test_top_keys_through_per_owner_directories(self):
        """The sharded backend's shape: one directory per owner, global
        slot = owner * stride + local slot; each owner's directory is
        asked by index (native) or walked (Python twin), same answer."""
        from gubernator_tpu.models.keyspace import KeyDirectory
        from gubernator_tpu.native import make_key_directory

        inst = Instance(InstanceConfig(backend=Engine(capacity=64)))
        try:
            stride = 16
            dirs = [make_key_directory(stride), KeyDirectory(stride)]
            counts = np.zeros(2 * stride, np.int64)
            want = {}
            for o, d in enumerate(dirs):
                names = [f"owner{o}-key{i}" for i in range(5)]
                slots, _ = d.lookup(names)
                for i, (name, s) in enumerate(zip(names, slots)):
                    counts[o * stride + s] = 100 * (o + 1) + i
                    want[o * stride + s] = name

            class _Sharded:
                directories = dirs

            top = inst.keyspace._top_keys(_Sharded(), counts, stride)
            assert len(top) == 10
            assert [e["hits"] for e in top] == sorted(
                counts[counts > 0].tolist(), reverse=True)
            assert {e["slot"]: e["key"] for e in top} == want
            assert {e["owner"] for e in top} == {0, 1}
        finally:
            inst.close()

    def test_top_k_bound_and_disabled_hatch(self):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256),
                                       keyspace_top_k=3,
                                       keyspace_scan=False))
        try:
            inst.get_rate_limits([_rl(f"k{i}", hits=i + 1)
                                  for i in range(8)])
            # report() never scans while disabled
            assert inst.keyspace.report() is None
            body = inst.keyspace.endpoint_body()
            assert body["enabled"] is False and body["report"] is None
            inst.keyspace.start()
            assert inst.keyspace._thread is None
            # an explicit harvest still works (operator ?refresh=1)
            rep = inst.keyspace.harvest()
            assert [e["key"] for e in rep["top_keys"]] == [
                "cap_k7", "cap_k6", "cap_k5"]
        finally:
            inst.close()

    def test_maybe_harvest_interval_gate(self, monkeypatch):
        """The gate reads time.monotonic() against a `_last_harvest` that
        starts at 0.0: on a host up for less than the interval the first
        call is not due, which made this test fail on a freshly booted
        machine. The test owns the clock."""
        import gubernator_tpu.obs.keyspace as keyspace_mod

        class Clock:
            now = 10_000.0
            monotonic = staticmethod(lambda: Clock.now)
            perf_counter = staticmethod(time.perf_counter)
            time = staticmethod(time.time)

        monkeypatch.setattr(keyspace_mod, "time", Clock)
        inst = Instance(InstanceConfig(backend=Engine(capacity=256),
                                       keyspace_interval_s=3600.0))
        try:
            inst.keyspace.maybe_harvest()
            assert inst.keyspace.harvests == 1
            Clock.now += 3599.0
            inst.keyspace.maybe_harvest()  # within the interval: no scan
            assert inst.keyspace.harvests == 1
            Clock.now += 1.0
            inst.keyspace.maybe_harvest()  # one interval later: due
            assert inst.keyspace.harvests == 2
        finally:
            inst.close()


# -------------------------------------------------- anomaly ring + drill


class TestCapacityDetector:
    def test_anomaly_shares_instance_ring(self):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256)))
        try:
            assert inst.anomaly.history is inst.history
            inst.anomaly.check()
            assert inst.history.sample_count() >= 1
        finally:
            inst.close()

    def test_standalone_engine_builds_private_ring(self):
        eng = AnomalyEngine(_StubInstance(), interval_s=5.0)
        assert isinstance(eng.history, MetricsHistory)
        assert eng.history.anomaly is eng
        eng.check(1000.0)
        eng.check(1005.0)
        assert eng.history.sample_count() == 2

    def test_capacity_drill_fires_and_bundles(self, tmp_path):
        """Fill a 512-slot table past the occupancy floor at a steady
        rate: the forecaster projects full inside the horizon, the
        `capacity` anomaly fires, health is annotated, and the bundle
        carries the history run-up."""
        inst = Instance(InstanceConfig(backend=Engine(capacity=512),
                                       capacity_horizon_s=1800.0))
        inst.bundle_writer = BundleWriter(str(tmp_path), min_interval_s=0.0)
        try:
            t0 = time.monotonic() + 100.0
            step, batch = 5.0, 48
            fired_at = None
            for i in range(8):
                inst.get_rate_limits([
                    _rl(f"fill-{i}-{j}") for j in range(batch)])
                found = inst.anomaly.check(t0 + i * step)
                if found["capacity"]:
                    fired_at = i
                    break
            assert fired_at is not None, "capacity never fired"
            # floor: > 50% of 512 slots filled before the first fire
            assert (fired_at + 1) * batch > 256
            assert "capacity" in inst.anomaly.detail
            assert "table full in" in inst.anomaly.detail["capacity"]
            assert inst.anomaly.trips["capacity"] == 1
            # annotation only: the node never flips unhealthy from this
            h = inst.health_check()
            assert h.status == "healthy"
            assert "capacity" in h.message
            # the triggered bundle carries the run-up
            files = [f for f in os.listdir(tmp_path)
                     if "anomaly-capacity" in f]
            assert len(files) == 1
            with open(tmp_path / files[0]) as f:
                b = json.load(f)
            assert b["reason"] == "anomaly:capacity"
            kc = [s["key_count"] for s in b["history"]]
            assert len(kc) >= 3 and kc[-1] > kc[0]  # growth visible
            assert b["capacity"]["time_to_full_s"] is not None
            assert b["capacity"]["time_to_full_s"] <= 1800.0
        finally:
            inst.close()

    def test_young_table_stays_quiet(self):
        """Same growth, but far below the occupancy floor: the first-fill
        slope must not page anyone."""
        inst = Instance(InstanceConfig(backend=Engine(capacity=4096)))
        try:
            t0 = time.monotonic() + 100.0
            for i in range(5):
                inst.get_rate_limits([
                    _rl(f"young-{i}-{j}") for j in range(48)])
                found = inst.anomaly.check(t0 + i * 5.0)
                assert not found["capacity"]
        finally:
            inst.close()


# ------------------------------------------------------ endpoints & env


class TestEndpoints:
    def test_history_and_keyspace_endpoints(self):
        m = Metrics()
        inst = Instance(InstanceConfig(backend=Engine(capacity=256),
                                       metrics=m))
        gw = HttpGateway(inst, "127.0.0.1:0", metrics=m)
        gw.start()
        try:
            inst.get_rate_limits([_rl("hot", hits=90), _rl("cold")])
            inst.history.tick()

            def get(path):
                url = f"http://{gw.address}{path}"
                with urllib.request.urlopen(url) as r:
                    return json.loads(r.read())

            h = get("/v1/debug/history?n=10")
            # v3 added the ledger_* columns (tests/test_debug_schema.py)
            assert h["schema_version"] == 3
            assert h["sample_count"] >= 1
            assert h["samples"][-1]["key_count"] == 2.0
            k = get("/v1/debug/keyspace?refresh=1")
            assert k["schema_version"] == 1
            assert k["report"]["occupancy"]["key_count"] == 2
            assert k["report"]["top_keys"][0]["key"] == "cap_hot"
            # scrape exports the new families
            text = m.render(inst).decode()
            assert "keyspace_fill_fraction" in text
            assert 'keyspace_hit_share{bucket="top1"}' in text
            assert "capacity_time_to_full_seconds" in text
            assert "history_samples" in text
        finally:
            gw.close()
            inst.close()

    def test_bundle_omits_history_when_disabled(self):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256),
                                       history_enabled=False))
        try:
            b = build_bundle(inst)
            assert "history" not in b
            assert "keyspace" in b  # the harvest is separate
        finally:
            inst.close()


class TestEnvKnobs:
    def test_defaults(self, monkeypatch):
        for var in ("GUBER_HISTORY", "GUBER_HISTORY_TICK_S",
                    "GUBER_HISTORY_RETENTION", "GUBER_KEYSPACE_SCAN",
                    "GUBER_KEYSPACE_INTERVAL", "GUBER_KEYSPACE_TOP_K",
                    "GUBER_CAPACITY_HORIZON"):
            monkeypatch.delenv(var, raising=False)
        from gubernator_tpu.cmd.envconf import config_from_env

        conf = config_from_env([])
        assert conf.history is True and conf.keyspace_scan is True
        assert conf.history_tick_s == 5.0
        assert conf.history_retention_s == 7200.0
        assert conf.keyspace_interval_s == 60.0
        assert conf.keyspace_top_k == 20
        assert conf.capacity_horizon_s == 1800.0

    def test_round_trip(self, monkeypatch):
        monkeypatch.setenv("GUBER_HISTORY", "0")
        monkeypatch.setenv("GUBER_HISTORY_TICK_S", "2s")
        monkeypatch.setenv("GUBER_HISTORY_RETENTION", "1h")
        monkeypatch.setenv("GUBER_KEYSPACE_SCAN", "false")
        monkeypatch.setenv("GUBER_KEYSPACE_INTERVAL", "30s")
        monkeypatch.setenv("GUBER_KEYSPACE_TOP_K", "50")
        monkeypatch.setenv("GUBER_CAPACITY_HORIZON", "15m")
        from gubernator_tpu.cmd.envconf import config_from_env

        conf = config_from_env([])
        assert conf.history is False and conf.keyspace_scan is False
        assert conf.history_tick_s == 2.0
        assert conf.history_retention_s == 3600.0
        assert conf.keyspace_interval_s == 30.0
        assert conf.keyspace_top_k == 50
        assert conf.capacity_horizon_s == 900.0

    @pytest.mark.parametrize("var,value", [
        ("GUBER_HISTORY_TICK_S", "0s"),
        ("GUBER_HISTORY_RETENTION", "1s"),  # < default 5 s tick
        ("GUBER_KEYSPACE_INTERVAL", "0s"),
        ("GUBER_KEYSPACE_TOP_K", "0"),
        ("GUBER_CAPACITY_HORIZON", "0s"),
    ])
    def test_validation(self, monkeypatch, var, value):
        monkeypatch.setenv(var, value)
        from gubernator_tpu.cmd.envconf import config_from_env

        with pytest.raises(ValueError, match=var):
            config_from_env([])


# --------------------------------------------------------- cluster view


@pytest.mark.slow
class TestClusterRollup:
    def test_two_node_keyspace_and_capacity_merge(self):
        cluster = LocalCluster().start(2)
        try:
            inst0 = cluster.instances[0].instance
            # spread keys across both owners; forwards land them on the
            # owning node's table
            inst0.get_rate_limits([_rl(f"spread{i}") for i in range(40)])
            # plus one unmistakable heavy hitter per owner, so the
            # cross-node top-K cut must keep entries from both nodes
            hot = {}
            for i in range(3000):
                addr = inst0.get_peer(f"cap_hh{i}").info.address
                if addr not in hot:
                    hot[addr] = f"hh{i}"
                if len(hot) == 2:
                    break
            assert len(hot) == 2
            inst0.get_rate_limits([_rl(k, hits=500) for k in hot.values()])
            for ci in cluster.instances:
                ci.instance.keyspace.harvest()
            from gubernator_tpu.obs.bundle import cluster_view

            view = cluster_view(inst0, timeout_s=10)
            ks = view["keyspace"]
            assert ks["total_keys"] == 42
            assert len(ks["node_key_counts"]) == 2
            assert sum(ks["node_key_counts"].values()) == 42
            rb = ks["ring_balance"]
            assert rb["ideal_share"] == pytest.approx(0.5)
            assert rb["max_skew"] >= 1.0
            assert sum(rb["shares"].values()) == pytest.approx(1.0,
                                                               abs=1e-3)
            # cross-node top-K merge is hit-sorted and node-tagged
            tops = ks["top_keys"]
            assert len({e["node"] for e in tops}) == 2
            hits = [e["hits"] for e in tops]
            assert hits == sorted(hits, reverse=True)
            assert len(view["capacity"]["nodes"]) == 2
        finally:
            cluster.stop()


class TestCapacityReport:
    """The operator report script renders real endpoint bodies offline —
    main() only adds the fetch."""

    def _import(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "capacity_report",
            os.path.join(os.path.dirname(__file__), os.pardir,
                         "scripts", "capacity_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_renders_live_instance_bodies(self):
        cr = self._import()
        eng = Engine(capacity=256)
        inst = Instance(InstanceConfig(backend=eng, history_tick_s=0.05))
        try:
            inst.get_rate_limits([_rl("whale", hits=300)]
                                 + [_rl(f"w{i}", hits=2) for i in range(9)])
            inst.history.tick()
            time.sleep(0.06)
            inst.history.tick()
            text = cr.render_report(inst.keyspace.endpoint_body(),
                                    inst.history.endpoint_body(n=24))
            assert "occupancy      10 / 256 keys" in text
            assert "cap_whale" in text
            assert "heavy hitters" in text
            assert "metrics-history ring" in text
        finally:
            inst.close()

    def test_renders_empty_and_disabled_branches(self):
        cr = self._import()
        text = cr.render_report({"enabled": True, "report": None,
                                 "forecast": {}})
        assert "no harvest yet" in text
        text = cr.render_report(
            {"enabled": False, "report": {"backend": "Engine",
                                          "occupancy": {}, "top_keys": []},
             "forecast": {"projectable": False, "samples": 1}},
            {"enabled": False, "sample_count": 0, "tick_s": 5.0,
             "retention_s": 900.0, "samples": []})
        assert "DISABLED (GUBER_KEYSPACE_SCAN=0)" in text
        assert "not projectable" in text
        assert "ring DISABLED (GUBER_HISTORY=0)" in text
