"""Continuous profiling plane: serving-cycle decomposition, lock-wait
accounting, kernel introspection, profile_shift, and the GUBER_PROFILE
escape hatch.

- differential: ``profile_enabled=False`` (GUBER_PROFILE=0) is
  bit-identical to the profiling path — the profiler only reads clocks,
  so turning it off cannot change a single decision;
- the `profile_shift` detector reads only history-ring columns and
  stays quiet without traffic.
"""

import json
import os

import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.obs.anomaly import AnomalyEngine
from gubernator_tpu.obs.profile import (
    FRONT_PHASES,
    PHASES,
    SERIAL_PHASES,
    SUB_PHASES,
    PhaseHist,
    Profiler,
    check_recompile,
    hlo_fingerprint,
)
from gubernator_tpu.service.config import InstanceConfig
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.types import PeerInfo, RateLimitReq


def _rl(key, hits=1, limit=1_000_000, duration=60_000, name="prof"):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration)


class _StubInstance:
    def __init__(self):
        self.deadline_expired_stats = {}

    backend = None


# ---------------------------------------------------------- histograms


class TestPhaseHist:
    def test_counts_totals_max(self):
        h = PhaseHist()
        for ns in (500, 2_000, 2_000_000, 7):
            h.observe(ns)
        n, total = h.totals()
        assert n == 4
        assert total == 500 + 2_000 + 2_000_000 + 7
        snap = h.snapshot()
        assert snap["n"] == 4
        assert snap["max_ns"] == 2_000_000
        # bucket-resolution quantiles bracket the mass
        assert snap["p50_ns"] <= snap["p99_ns"]
        assert snap["p99_ns"] >= 2_000_000 / 2  # within one log2 bucket

    def test_negative_clamped(self):
        h = PhaseHist()
        h.observe(-50)  # clock skew between two monotonic reads
        assert h.totals() == (1, 0)

    def test_empty_snapshot(self):
        snap = PhaseHist().snapshot()
        assert snap == {"n": 0, "total_ns": 0, "max_ns": 0,
                        "p50_ns": 0, "p99_ns": 0}


# ------------------------------------------------------------ profiler


class TestProfiler:
    def test_phases_and_sites(self):
        p = Profiler(enabled=True)
        for phase in PHASES:
            p.observe(phase, 1_000)
        p.lock_wait("site_a", 5_000)
        t = p.totals()
        assert set(t) == set(PHASES)
        assert all(t[ph]["n"] >= 1 for ph in PHASES)
        # lock_wait() feeds both the phase and the site histogram
        assert t["lock_wait"]["n"] == 2
        st = p.site_totals()
        assert st["site_a"]["n"] == 1
        assert st["site_a"]["total_ns"] == 5_000

    def test_disabled_is_inert(self):
        p = Profiler(enabled=False)
        p.observe("prep", 1_000)
        p.lock_wait("site_a", 1_000)
        assert all(t["n"] == 0 for t in p.totals().values())
        assert p.site_totals() == {}

    def test_decomposition_shares_over_serial_cycle(self):
        p = Profiler(enabled=True)
        p.observe("prep", 3_000_000)
        p.observe("dispatch", 6_000_000)
        p.observe("readback", 1_000_000)
        p.observe("queue_wait", 50_000_000)  # residency, not a slice
        dec = p.decomposition()
        serial_share = sum(dec[ph]["share"] for ph in SERIAL_PHASES)
        assert serial_share == pytest.approx(1.0, abs=0.01)
        assert dec["dispatch"]["share"] == pytest.approx(0.6, abs=0.01)
        # queue_wait reports against the same denominator and may exceed 1
        assert dec["queue_wait"]["share"] > 1.0

    def test_endpoint_body_and_debug(self):
        p = Profiler(enabled=True)
        p.observe("prep", 1_000)
        body = p.endpoint_body()
        assert body["enabled"] is True
        assert set(body["phases"]) == \
            set(PHASES) | set(FRONT_PHASES) | set(SUB_PHASES) \
            | {"leftover", "lock_hold"}
        dbg = p.debug()
        assert dbg["phases"]["prep"]["n"] == 1
        assert set(dbg["shares"]) == set(SERIAL_PHASES)


# ------------------------------------------------- engine integration


class TestEngineIntegration:
    def test_serving_feeds_every_phase(self):
        eng = Engine(capacity=256, min_width=8, max_width=16)
        try:
            eng.profiler.enabled = True
            reqs = [_rl(f"k{i}") for i in range(8)]
            for _ in range(3):
                eng.get_rate_limits(reqs, now_ms=1_000_000)
            t = eng.profiler.totals()
            for phase in ("lock_wait", "prep", "dispatch", "readback",
                          "demux"):
                assert t[phase]["n"] >= 3, (phase, t)
            assert eng.profiler.site_totals()  # at least one lock site
        finally:
            eng.close()

    def test_kernel_fingerprints_stable_within_process(self):
        eng = Engine(capacity=256, min_width=8, max_width=16)
        try:
            fps = eng.kernel_fingerprints()
            assert fps and all(len(v) == 16 for v in fps.values())
            assert fps == eng.kernel_fingerprints()  # deterministic
        finally:
            eng.close()


# ------------------------------------------------------------- hatch


class TestDifferential:
    def test_profile_off_bit_identical(self):
        """GUBER_PROFILE=0 differential: the SAME request stream through
        a profiling engine and a profile_enabled=False engine produces
        bit-identical decisions — status, limit, remaining, reset_time,
        every response field."""
        streams = [[_rl(f"d{i % 13}", hits=1 + i % 3, limit=40)
                    for i in range(24)] for _ in range(4)]
        now = 1_700_000_000_000
        eng_on = Engine(capacity=256, min_width=8, max_width=16)
        eng_off = Engine(capacity=256, min_width=8, max_width=16)
        try:
            eng_on.profiler.enabled = True
            eng_off.profiler.enabled = False
            for batch in streams:
                on = eng_on.get_rate_limits(batch, now_ms=now)
                off = eng_off.get_rate_limits(batch, now_ms=now)
                assert on == off
                now += 1_000
            # and the off profiler never moved a counter
            assert all(t["n"] == 0
                       for t in eng_off.profiler.totals().values())
            assert any(t["n"] > 0
                       for t in eng_on.profiler.totals().values())
            # nor a sub-phase, a lock hold or a site; and the link's
            # counters are the engine's, not the profiler's: they count
            # the same on both
            body_off = eng_off.profiler.endpoint_body()
            body_on = eng_on.profiler.endpoint_body()
            assert all(h["n"] == 0 for h in body_off["phases"].values())
            assert body_off["lock_hold_sites"] == {}
            # (no capture ran: the wait is not told from the copy)
            for p in ("stage", "launch", "lock_hold"):
                assert body_on["phases"][p]["n"] > 0, p
            for p in ("device_wait", "fetch"):
                assert body_on["phases"][p]["n"] == 0, p
            on_stats, off_stats = (e.stats.as_dict()
                                   for e in (eng_on, eng_off))
            for key in ("staged_bytes", "fetched_bytes"):
                assert on_stats[key] == off_stats[key] > 0, key
        finally:
            eng_on.close()
            eng_off.close()

    def test_instance_conf_overrides_profiler(self):
        inst = Instance(
            InstanceConfig(backend=Engine(capacity=256),
                           profile_enabled=False),
            advertise_address="127.0.0.1:9999")
        try:
            inst.set_peers([PeerInfo(address="127.0.0.1:9999")])
            assert inst.profiler.enabled is False
            inst.get_rate_limits([_rl("o")])
            assert all(t["n"] == 0 for t in inst.profiler.totals().values())
        finally:
            inst.close()

    def test_envconf_hatch_parses(self, monkeypatch):
        from gubernator_tpu.cmd.envconf import config_from_env

        monkeypatch.setenv("GUBER_PROFILE", "0")
        monkeypatch.setenv("GUBER_PROFILE_CAPTURE_S", "2m")
        conf = config_from_env()
        assert conf.profile_enabled is False
        assert conf.profile_capture_s == 120.0
        monkeypatch.setenv("GUBER_PROFILE", "1")
        assert config_from_env().profile_enabled is True
        monkeypatch.setenv("GUBER_PROFILE_CAPTURE_S", "0s")
        with pytest.raises(ValueError, match="GUBER_PROFILE_CAPTURE_S"):
            config_from_env()


# ------------------------------------------------------- profile_shift


class TestProfileShift:
    def _sig(self, **kw):
        return AnomalyEngine(_StubInstance(), **kw)._profile_shift_signal

    @staticmethod
    def _sample(cycles, **phase_s):
        s = {f"profile_{p}_s": 0.0 for p in PHASES}
        s["profile_cycles"] = float(cycles)
        for p, v in phase_s.items():
            s[f"profile_{p}_s"] = float(v)
        return s

    def test_fires_on_share_shift(self):
        sig = self._sig(profile_shift_threshold=0.15, profile_min_cycles=50)
        slow_old = self._sample(0)
        # baseline window: prep 20% / dispatch 80%
        fast_old = self._sample(100, prep=2.0, dispatch=8.0)
        # recent window: prep jumped to 60% of the serial cycle (the
        # mirror-image dispatch drop is the same magnitude; either phase
        # naming the shift is correct)
        cur = self._sample(200, prep=2.0 + 6.0, dispatch=8.0 + 4.0)
        detail = sig(cur, fast_old, slow_old)
        assert ("prep" in detail or "dispatch" in detail)
        assert "->" in detail and "over fast window" in detail

    def test_quiet_when_shares_stable(self):
        sig = self._sig(profile_min_cycles=50)
        slow_old = self._sample(0)
        fast_old = self._sample(100, prep=2.0, dispatch=8.0)
        cur = self._sample(200, prep=4.0, dispatch=16.0)  # same 20/80
        assert sig(cur, fast_old, slow_old) == ""

    def test_traffic_guard(self):
        sig = self._sig(profile_min_cycles=50)
        slow_old = self._sample(0)
        fast_old = self._sample(10, prep=0.1, dispatch=0.1)
        cur = self._sample(20, prep=1.0, dispatch=0.1)  # huge shift, 10 cycles
        assert sig(cur, fast_old, slow_old) == ""

    def test_quiet_without_profile_columns(self):
        sig = self._sig()
        assert sig({"decisions": 1.0}, {}, {}) == ""

    def test_end_to_end_through_history_ring(self):
        """The detector reads the ring the Instance actually records:
        an engine-backed instance's samples carry profile_* columns and
        a sweep runs without firing on quiet traffic."""
        inst = Instance(InstanceConfig(backend=Engine(capacity=256)),
                        advertise_address="127.0.0.1:9999")
        try:
            inst.set_peers([PeerInfo(address="127.0.0.1:9999")])
            inst.get_rate_limits([_rl("e")])
            found = inst.anomaly.check()
            assert "profile_shift" in found
            assert found["profile_shift"] is False
            sample = inst.history.collect(0.0)
            assert {f"profile_{p}_s" for p in PHASES} <= set(sample)
        finally:
            inst.close()


# ---------------------------------------------------- recompile watch


class _Recorder:
    def __init__(self):
        self.events = []

    def emit(self, kind, **fields):
        self.events.append((kind, fields))


class TestRecompileWatch:
    def test_first_boot_then_change(self, tmp_path):
        state = str(tmp_path / "fp.json")
        rec = _Recorder()
        r1 = check_recompile({"packed@64": "aa", "scan@64": "bb"}, state,
                             recorder=rec)
        assert r1["first_boot"] is True and not r1["changed"]
        r2 = check_recompile({"packed@64": "aa", "scan@64": "bb"}, state,
                             recorder=rec)
        assert r2["first_boot"] is False and not r2["changed"]
        assert rec.events == []
        r3 = check_recompile({"packed@64": "CHANGED", "scan@64": "bb"},
                             state, recorder=rec)
        assert set(r3["changed"]) == {"packed@64"}
        assert rec.events and rec.events[0][0] == "profile.recompile"
        # state persisted: the changed fingerprint is the new baseline
        r4 = check_recompile({"packed@64": "CHANGED"}, state, recorder=rec)
        assert not r4["changed"]

    def test_never_raises_on_bad_state(self, tmp_path):
        bad = tmp_path / "fp.json"
        bad.write_text("{not json")
        out = check_recompile({"k": "v"}, str(bad))
        assert out["first_boot"] is True

    def test_fingerprint_shape(self):
        fp = hlo_fingerprint("HloModule m\nROOT x = f32[] parameter(0)")
        assert len(fp) == 16
        assert fp == hlo_fingerprint(
            "HloModule m\nROOT x = f32[] parameter(0)")


# -------------------------------------------------------- deep capture


class TestCapture:
    def test_rate_limited(self, tmp_path):
        p = Profiler(enabled=True, capture_min_interval_s=3600.0)
        first = p.capture(str(tmp_path), seconds=0.05)
        assert first["ok"] is True
        assert first["mode"] in ("jax_trace", "wall_sampler")
        second = p.capture(str(tmp_path), seconds=0.05)
        assert second["ok"] is False
        assert second["error"] == "rate_limited"
        assert second["retry_in_s"] > 0
        body = p.endpoint_body()["capture"]
        assert body["count"] == 1
        assert body["last_path"] == first["path"]

    def test_wall_sampler_writes_stacks(self, tmp_path):
        p = Profiler(enabled=True, capture_min_interval_s=0.0)
        out = p.capture(str(tmp_path), seconds=0.05, mode="wall")
        assert out["ok"] is True and out["mode"] == "wall_sampler"
        with open(out["path"], encoding="utf-8") as fh:
            doc = json.load(fh)
        assert doc["samples"] >= 1 and doc["stacks"]

    def test_gateway_capture_path(self, tmp_path):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256),
                                       profile_capture_s=3600.0),
                        advertise_address="127.0.0.1:9999")
        try:
            inst.set_peers([PeerInfo(address="127.0.0.1:9999")])
            out = inst.profile_capture(0.05)
            assert out["ok"] is True
            assert os.path.exists(out["path"])
        finally:
            inst.close()


# ------------------------------------------------------ slow-log attach


class TestSlowLogAttach:
    def test_tracer_snapshot_wired(self):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256)),
                        advertise_address="127.0.0.1:9999")
        try:
            inst.set_peers([PeerInfo(address="127.0.0.1:9999")])
            inst.get_rate_limits([_rl("s")])
            snap = inst.tracer.profile_snapshot
            assert snap is not None
            doc = snap()
            assert set(doc["phases"]) == set(PHASES)
            json.dumps(doc)  # the slow log serializes it verbatim
        finally:
            inst.close()


# ----------------------------------------------------- operator report


class TestProfileReport:
    @staticmethod
    def _render(*bodies):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "profile_report",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "profile_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.render_report(*bodies)

    def test_renders_live_bodies_offline(self):
        from gubernator_tpu.ops.decide import kernel_telemetry

        eng = Engine(capacity=256, min_width=8, max_width=16)
        try:
            eng.profiler.enabled = True
            eng.get_rate_limits([_rl(f"r{i}") for i in range(8)],
                                now_ms=1_000_000)
            out = self._render(eng.profiler.endpoint_body(),
                               kernel_telemetry.kernels_body())
        finally:
            eng.close()
        assert "cycle decomposition" in out
        assert "engine-lock wait by call site" in out
        assert "kernel dispatch & cost" in out
        for phase in PHASES:
            assert phase in out

    def test_renders_empty_and_disabled(self):
        out = self._render({"enabled": False, "decomposition": {}})
        assert "DISABLED" in out
        assert "no serving cycles" in out
