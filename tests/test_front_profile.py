"""One clock from the socket to the chip (obs/profile.py, PR 25).

- the native front's own histograms and counters (native/peerlink.cpp
  pls_profile): a frame held before the pull shows in `front_wait`, the
  whole call in `front_call`, and the pull counters match what was sent;
- `Profiler.background(site)`: per-site histograms of each site's own
  time, the Prometheus family, the flight-recorder event for a slow unit;
- the capture: no Python tracer, the program's own spans on the host
  plane while it runs and none outside it, units that straddle its edges;
- the two device facts of /v1/debug/vars: allocator memory and compiles
  since Ready.
"""

import ctypes
import glob
import os
import socket
import struct
import threading
import time

import grpc
import jax
import jax.numpy as jnp
import pytest

from gubernator_tpu import native
from gubernator_tpu.cluster.harness import LocalCluster
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.obs import profile as profile_mod
from gubernator_tpu.obs.introspect import debug_vars
from gubernator_tpu.obs.profile import (
    FRONT_COUNTERS,
    FRONT_PHASES,
    Profiler,
    background_of,
)
from gubernator_tpu.service.config import InstanceConfig
from gubernator_tpu.service.grpc_api import V1Stub
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.service.metrics import Metrics
from gubernator_tpu.service.pb import gubernator_pb2 as pb
from gubernator_tpu.service.peerlink import (
    METHOD_GET_PEER_RATE_LIMITS,
    PeerLinkService,
    encode_request_frame,
    read_front_profile,
)
from gubernator_tpu.types import PeerInfo, RateLimitReq
from gubernator_tpu.utils.platform import CompileWatch

MS = 1_000_000


def _rl(key, hits=1, limit=1_000_000, duration=60_000, name="fp"):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration)


class _Recorder:
    def __init__(self):
        self.events = []

    def emit(self, kind, **fields):
        self.events.append((kind, fields))


# ------------------------------------------------------- the native front


class _RawFront:
    """The C++ transport alone: this test is its puller and responder."""

    def __init__(self):
        self.lib = native.load_peerlink()
        bound = ctypes.c_int(0)
        self.handle = self.lib.pls_start2(0, ctypes.byref(bound), 1)
        assert self.handle
        self.sock = socket.create_connection(("127.0.0.1", bound.value))
        self.sock.settimeout(10)
        # the service's own buffer set, built without starting a service
        self.bufs = PeerLinkService._mk_pull_bufs(PeerLinkService)
        self.rid = 0

    def send(self, n_items: int) -> None:
        self.rid += 1
        self.sock.sendall(encode_request_frame(
            self.rid, METHOD_GET_PEER_RATE_LIMITS,
            [_rl(f"k{self.rid}-{i}") for i in range(n_items)]))

    def pull(self) -> int:
        return self.lib.pls_next_batch(self.handle, 2_000_000,
                                       *self.bufs["args"])

    def answer(self, got: int) -> None:
        b = self.bufs
        b["err_off"][:got + 1] = 0
        b["meta_off"][:got + 1] = 0
        self.lib.pls_send_responses(self.handle, got, *b["resp_ptrs"], b"",
                                    b["meta_ptr"], b"")

    def read_reply(self) -> None:
        (length,) = struct.unpack("<I", self._recv(4))
        self._recv(length)

    def _recv(self, n: int) -> bytes:
        out = b""
        while len(out) < n:
            chunk = self.sock.recv(n - len(out))
            assert chunk, "front closed the connection"
            out += chunk
        return out

    def profile(self):
        """({front phase: snapshot}, {front counter: value})"""
        prof = Profiler(enabled=True)
        prof.attach_front(
            lambda: read_front_profile(self.lib, self.handle))
        return prof.front_totals()

    def close(self) -> None:
        self.sock.close()
        self.lib.pls_stop(self.handle)
        self.lib.pls_free(self.handle)


@pytest.fixture()
def raw_front():
    front = _RawFront()
    yield front
    front.close()


class TestNativeFront:
    def test_a_frame_held_before_the_pull(self, raw_front):
        f = raw_front
        for _ in range(3):
            f.send(2)
        time.sleep(0.06)  # nobody pulls: the frames sit in the C++ queue
        got = 0
        while got < 6:  # the IO thread may still be parsing the last one
            n = f.pull()
            assert n > 0
            time.sleep(0.01)
            f.answer(n)
            got += n
        for _ in range(3):
            f.read_reply()
        phases, counters = f.profile()
        wait, call = phases["front_wait"], phases["front_call"]
        assert wait["n"] == call["n"] == 3
        # every frame but possibly the last was parsed before the sleep
        assert wait["max_ns"] >= 50 * MS
        assert wait["total_ns"] >= 2 * 50 * MS
        # the call contains the wait and the 10 ms the "engine" took
        assert call["total_ns"] >= wait["total_ns"] + 10 * MS
        assert call["max_ns"] >= wait["max_ns"]
        assert phases["front_parse"]["n"] == 3
        assert phases["front_write"]["n"] == 3
        assert 0 < phases["front_write"]["total_ns"] < call["total_ns"]
        assert wait["p50_ns"] >= 50 * MS  # the bucket's upper bound
        assert counters["frames_pulled"] == 3
        assert counters["items_pulled"] == 6
        assert counters["frames_native"] == 0

    def test_pull_counters_match_frames_sent(self, raw_front):
        f = raw_front
        for n_items in (1, 5, 3, 7):  # one pull a frame
            f.send(n_items)
            assert f.pull() == n_items
            f.answer(n_items)
            f.read_reply()
        _, counters = f.profile()
        assert counters == {"pulls": 4, "frames_pulled": 4,
                            "items_pulled": 16, "frames_native": 0}
        for _ in range(5):  # five frames, one pull
            f.send(2)
        time.sleep(0.05)
        assert f.pull() == 10
        f.answer(10)
        _, counters = f.profile()
        assert counters["pulls"] == 5 and counters["frames_pulled"] == 9
        # a pull that times out takes nothing and counts nothing
        assert f.lib.pls_next_batch(f.handle, 1000, *f.bufs["args"]) == 0
        assert f.profile()[1]["pulls"] == 5

    def test_a_short_buffer_is_refused(self, raw_front):
        buf = (ctypes.c_longlong * 8)()
        assert raw_front.lib.pls_profile(raw_front.handle, buf, 8) == -1


class TestServiceFront:
    def test_the_profile_body_carries_the_front(self):
        cl = LocalCluster().start(1)
        inst = cl.instances[0].instance
        svc = PeerLinkService(inst, port=0, grpc_port=0)
        ch = grpc.insecure_channel(f"127.0.0.1:{svc.grpc_port}")
        try:
            v1 = V1Stub(ch)
            calls = 7
            for i in range(calls):  # two items: the IO thread takes lone ones
                r = v1.GetRateLimits(pb.GetRateLimitsReq(requests=[
                    pb.RateLimitReq(name="fp", unique_key=f"a{i}", hits=1,
                                    limit=9, duration=60_000),
                    pb.RateLimitReq(name="fp", unique_key=f"b{i}", hits=1,
                                    limit=9, duration=60_000)]), timeout=10)
                assert len(r.responses) == 2
            body = inst.profiler.endpoint_body()
            front = body["front"]
            assert front["attached"] is True
            assert front["frames_pulled"] + front["frames_native"] == calls
            assert front["items_pulled"] == 2 * front["frames_pulled"]
            assert 1 <= front["pulls"] <= front["frames_pulled"]
            for p in FRONT_PHASES:
                assert body["phases"][p]["n"] == front["frames_pulled"]
            assert body["phases"]["front_call"]["total_ns"] >= \
                body["phases"]["front_wait"]["total_ns"]
        finally:
            ch.close()
            svc.close()
            cl.stop()
        # closed: the reader is detached before the handle is freed
        assert inst.profiler.endpoint_body()["front"]["attached"] is False

    def test_no_front_reads_zeros(self):
        body = Profiler(enabled=True).endpoint_body()
        assert body["front"] == {"attached": False,
                                 **{c: 0 for c in FRONT_COUNTERS}}
        assert all(body["phases"][p]["n"] == 0 for p in FRONT_PHASES)


# --------------------------------------------------------- background work


class TestBackground:
    def test_feeds_bg_sites_with_each_sites_own_time(self):
        p = Profiler(enabled=True)
        with p.background("outer"):
            time.sleep(0.02)
            with p.background("inner"):
                time.sleep(0.03)
        sites = p.endpoint_body()["bg_sites"]
        assert set(sites) == {"inner", "outer"}
        assert sites["inner"]["n"] == sites["outer"]["n"] == 1
        assert sites["inner"]["total_ns"] >= 30 * MS
        # the outer unit's own time leaves the nested unit out
        assert 20 * MS <= sites["outer"]["total_ns"] < 30 * MS + 15 * MS
        assert p.debug()["bg_sites"] == 2
        totals = p.background_totals()
        assert totals["inner"] == {"n": 1,
                                   "total_ns": sites["inner"]["total_ns"]}

    def test_a_slow_unit_lands_in_the_flight_recorder(self, monkeypatch):
        monkeypatch.setattr(profile_mod, "BACKGROUND_SLOW_NS", 10 * MS)
        p = Profiler(enabled=True)
        p.recorder = rec = _Recorder()
        with p.background("quick"):
            pass
        assert rec.events == []
        with p.background("slow"):
            time.sleep(0.02)
        (kind, fields), = rec.events
        assert kind == "profile.background_slow"
        assert fields["site"] == "slow" and fields["ms"] >= 20

    def test_disabled_and_absent_profilers_are_inert(self):
        p = Profiler(enabled=False)
        with p.background("x"):
            pass
        assert p.endpoint_body()["bg_sites"] == {}
        with background_of(None, "x"), background_of(object(), "x"):
            pass

    def test_a_unit_that_raises_is_still_counted(self):
        p = Profiler(enabled=True)
        with pytest.raises(ValueError):
            with p.background("boom"):
                raise ValueError("x")
        assert p.background_totals()["boom"]["n"] == 1
        assert p._bg_open == {}

    def test_the_tickers_report_and_prometheus_mirrors_them(self):
        metrics = Metrics()
        inst = Instance(InstanceConfig(backend=Engine(capacity=256),
                                       metrics=metrics),
                        advertise_address="127.0.0.1:9999")
        try:
            inst.set_peers([PeerInfo(address="127.0.0.1:9999")])
            inst.get_rate_limits([_rl("bg1"), _rl("bg2")])
            inst.anomaly.check()
            inst.ledger.audit(inst.backend)
            inst.history.tick(time.monotonic() + 3600)
            inst.keyspace.harvest()
            sites = inst.profiler.endpoint_body()["bg_sites"]
            assert {"anomaly.check", "ledger.audit", "ledger.resolve_slots",
                    "history.sample", "keyspace.harvest"} <= set(sites)
            text = metrics.render(inst).decode()
            assert 'background_seconds_total{site="ledger.audit"}' in text
            assert 'background_seconds_total{site="anomaly.check"}' in text
        finally:
            inst.close()


# -------------------------------------------------------------- the capture


def _host_spans(trace_dir):
    """{span name: count} over the host plane of a capture."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, f"no .xplane.pb under {trace_dir}"
    data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    names = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
    return names


class TestCaptureSpans:
    def test_the_seams_write_spans_only_while_a_capture_runs(self, tmp_path):
        eng = Engine(capacity=256, min_width=8, max_width=16)
        prof = eng.profiler
        prof.enabled = True
        prof.capture_min_interval_s = 0.0
        stop = threading.Event()
        served = []

        def serve():
            i = 0
            while not stop.is_set():
                eng.get_rate_limits([_rl(f"c{i}-{j}") for j in range(8)])
                i += 1
            served.append(i)

        def ticker():
            with prof.background("test.straddles_the_start"):
                entered.set()
                time.sleep(0.7)

        entered = threading.Event()
        try:
            eng.get_rate_limits([_rl("warm")])
            # no capture: the seams do nothing and make no span object
            assert prof.seams() is profile_mod._no_seams
            assert prof.span("post") is profile_mod._NO_SPAN
            threads = [threading.Thread(target=serve),
                       threading.Thread(target=ticker)]
            for t in threads:
                t.start()
            assert entered.wait(5)
            out = prof.capture(str(tmp_path), seconds=0.3)
            with prof.background("test.after_the_capture"):
                pass
            stop.set()
            for t in threads:
                t.join(timeout=20)
                assert not t.is_alive()
        finally:
            stop.set()
            eng.close()
        assert out["ok"] is True and out["mode"] == "jax_trace", out
        assert prof._capturing is False
        assert prof.seams() is profile_mod._no_seams
        assert out["launches_per_s_in"] > 0 and out["launches_per_s_out"] > 0
        rates = prof.endpoint_body()["capture"]["last_rates"]
        assert rates["launches_per_s_in"] == out["launches_per_s_in"]
        # no native front attached: its counters stand still
        assert (rates["frames_pulled_in"], rates["items_pulled_in"]) == (0, 0)
        spans = _host_spans(out["path"])
        for name in ("lock_wait", "prep", "dispatch", "readback", "demux"):
            assert spans.get(name, 0) >= 1, (name, sorted(spans))
        # a unit that began before the capture is in it; one that began
        # after it is not
        assert spans.get("bg:test.straddles_the_start") == 1
        assert "bg:test.after_the_capture" not in spans
        # the Python tracer is off: no interpreter frames among the events
        assert not any(n.startswith("$") for n in spans), sorted(spans)[:20]
        assert served and served[0] > 0

    def test_capture_options_reach_the_profiler(self):
        body = Profiler(enabled=True).endpoint_body()["capture"]
        assert body["options"] == {"python_tracer_level": 0,
                                   "host_tracer_level": 2}
        assert body["last_rates"] is None


# ---------------------------------------------------------- device facts


class TestDeviceFacts:
    def test_memory_and_compiles_are_present_with_nulls_on_cpu(self):
        inst = Instance(InstanceConfig(backend=Engine(capacity=256)),
                        advertise_address="127.0.0.1:9999")
        try:
            dev = debug_vars(inst)["engine"]["device"]
            (mem,) = dev["memory"]
            assert set(mem) == {"device", "bytes_in_use",
                                "peak_bytes_in_use", "bytes_limit"}
            # the CPU allocator reports none of them
            assert mem["peak_bytes_in_use"] is None
            assert dev["compiles"] == {"count": None, "seconds": None}
            # mid-dispatch `backend.state` is the donated, deleted table:
            # the facts must not go through it
            state, inst.backend.state = inst.backend.state, None
            try:
                assert debug_vars(inst)["engine"]["device"]["memory"] == [mem]
            finally:
                inst.backend.state = state
            rec = _Recorder()
            inst.backend.compile_watch = watch = CompileWatch(rec)
            try:
                assert debug_vars(inst)["engine"]["device"]["compiles"] == {
                    "count": 0, "seconds": 0.0}

                def never_compiled_before(x):
                    return x * 3 + 1

                jax.jit(never_compiled_before)(
                    jnp.ones((3,), jnp.float32)).block_until_ready()
                got = debug_vars(inst)["engine"]["device"]["compiles"]
                assert got["count"] >= 1
                kinds = [k for k, _ in rec.events]
                assert kinds and set(kinds) == {"profile.compile"}
                assert any("never_compiled_before" in f["program"]
                           for _, f in rec.events)
                # published once, at the read, with its age
                assert len(rec.events) == got["count"]
                assert all(f["ago_s"] >= 0 for _, f in rec.events)
                debug_vars(inst)
                assert len(rec.events) == got["count"]
            finally:
                watch.close()
            n = len(rec.events)
            jax.jit(lambda x: x - 7)(jnp.ones((5,))).block_until_ready()
            assert watch.facts()["count"] == got["count"]
            assert len(rec.events) == n  # closed: it hears nothing more
        finally:
            inst.close()
