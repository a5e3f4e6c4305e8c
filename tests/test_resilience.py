"""Peer-failure resilience drills: circuit breaker, degraded-local serving,
and recovery — proven deterministically via the fault-injection harness
(service/faults.py) in tier-1 wall time, instead of the ~minute-long
process-kill soaks.

The `chaos` marker groups these: they run fast and pinned-seed by default
(tier-1), and `make chaos` re-runs them with a randomized GUBER_CHAOS_SEED
(printed for reproduction)."""

import os
import random
import time
from concurrent.futures import Future

import pytest

from gubernator_tpu.cluster.harness import LocalCluster
from gubernator_tpu.cluster.harness import test_behaviors as _behaviors
from gubernator_tpu.service import faults
from gubernator_tpu.service.peer_client import (
    CIRCUIT_CLOSED,
    CIRCUIT_HALF_OPEN,
    CIRCUIT_OPEN,
    CircuitBreaker,
    CircuitOpenError,
    PeerClient,
    PeerNotReadyError,
)
from gubernator_tpu.types import PeerInfo, RateLimitReq

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.clear()


def _rl(key, hits=1, limit=5, duration=60_000, behavior=0, name="test"):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration, behavior=behavior)


def _key_owned_by(instance, owner_addr, prefix="cb"):
    """A key that `instance` routes to `owner_addr` (leading digits vary:
    trailing-suffix keys can collapse onto one fnv ring arc)."""
    for i in range(3000):
        k = f"{i}{prefix}"
        if instance.get_peer(f"test_{k}").info.address == owner_addr:
            return k
    raise AssertionError(f"no probe key routed to {owner_addr}")


class TestCircuitBreakerUnit:
    def test_transitions_and_single_probe(self):
        conf = _behaviors()
        conf.circuit_threshold = 3
        conf.circuit_open_s = 0.05
        cb = CircuitBreaker(conf, "peer:1")
        assert cb.allow() and not cb.blocked()
        cb.record_failure()
        cb.record_failure()
        assert cb.state == CIRCUIT_CLOSED  # below threshold
        cb.record_failure()
        assert cb.state == CIRCUIT_OPEN and cb.opened_total == 1
        assert cb.blocked() and not cb.allow()
        time.sleep(0.06)
        assert not cb.blocked()
        assert cb.allow()  # THE half-open probe
        assert cb.state == CIRCUIT_HALF_OPEN
        assert not cb.allow()  # concurrent caller blocked while probing
        cb.record_failure()  # probe failed: reopen for another cooldown
        assert cb.state == CIRCUIT_OPEN and cb.opened_total == 2
        time.sleep(0.06)
        assert cb.allow()
        cb.record_success()
        assert cb.state == CIRCUIT_CLOSED and cb.allow()

    def test_success_resets_consecutive_count(self):
        conf = _behaviors()
        conf.circuit_threshold = 3
        cb = CircuitBreaker(conf, "peer:1")
        for _ in range(5):  # interleaved successes never accumulate to open
            cb.record_failure()
            cb.record_failure()
            cb.record_success()
        assert cb.state == CIRCUIT_CLOSED

    def test_disabled_breaker_never_opens(self):
        conf = _behaviors()
        conf.circuit_threshold = 0
        cb = CircuitBreaker(conf, "peer:1")
        for _ in range(50):
            cb.record_failure()
        assert cb.state == CIRCUIT_CLOSED and cb.allow() and not cb.blocked()


class TestBreakerEndToEnd:
    """The acceptance drill: with one peer's transport killed (injected),
    (a) the breaker opens after the threshold and later forwards complete
    in < 50 ms, (b) GUBER_DEGRADED_LOCAL turns those into enforced
    degraded-local responses, (c) a half-open probe restores normal
    forwarding — transitions visible in the metrics exposition."""

    def test_breaker_opens_degrades_and_recovers(self):
        c = LocalCluster().start(3)
        try:
            for ci in c.instances:
                b = ci.instance.conf.behaviors
                b.circuit_threshold = 3
                b.circuit_open_s = 5.0  # long: the open phase is asserted
                b.degraded_local = False
            inst0 = c.instances[0].instance
            owner_addr = c.instances[1].address
            key = _key_owned_by(inst0, owner_addr)
            peer = inst0.get_peer(f"test_{key}")

            # kill the owner's transport (every call, both transports)
            faults.install(f"peer={owner_addr};action=error")

            # (a) exactly `threshold` transport failures, then open
            for i in range(3):
                r = inst0.get_rate_limits([_rl(key)])[0]
                assert "injected" in r.error, (i, r.error)
            assert peer.circuit.state == CIRCUIT_OPEN
            assert peer.circuit.opened_total == 1

            # open circuit: forwards fail fast — no batch_timeout_s stall
            for _ in range(5):
                t0 = time.monotonic()
                r = inst0.get_rate_limits([_rl(key)])[0]
                dt = time.monotonic() - t0
                assert "circuit open to owner" in r.error
                assert dt < 0.05, f"open-circuit forward took {dt * 1e3:.1f} ms"

            # (b) degraded-local: enforced decisions, marked in metadata
            inst0.conf.behaviors.degraded_local = True
            degraded = []
            for _ in range(3):
                t0 = time.monotonic()
                r = inst0.get_rate_limits([_rl(key, limit=2)])[0]
                dt = time.monotonic() - t0
                assert r.error == ""
                assert r.metadata["degraded"] == "true"
                assert r.metadata["owner"] == owner_addr
                assert dt < 0.05, f"degraded forward took {dt * 1e3:.1f} ms"
                degraded.append(r)
            # the local as-if-owner bucket ENFORCES the limit
            assert [r.remaining for r in degraded] == [1, 0, 0]
            assert degraded[2].status == 1  # OVER_LIMIT

            # breaker transitions + degraded serving in the exposition
            text = c.instances[0].metrics.render(inst0).decode()
            assert f'circuit_open_total{{peer="{owner_addr}"}} 1.0' in text
            assert f'circuit_state{{peer="{owner_addr}"}} 2.0' in text
            assert "degraded_local_total 3.0" in text

            # health reports the open circuit, bounded
            hc = inst0.health_check()
            assert hc.status == "unhealthy"
            assert "circuit open" in hc.message

            # (c) revive the peer: clear faults, shrink the cooldown so the
            # next call is the half-open probe (the breaker reads its
            # thresholds live), and watch normal forwarding return
            faults.clear()
            inst0.conf.behaviors.circuit_open_s = 0.05
            time.sleep(0.1)
            r = inst0.get_rate_limits([_rl(key)])[0]
            assert r.error == "", r.error
            assert r.metadata["owner"] == owner_addr
            assert "degraded" not in r.metadata
            assert peer.circuit.state == CIRCUIT_CLOSED
            text = c.instances[0].metrics.render(inst0).decode()
            assert f'circuit_state{{peer="{owner_addr}"}} 0.0' in text
            # still exactly one open transition: recovery was the probe
            assert f'circuit_open_total{{peer="{owner_addr}"}} 1.0' in text
        finally:
            faults.clear()
            c.stop()

    def test_group_forward_degrades_in_one_apply(self):
        """A multi-request same-owner group degrades as ONE local owner
        batch (order preserved), not request-by-request."""
        c = LocalCluster().start(2)
        try:
            inst0 = c.instances[0].instance
            b = inst0.conf.behaviors
            b.circuit_threshold = 1
            b.circuit_open_s = 5.0
            b.degraded_local = True
            owner_addr = c.instances[1].address
            key = _key_owned_by(inst0, owner_addr, prefix="grp")
            faults.install(f"peer={owner_addr};action=error")
            # trip the breaker (threshold 1: first failure opens it)
            r = inst0.get_rate_limits([_rl(key)])[0]
            assert "injected" in r.error
            # a same-key group rides one degraded owner-batch: strictly
            # decreasing remaining proves single-apply ordering
            rs = inst0.get_rate_limits([_rl(key, limit=10) for _ in range(4)])
            assert [r.remaining for r in rs] == [9, 8, 7, 6]
            assert all(r.metadata.get("degraded") == "true" for r in rs)
        finally:
            faults.clear()
            c.stop()


class TestChaosRandomized:
    def test_breaker_invariants_hold_for_any_seed(self):
        """Randomized drill (`make chaos`): the seed varies the threshold,
        the fault verb, and the extra-failure count; the invariants may
        not. Reproduce any failure with GUBER_CHAOS_SEED=<seed> make chaos."""
        seed = int(os.environ.get("GUBER_CHAOS_SEED", "0") or "0")
        rng = random.Random(seed)
        threshold = rng.randint(1, 4)
        verb = rng.choice(["error", "timeout", "drop"])
        extra = rng.randint(0, 2)
        print(f"chaos seed: {seed} (threshold={threshold} verb={verb} "
              f"extra={extra})")
        c = LocalCluster().start(2)
        try:
            inst0 = c.instances[0].instance
            b = inst0.conf.behaviors
            b.circuit_threshold = threshold
            b.circuit_open_s = 5.0
            b.degraded_local = True
            owner_addr = c.instances[1].address
            key = _key_owned_by(inst0, owner_addr, prefix=f"cs{seed}")
            peer = inst0.get_peer(f"test_{key}")
            faults.install(f"peer={owner_addr};action={verb}")
            # invariant 1: the breaker opens after EXACTLY threshold
            # consecutive transport failures, whatever the failure verb
            for i in range(threshold):
                assert peer.circuit.state == CIRCUIT_CLOSED, i
                r = inst0.get_rate_limits([_rl(key)])[0]
                assert "injected" in r.error, (i, r.error)
            assert peer.circuit.state == CIRCUIT_OPEN
            # invariant 2: open means degraded-local, marked, and fast
            for _ in range(1 + extra):
                t0 = time.monotonic()
                r = inst0.get_rate_limits([_rl(key)])[0]
                assert r.metadata.get("degraded") == "true"
                assert time.monotonic() - t0 < 0.05
            # invariant 3: revival closes the circuit via the probe
            faults.clear()
            b.circuit_open_s = 0.05
            time.sleep(0.1)
            r = inst0.get_rate_limits([_rl(key)])[0]
            assert r.error == "" and "degraded" not in r.metadata
            assert peer.circuit.state == CIRCUIT_CLOSED
        finally:
            faults.clear()
            c.stop()


@pytest.fixture(scope="module")
def duo():
    c = LocalCluster().start(2)
    yield c
    c.stop()


class TestPeerClientPaths:
    """Transport-path coverage for PeerClient: peerlink->gRPC fallback,
    timeout surfacing without resend, error-history TTL, shutdown sweep."""

    def test_peerlink_error_falls_back_to_grpc(self, duo):
        from gubernator_tpu.cluster.harness import wire_peerlink

        links = wire_peerlink(duo)
        assert links, "no peerlink offset bound"
        ci0, ci1 = duo.instances
        pc = PeerClient(ci0.instance.conf.behaviors,
                        PeerInfo(address=ci1.address))
        try:
            r = pc.get_peer_rate_limits([_rl("plfb_warm", limit=9)])[0]
            assert r.error == "" and pc._link is not None  # rides the link
            # counters start at install time: the next link call is call 1
            faults.install(f"peer={ci1.address};transport=peerlink;"
                           "calls=1;action=error")
            r = pc.get_peer_rate_limits([_rl("plfb_warm", limit=9)])[0]
            assert r.error == ""  # served over gRPC
            assert r.remaining == 7  # applied exactly once, same bucket
            assert pc._link is None  # broken link dropped + backed off
            assert any("peerlink" in e for e in pc.get_last_err())
            # the call SUCCEEDED via gRPC: a dead link port alone must
            # never accumulate toward opening the peer's circuit
            assert pc.circuit.state == CIRCUIT_CLOSED
            assert pc.circuit._failures == 0
        finally:
            faults.clear()
            pc.shutdown(timeout_s=2)
            for svc in links:
                svc.close()
            for ci in duo.instances:
                ci.instance.conf.behaviors.peer_link_offset = 0

    def test_peerlink_timeout_surfaces_without_resend(self, duo):
        from gubernator_tpu.cluster.harness import wire_peerlink
        from gubernator_tpu.service.peerlink import PeerLinkTimeout

        links = wire_peerlink(duo)
        assert links
        ci0, ci1 = duo.instances
        pc = PeerClient(ci0.instance.conf.behaviors,
                        PeerInfo(address=ci1.address))
        try:
            faults.install(f"peer={ci1.address};transport=peerlink;"
                           "calls=1;action=timeout")
            with pytest.raises(PeerLinkTimeout):
                pc.get_peer_rate_limits([_rl("plto", limit=7)])
            assert pc.circuit._failures == 1  # the breaker was charged
            assert pc._link is not None  # a timeout must NOT drop the link
            faults.clear()
            r = pc.get_peer_rate_limits([_rl("plto", limit=7)])[0]
            # remaining 6 proves the timed-out frame was never re-sent
            # over gRPC (a resend would have burned a second hit)
            assert r.error == "" and r.remaining == 6
            assert pc.circuit._failures == 0  # success reset the count
        finally:
            faults.clear()
            pc.shutdown(timeout_s=2)
            for svc in links:
                svc.close()
            for ci in duo.instances:
                ci.instance.conf.behaviors.peer_link_offset = 0

    def test_get_last_err_ttl_expiry(self, monkeypatch):
        monkeypatch.setattr(PeerClient, "ERR_TTL_MS", 30)
        pc = PeerClient(_behaviors(), PeerInfo(address="127.0.0.1:1"))
        pc._record_err("transient boom")
        assert any("transient boom" in e for e in pc.get_last_err())
        time.sleep(0.06)
        assert pc.get_last_err() == []  # expired, health no longer poisoned

    def test_shutdown_sweep_fails_queued_futures(self):
        """Requests the worker never reached must fail loudly with the
        clean not-ready signal, not sit orphaned until the batch timeout."""
        pc = PeerClient(_behaviors(), PeerInfo(address="127.0.0.1:9"))
        futs = [Future() for _ in range(3)]
        for fut in futs:  # queued, but no worker thread ever started
            pc._queue.put((_rl("orphan"), fut, None))
        pc.shutdown(timeout_s=0.1)
        for fut in futs:
            with pytest.raises(PeerNotReadyError):
                fut.result(timeout=1)


class TestLinkRetryKnob:
    def test_retry_delay_is_configurable_and_jittered(self):
        conf = _behaviors()
        conf.link_retry_s = 2.0
        pc = PeerClient(conf, PeerInfo(address="127.0.0.1:1"))
        delays = {pc._link_retry_delay() for _ in range(32)}
        assert all(1.0 <= d <= 3.0 for d in delays)  # base ±50%
        assert len(delays) > 1  # jittered, not a fleet-wide metronome

    def test_failed_connect_backs_off_by_knob(self):
        conf = _behaviors()
        conf.peer_link_offset = 1  # nothing listens there
        conf.link_retry_s = 0.01
        pc = PeerClient(conf, PeerInfo(address="127.0.0.1:9"))
        t0 = time.monotonic()
        assert pc._peer_link() is None
        assert pc._link_retry_at - t0 < 0.2  # seconds-scale, not LINK_RETRY_S

    def test_lost_install_race_never_returns_dead_link(self, monkeypatch):
        """The race tail: a loser thread must hand back None (gRPC
        fallback) when the winner's link already died, never the corpse."""
        import gubernator_tpu.service.peerlink as pl

        conf = _behaviors()
        conf.peer_link_offset = 1000
        pc = PeerClient(conf, PeerInfo(address="127.0.0.1:2345"))

        class FakeLink:
            _closed = False

            def close(self):
                self._closed = True

        dead = FakeLink()
        dead._closed = True

        def fake_ctor(addr, fault_key="", recorder=None):
            # interleave: another thread wins the install race with a link
            # that dies immediately after
            pc._link = dead
            return FakeLink()

        monkeypatch.setattr(pl, "PeerLinkClient", fake_ctor)
        assert pc._peer_link() is None


class TestForwardRepickBackoff:
    def test_repick_loop_backs_off_and_respects_deadline(self, duo,
                                                         monkeypatch):
        inst0 = duo.instances[0].instance
        owner_addr = duo.instances[1].address
        key = _key_owned_by(inst0, owner_addr, prefix="rp")
        peer = inst0.get_peer(f"test_{key}")
        calls = []

        def not_ready(req, trace_span=None, deadline=None):
            calls.append(time.monotonic())
            raise PeerNotReadyError(peer.info.address)

        monkeypatch.setattr(peer, "get_peer_rate_limit", not_ready)
        monkeypatch.setattr(inst0.conf.behaviors, "batch_timeout_s", 0.25)
        t0 = time.monotonic()
        resp = inst0._forward(_rl(key), f"test_{key}")
        dt = time.monotonic() - t0
        assert "not connected" in resp.error
        assert len(calls) == 6  # full retry budget inside the deadline
        assert dt >= 0.01, "re-picks spun hot with no backoff"
        assert dt <= 0.6, "re-pick loop outlived the client timeout"

    def test_repick_deadline_cuts_retries_short(self, duo, monkeypatch):
        inst0 = duo.instances[0].instance
        owner_addr = duo.instances[1].address
        key = _key_owned_by(inst0, owner_addr, prefix="rpd")
        peer = inst0.get_peer(f"test_{key}")
        calls = []

        def slow_not_ready(req, trace_span=None, deadline=None):
            calls.append(1)
            time.sleep(0.03)
            raise PeerNotReadyError(peer.info.address)

        monkeypatch.setattr(peer, "get_peer_rate_limit", slow_not_ready)
        monkeypatch.setattr(inst0.conf.behaviors, "batch_timeout_s", 0.05)
        t0 = time.monotonic()
        resp = inst0._forward(_rl(key), f"test_{key}")
        dt = time.monotonic() - t0
        assert resp.error != ""
        assert len(calls) < 6  # the deadline, not the count, ended the loop
        assert dt < 0.3


class TestHealthMessageBound:
    def test_sustained_failure_stays_bounded_with_counts(self, duo):
        from gubernator_tpu.utils.lru import LRUCache

        inst0 = duo.instances[0].instance
        owner_addr = duo.instances[1].address
        peer = inst0.get_peer(
            f"test_{_key_owned_by(inst0, owner_addr, prefix='hb')}")
        try:
            for i in range(150):  # sustained distinct failures
                peer._record_err(f"sustained failure {i} " + "x" * 120)
            for _ in range(inst0.conf.behaviors.circuit_threshold):
                peer.circuit.record_failure()
            hc = inst0.health_check()
            assert hc.status == "unhealthy"
            # bounded: counts + samples, never the multi-KB raw join
            # (150 errors x ~140 chars would exceed 20 KB unbounded)
            assert len(hc.message) <= inst0.HEALTH_MESSAGE_CHARS + 64
            assert "100 errors" in hc.message  # per-peer LRU retention cap
            assert "circuit open" in hc.message
            assert "sustained failure" in hc.message  # a sample survives
        finally:
            # restore the shared cluster's health for later tests
            peer.last_errs = LRUCache(max_size=100)
            peer.circuit.record_success()
        assert inst0.health_check().status == "healthy"
