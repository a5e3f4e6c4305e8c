"""Peer RPC client with micro-batching.

Mirrors the reference's per-peer request pipeline (reference:
peer_client.go:47-383): a lazy gRPC connection, a per-peer queue whose
batches flush at `batch_limit` (1000) items or `batch_wait` (500 µs) after
the first enqueue — the thundering-herd defense the reference documents
(architecture.md:19-25) — plus a NO_BATCHING bypass, graceful shutdown that
drains in-flight requests, and an LRU of recent errors feeding HealthCheck
(reference: peer_client.go:184-213).
"""

from __future__ import annotations

import queue
import random
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import List, Optional, Sequence

import grpc

from gubernator_tpu.obs import witness
from gubernator_tpu.service import faults
from gubernator_tpu.service import deadline as deadline_mod
from gubernator_tpu.service.config import BehaviorConfig
from gubernator_tpu.service.convert import req_to_pb, resp_from_pb
from gubernator_tpu.service.grpc_api import CHANNEL_OPTIONS, PeersV1Stub
from gubernator_tpu.service.pb import peers_pb2 as peers_pb
from gubernator_tpu.types import Behavior, PeerInfo, RateLimitReq, RateLimitResp, has_behavior
from gubernator_tpu.utils.lru import CacheItem, LRUCache


class PeerNotReadyError(RuntimeError):
    """Raised when the peer is shutting down; the router retries another
    owner pick (reference: peer_client.go:359-383 IsNotReady)."""


class CircuitOpenError(PeerNotReadyError):
    """The peer's circuit breaker is open: recent transport failures
    crossed the threshold, so calls fail fast PRE-send. A subclass of
    PeerNotReadyError because the guarantees are identical — nothing was
    sent, so the router may re-pick, degrade locally, or refund hits
    without double-count risk."""


CIRCUIT_CLOSED, CIRCUIT_HALF_OPEN, CIRCUIT_OPEN = 0, 1, 2
_CIRCUIT_NAMES = {CIRCUIT_CLOSED: "closed", CIRCUIT_HALF_OPEN: "half-open",
                  CIRCUIT_OPEN: "open"}


class CircuitBreaker:
    """Per-peer circuit shared by BOTH transports (peerlink and gRPC feed
    one breaker): closed -> open after `circuit_threshold` consecutive
    transport failures -> half-open single-probe after `circuit_open_s`
    -> closed again on probe success. A dead peer then costs the fleet one
    probe timeout per cooldown, not one `batch_timeout_s` stall per batch.

    Thresholds are read from the live BehaviorConfig on every decision, so
    tests (and future hot-reload) can tune a running breaker.
    `circuit_threshold <= 0` disables the breaker entirely — every call
    behaves exactly as before this layer existed."""

    def __init__(self, conf: BehaviorConfig, address: str, metrics=None,
                 recorder=None):
        self.conf = conf
        self.address = address
        self.metrics = metrics
        self.recorder = recorder  # flight recorder (obs/events.py) or None
        self._lock = witness.make_lock("peer.circuit")
        self._failures = 0
        self._state = CIRCUIT_CLOSED
        self._opened_at = 0.0
        self._probing = False
        self.opened_total = 0  # lifetime open transitions (health/debug)

    def _record(self, kind: str, **fields) -> None:
        if self.recorder is not None:
            self.recorder.emit(kind, peer=self.address, **fields)

    @property
    def enabled(self) -> bool:
        return getattr(self.conf, "circuit_threshold", 0) > 0

    @property
    def state(self) -> int:
        return self._state

    @property
    def state_name(self) -> str:
        return _CIRCUIT_NAMES[self._state]

    def _open_s(self) -> float:
        return max(getattr(self.conf, "circuit_open_s", 5.0), 0.001)

    def blocked(self) -> bool:
        """Read-only fast-fail check: True only while OPEN inside the
        cooldown. Does NOT consume the half-open probe slot, so callers on
        the batched path can fail fast without starving the probe."""
        return (self._state == CIRCUIT_OPEN
                and time.monotonic() - self._opened_at < self._open_s())

    def allow(self) -> bool:
        """Admission check at the transport choke point. Exactly one
        caller at a time gets through an open-but-cooled-down circuit: the
        half-open probe whose outcome decides reopen vs close."""
        if not self.enabled:
            return True
        with self._lock:
            if self._state == CIRCUIT_CLOSED:
                return True
            if self._state == CIRCUIT_OPEN:
                if time.monotonic() - self._opened_at < self._open_s():
                    return False
                self._state = CIRCUIT_HALF_OPEN
                self._probing = True
                self._record("circuit.half_open")
                return True
            if self._probing:  # HALF_OPEN with the probe already in flight
                return False
            self._probing = True
            return True

    def record_success(self) -> None:
        with self._lock:
            closed = self._state != CIRCUIT_CLOSED
            self._failures = 0
            self._probing = False
            self._state = CIRCUIT_CLOSED
        if closed:
            self._record("circuit.close")

    def record_failure(self) -> None:
        if not self.enabled:
            return
        opened = False
        probe_failed = False
        failures = 0
        with self._lock:
            self._failures += 1
            failures = self._failures
            if self._state == CIRCUIT_HALF_OPEN:
                # the probe failed: reopen for another cooldown
                self._state = CIRCUIT_OPEN
                self._opened_at = time.monotonic()
                self._probing = False
                self.opened_total += 1
                opened = probe_failed = True
            elif (self._state == CIRCUIT_CLOSED
                  and self._failures >= self.conf.circuit_threshold):
                self._state = CIRCUIT_OPEN
                self._opened_at = time.monotonic()
                self.opened_total += 1
                opened = True
        if opened:
            self._record("circuit.open", failures=failures,
                         probe_failed=probe_failed,
                         cooldown_s=self._open_s())
            if self.metrics is not None:
                try:
                    self.metrics.circuit_open.labels(peer=self.address).inc()
                except Exception:  # noqa: BLE001 — metrics must not break calls
                    pass


class PeerClient:
    """One remote peer: connection + batching queue + error history."""

    ERR_TTL_MS = 5 * 60 * 1000  # last-error retention (reference: peer_client.go:53)

    def __init__(self, behaviors: BehaviorConfig, info: PeerInfo,
                 metrics=None, recorder=None):
        self.conf = behaviors
        self.info = info
        self.metrics = metrics
        # one breaker for BOTH transports: peerlink timeouts and gRPC
        # failures feed the same consecutive-failure count
        self.circuit = CircuitBreaker(behaviors, info.address, metrics,
                                      recorder=recorder)
        self._stub: Optional[PeersV1Stub] = None
        self._channel: Optional[grpc.Channel] = None
        self._queue: "queue.Queue" = queue.Queue()
        self._closing = False
        self._lock = witness.make_lock("peer.client")
        self._thread: Optional[threading.Thread] = None
        self.last_errs = LRUCache(max_size=100)
        # native peer transport (service/peerlink.py); None until connected,
        # False while in gRPC-fallback backoff
        self._link = None
        self._link_retry_at = 0.0
        # set by the owning Instance to LeaseManager.want: lets the batch
        # worker attach a hot-key lease ask to micro-batched flushes, the
        # path where the Instance is not on the call stack
        self.lease_advisor = None

    # ------------------------------------------------------- native link

    LINK_RETRY_S = 30.0  # default when the BehaviorConfig predates the knob

    def _link_retry_delay(self) -> float:
        """gRPC-fallback backoff before the next link attempt
        (GUBER_LINK_RETRY_S), jittered ±50% so a fleet that lost a peer
        does not re-dial its revived link port in one synchronized wave."""
        base = getattr(self.conf, "link_retry_s", 0.0) or self.LINK_RETRY_S
        return base * (0.5 + random.random())

    def _peer_link(self):
        """The native link to this peer, or None (disabled / unreachable —
        callers fall back to gRPC; reference peers in a mixed fleet never
        answer the link port, so the fallback IS the compatibility path)."""
        offset = getattr(self.conf, "peer_link_offset", 0)
        if offset <= 0 or self._closing:
            return None
        link = self._link
        if link is not None:
            if not link._closed:
                return link
            # the reader died since the last call (peer restarted, network
            # blip): retire the dead client and back off to gRPC
            self._drop_link()
            return None
        if time.monotonic() < self._link_retry_at:
            return None
        from gubernator_tpu.service.peerlink import (
            PeerLinkClient,
            PeerLinkError,
        )

        host, _, port = self.info.address.rpartition(":")
        try:
            link = PeerLinkClient(f"{host}:{int(port) + offset}",
                                  fault_key=self.info.address,
                                  recorder=self.circuit.recorder)
        except (OSError, ValueError, PeerLinkError):
            self._link_retry_at = time.monotonic() + self._link_retry_delay()
            return None
        with self._lock:
            if self._link is None and not self._closing:
                self._link = link
                return link
            winner = self._link
        link.close()  # lost the race or closing
        # race tail: the winner may itself have died or been dropped since
        # the install — hand back only a verified-live link, never a
        # just-closed one (callers would burn a call on a dead socket and
        # charge the breaker for it)
        if winner is not None and not winner._closed:
            return winner
        return None

    def link_wire_version(self) -> int:
        """Negotiated wire contract of the live link (0 = no live link).
        Exposed as peerlink_wire_version{peer} at metrics exposition."""
        link = self._link
        if link is None or link is False or link._closed:
            return 0
        return getattr(link, "wire_version", 1)

    def _drop_link(self) -> None:
        with self._lock:
            link, self._link = self._link, None
        self._link_retry_at = time.monotonic() + self._link_retry_delay()
        if link is not None:
            link.close()

    # ------------------------------------------------------------ lifecycle

    def _connect(self) -> PeersV1Stub:
        """Lazy connect (reference: peer_client.go:81-125)."""
        with self._lock:
            if self._stub is None:
                if self._closing:
                    # refuse NEW connections once closing — but an existing
                    # stub keeps serving so shutdown can drain the queue
                    # (channel closes only after the worker joins). Callers
                    # racing shutdown get the clean not-ready signal the
                    # reference returns from its status check
                    # (reference: peer_client.go:127-133), never a raw
                    # closed-channel error.
                    raise PeerNotReadyError(self.info.address)
                # bounded reconnect backoff: a peer restarting on the same
                # address must be forwardable-to within ~1 s, not after
                # grpc's default multi-second exponential backoff
                self._channel = grpc.insecure_channel(
                    self.info.address, options=CHANNEL_OPTIONS)
                # the fault-injection choke point for the gRPC transport:
                # a no-op passthrough unless a plan is armed (faults.py)
                self._stub = faults.wrap_stub(
                    PeersV1Stub(self._channel), self.info.address)
                self._thread = threading.Thread(
                    target=self._run, name=f"peer-batch-{self.info.address}",
                    daemon=True,
                )
                self._thread.start()
            return self._stub

    def shutdown(self, timeout_s: Optional[float] = None) -> None:
        """Stop accepting requests and drain the queue
        (reference: peer_client.go:322-356).

        Enqueues are atomic with the closing check (get_peer_rate_limit holds
        _lock for check+put), so everything in the queue precedes the
        sentinel and the worker drains it all; the sweep below only fires
        when the worker died or outlived the join timeout."""
        with self._lock:
            if self._closing:
                return
            self._closing = True
        self._queue.put(None)  # wake the batch loop
        if self._thread is not None:
            self._thread.join(timeout=timeout_s or self.conf.batch_timeout_s)
        while True:  # fail anything the worker never got to, loudly
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is None:
                continue
            fut = item[1]
            if not fut.done():
                fut.set_exception(PeerNotReadyError(self.info.address))
        if self._link is not None:
            self._link.close()
        if self._channel is not None:
            self._channel.close()

    # ------------------------------------------------------------------ API

    def get_peer_rate_limit(self, req: RateLimitReq, trace_span=None,
                            deadline=None) -> RateLimitResp:
        """Forward one request to this peer, batching unless NO_BATCHING
        (reference: peer_client.go:127-140).

        `deadline` (service/deadline.py, defaulting to the context's
        active budget) bounds the wait for the batched response: an
        already-expired budget sheds pre-send, and a caller never waits
        past its own remaining time for a batch flush it cannot use."""
        if deadline is None:
            deadline = deadline_mod.current()
        if has_behavior(req.behavior, Behavior.NO_BATCHING):
            resps = self.get_peer_rate_limits([req], trace_span=trace_span,
                                              deadline=deadline)
            return resps[0]
        if deadline is not None and deadline.expired():
            self._count_expired(deadline_mod.STAGE_FORWARD)
            raise deadline_mod.DeadlineExceededError(
                f"budget expired before forwarding to {self.info.address}")
        if self.circuit.blocked():
            # fail in microseconds instead of paying the batch window +
            # timeout against a peer known-dead; blocked() (not allow())
            # so this fast path can never consume the half-open probe slot
            raise CircuitOpenError(self.info.address)
        self._connect()
        fut: "Future[RateLimitResp]" = Future()
        # check+enqueue atomically vs shutdown's closing flag: a request in
        # the queue is then always AHEAD of the shutdown sentinel, so the
        # worker drains it; a request refused here fails fast instead of
        # sitting in a queue nobody reads until the batch timeout
        with self._lock:
            if self._closing:
                raise PeerNotReadyError(self.info.address)
            self._queue.put((req, fut, trace_span, deadline))
        timeout_s = self.conf.batch_timeout_s
        if deadline is not None:
            # never below the hop floor: the batch worker was granted at
            # least that much, so cutting the wait shorter would abandon
            # a response already being earned
            timeout_s = min(timeout_s, max(
                deadline.remaining_s(),
                self._min_hop_budget_ms() / 1e3))
        try:
            return fut.result(timeout=timeout_s)
        except _FutureTimeout:
            if deadline is not None and deadline.expired():
                # the budget, not the peer, ran out — the batch may still
                # be applying at the peer (delivery-uncertain, same
                # no-resend rule as a transport timeout), but the caller
                # sheds NOW instead of stalling out the full batch window
                self._count_expired(deadline_mod.STAGE_FORWARD)
                self._record_err("deadline expired awaiting batch response")
                raise deadline_mod.DeadlineExceededError(
                    f"budget expired awaiting batched response from "
                    f"{self.info.address}") from None
            self._record_err("batch response timeout")
            raise

    def get_peer_rate_limits(
        self, reqs: Sequence[RateLimitReq], wait_for_ready: bool = False,
        trace_span=None, deadline=None, lease_want: Optional[str] = None,
    ) -> List[RateLimitResp]:
        """One peer call carrying the whole batch: the native link when the
        peer answers it (~4-5x cheaper than Python gRPC), else gRPC.

        `wait_for_ready=True` rides out a cold/reconnecting channel up to
        the batch timeout instead of failing fast — for callers whose
        failure handling DROPS the payload (multi-region replication:
        delivery-uncertain errors cannot be retried without double
        counting). Routed request traffic keeps fail-fast so owner-down
        fallbacks stay prompt.

        `trace_span` (obs/trace.py) propagates W3C trace context to the
        owner: gRPC carries it as `traceparent` metadata, peerlink as a
        reserved carrier item in a TRACED frame — the owner's spans then
        share this request's trace id.

        `deadline` (service/deadline.py, defaulting to the context's
        active budget) turns the fixed `batch_timeout_s` RPC timeout into
        `min(remaining budget, batch_timeout)` floored at
        GUBER_MIN_HOP_BUDGET_MS, and propagates the granted hop budget to
        the owner — `guber-deadline-ms` metadata over gRPC, a reserved
        carrier item behind METHOD_DEADLINE over peerlink — so every hop
        works against a strictly smaller budget than its caller's.

        `lease_want` (service/leases.py) names a hash key this caller
        wants a hot-key lease for. Over peerlink it rides a METHOD_LEASE
        carrier and the owner's grant comes back in the carrier's own
        response lane, re-materialized here as the same
        `guber-lease` response metadata the gRPC wire carries natively —
        Instance's install path never sees which wire answered."""
        if deadline is None:
            deadline = deadline_mod.current()
        timeout_s = self.conf.batch_timeout_s
        hop_ms = None
        if deadline is not None:
            remaining = deadline.remaining_ms()
            if remaining <= 0:
                self._count_expired(deadline_mod.STAGE_FORWARD)
                raise deadline_mod.DeadlineExceededError(
                    f"budget expired before forwarding to "
                    f"{self.info.address}")
            hop_ms = deadline_mod.hop_budget_ms(
                remaining, self.conf.batch_timeout_s,
                self._min_hop_budget_ms())
            timeout_s = hop_ms / 1e3
        if not self.circuit.allow():
            # one gate for BOTH transports: the whole batch fails fast
            # pre-send (one CircuitOpenError per batch, not one timeout
            # per request) until the cooldown admits a half-open probe
            raise CircuitOpenError(self.info.address)
        link = self._peer_link()
        if link is not None:
            from gubernator_tpu.service.peerlink import (
                METHOD_DEADLINE,
                METHOD_GET_PEER_RATE_LIMITS,
                METHOD_LEASE,
                MAX_FRAME_ITEMS,
                METHOD_TRACED,
                PeerLinkError,
                PeerLinkTimeout,
                PeerLinkUnencodable,
                deadline_carrier,
                lease_carrier,
                trace_carrier,
            )

            flags = 0
            carriers = []
            if trace_span is not None:
                flags |= METHOD_TRACED
                carriers.append(trace_carrier(trace_span))
            if hop_ms is not None:
                flags |= METHOD_DEADLINE
                carriers.append(deadline_carrier(hop_ms))
            lease_lane = -1
            if lease_want:
                flags |= METHOD_LEASE
                lease_lane = len(carriers)
                carriers.append(lease_carrier(lease_want))
            try:
                if carriers and \
                        len(reqs) + len(carriers) <= MAX_FRAME_ITEMS:
                    resps = link.call(
                        METHOD_GET_PEER_RATE_LIMITS | flags,
                        carriers + list(reqs), timeout_s)
                    self.circuit.record_success()
                    body = resps[len(carriers):]
                    if lease_lane >= 0:
                        # grant encoding (peerlink._fill_lease_lane):
                        # status = frame-relative index of the granted
                        # item (-1 = no grant), limit = budget,
                        # remaining = ttl_ms, reset = seq
                        lane = resps[lease_lane]
                        gi = int(lane.status)
                        if 0 <= gi < len(body) and lane.limit > 0:
                            from gubernator_tpu.service.leases import (
                                GRANT_METADATA_KEY)

                            body[gi].metadata[GRANT_METADATA_KEY] = (
                                f"{lane.limit}:{lane.remaining}:"
                                f"{lane.reset_time}")
                    # the carriers' placeholder lanes are dropped
                    return body
                resps = link.call(METHOD_GET_PEER_RATE_LIMITS, list(reqs),
                                  timeout_s)
                self.circuit.record_success()
                return resps
            except PeerLinkUnencodable:
                pass  # THIS request can't ride the wire format; the link
                # is healthy — route just this call over gRPC below
            except PeerLinkTimeout as e:
                # the frame may already be applying at the peer: re-sending
                # over gRPC could double-count hits (the invariant
                # Instance._forward_group documents) — surface the error,
                # exactly as a gRPC deadline would
                self._record_err(f"peerlink: {e}")
                self.circuit.record_failure()
                raise
            except PeerLinkError as e:
                # broken link: back off to gRPC for a while (the peer may
                # have restarted without the link, or be a reference node).
                # NOT a breaker failure by itself — the gRPC attempt below
                # decides this call's outcome, and a healthy-gRPC peer with
                # a dead link port must not accumulate toward open.
                self._record_err(f"peerlink: {e}")
                self._drop_link()
        stub = self._connect()
        msg = peers_pb.GetPeerRateLimitsReq(requests=[req_to_pb(r) for r in reqs])
        metadata = []
        if trace_span is not None:
            from gubernator_tpu.obs.trace import format_traceparent

            metadata.append(("traceparent", format_traceparent(trace_span)))
        if hop_ms is not None:
            # the DECREMENTED budget: strictly smaller than the caller's
            # own capture, because remaining_ms() already paid the time
            # spent routing/queueing on this node
            metadata.append((deadline_mod.METADATA_KEY, f"{hop_ms:.3f}"))
        try:
            out = stub.GetPeerRateLimits(
                msg, timeout=timeout_s,
                wait_for_ready=wait_for_ready,
                metadata=tuple(metadata) or None)
        except grpc.RpcError as e:
            if self._closing and e.code() == grpc.StatusCode.CANCELLED:
                # shutdown() closed the channel under this in-flight call:
                # a membership change removed the peer while the batch was
                # on the wire. Locally cancelled, not a peer failure — no
                # breaker charge, and the not-ready signal sends the caller
                # back through GetPeer() for a re-pick under the new ring.
                # Delivery is uncertain (the old owner may have applied and
                # redirected the hits before the cancel landed), so the
                # retry can over-count this one batch — the conservative
                # direction; it can never mint budget.
                raise PeerNotReadyError(self.info.address) from e
            self._record_err(str(e.code()))
            if e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                # an admission shed: the peer is ALIVE and answering fast
                # — charging the breaker would convert its overload into
                # an open circuit (and, degraded-local, split-brain), the
                # opposite of backing off
                self.circuit.record_success()
            else:
                self.circuit.record_failure()
            raise
        except (faults.FaultError, faults.FaultTimeout) as e:
            # injected transport failures charge the breaker exactly as
            # their real counterparts would
            self._record_err(f"fault: {e}")
            self.circuit.record_failure()
            raise
        except ValueError as e:
            # grpc raises bare ValueError("Cannot invoke RPC on closed
            # channel!") when shutdown() closed the channel mid-call
            raise PeerNotReadyError(self.info.address) from e
        self.circuit.record_success()
        return [resp_from_pb(m) for m in out.rate_limits]

    def update_peer_globals(self, updates) -> None:
        """Push a batch of UpdatePeerGlobal messages (reference:
        peer_client.go:142-160)."""
        if not self.circuit.allow():
            # GLOBAL broadcasts to a dead peer fail fast too; the manager
            # counts them as broadcast errors and the next cooldown's
            # probe re-opens the path
            raise CircuitOpenError(self.info.address)
        stub = self._connect()
        msg = peers_pb.UpdatePeerGlobalsReq(globals=updates)
        try:
            stub.UpdatePeerGlobals(msg, timeout=self.conf.global_timeout_s)
        except grpc.RpcError as e:
            self._record_err(str(e.code()))
            self.circuit.record_failure()
            raise
        except (faults.FaultError, faults.FaultTimeout) as e:
            self._record_err(f"fault: {e}")
            self.circuit.record_failure()
            raise
        except ValueError as e:
            raise PeerNotReadyError(self.info.address) from e
        self.circuit.record_success()

    def reshard_call(self, payload: bytes, timeout_s: float = 5.0) -> bytes:
        """One reshard-plane message over the raw Debug bytes RPC
        (service/reshard.py). Deliberately outside the serving circuit
        breaker: a handoff probe to a peer whose serving path is shedding
        is exactly when moving keys matters, and the reshard protocol has
        its own lease-TTL fail-close."""
        from gubernator_tpu.service.grpc_api import dial_v1

        return dial_v1(self.info.address).Debug(payload, timeout=timeout_s)

    def get_last_err(self) -> List[str]:
        """Recent errors for HealthCheck (reference: peer_client.go:198-213)."""
        now = int(time.time() * 1000)
        return [
            item.key
            for item in self.last_errs.each()
            if item.expire_at == 0 or item.expire_at > now
        ]

    # ------------------------------------------------------------ internals

    def _record_err(self, err: str) -> None:
        msg = f"{self.info.address}: {err}"
        self.last_errs.add(
            CacheItem(key=msg, expire_at=int(time.time() * 1000) + self.ERR_TTL_MS)
        )

    def _min_hop_budget_ms(self) -> float:
        return getattr(self.conf, "min_hop_budget_ms", 5.0)

    def _count_expired(self, stage: str) -> None:
        if self.metrics is not None:
            try:
                self.metrics.deadline_expired.labels(stage=stage).inc()
            except Exception:  # noqa: BLE001 — metrics must not break calls
                pass

    def _run(self) -> None:
        """Batch loop: flush at batch_limit items or batch_wait after the
        first enqueue (reference: peer_client.go:243-283)."""
        while True:
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                if self._closing:
                    return
                continue
            if first is None:
                return
            batch = [first]
            deadline = time.monotonic() + self.conf.batch_wait_s
            while len(batch) < self.conf.batch_limit:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if item is None:
                    self._send_batch(batch)
                    return
                batch.append(item)
            self._send_batch(batch)

    def _send_batch(self, batch) -> None:
        """Send one batch, demuxing responses by index
        (reference: peer_client.go:287-319). One RPC carries one trace
        context: the first traced entry's (a merged batch IS one shared
        hop — co-batched traces share its owner-side spans).

        Entries whose deadline died waiting for the batch window are shed
        HERE, pre-send: their callers already stopped waiting, so carrying
        them would spend wire and owner work on answers nobody reads. The
        RPC runs under the WIDEST surviving budget — tighter co-batched
        callers stop waiting individually through their own result
        timeout, and failing the whole batch at the tightest budget would
        punish long-budget entries for their neighbors."""
        live = []
        dl = None
        for entry in batch:
            edl = entry[3]
            if edl is not None and edl.expired():
                fut = entry[1]
                if not fut.done():
                    fut.set_exception(deadline_mod.DeadlineExceededError(
                        "budget expired in the peer batch queue"))
                self._count_expired(deadline_mod.STAGE_BATCH)
                continue
            if edl is not None and (dl is None
                                    or edl.expires_at > dl.expires_at):
                dl = edl
            live.append(entry)
        if not live:
            return
        if any(e[3] is None for e in live):
            # an unbudgeted entry deserves the full batch timeout; the
            # budgeted co-riders still bound their own waits
            dl = None
        span = next((s for _, _, s, _ in live if s is not None), None)
        reqs = [req for req, _, _, _ in live]
        lease_want = None
        if self.lease_advisor is not None:
            try:
                lease_want = self.lease_advisor(reqs)
            except Exception:  # noqa: BLE001 — an ask is best-effort
                lease_want = None
        try:
            resps = self.get_peer_rate_limits(
                reqs, trace_span=span, deadline=dl,
                lease_want=lease_want)
            if len(resps) != len(live):
                raise RuntimeError(
                    f"server responded with incorrect rate limit list size: "
                    f"{len(resps)} != {len(live)}"
                )
            for (_, fut, _, _), resp in zip(live, resps):
                fut.set_result(resp)
        except Exception as e:  # noqa: BLE001 — every waiter must wake
            for _, fut, _, _ in live:
                if not fut.done():
                    fut.set_exception(e)
