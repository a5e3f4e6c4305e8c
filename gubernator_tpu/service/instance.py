"""Instance: the core request router.

The reference routes each request of a batch through a 1000-wide goroutine
fan-out, taking a global cache mutex per request (reference:
gubernator.go:110-224). Here routing is a partition pass: one walk over the
batch splits it into (a) locally-owned requests — applied to the TPU backend
as ONE batched kernel call, (b) per-peer forward lists riding the micro-batch
windows, (c) GLOBAL cache answers. The goroutine fan-out disappears into the
vectorized kernel.

Owner semantics, health checking, peer rebuild/drain on membership change,
and the GLOBAL/multi-region queues mirror the reference Instance
(gubernator.go:41-468).
"""

from __future__ import annotations

import dataclasses

import logging
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

from gubernator_tpu.obs import witness
from gubernator_tpu.cluster.pickers import (
    PickerEmptyError,
    RegionPicker,
    ReplicatedConsistentHashPicker,
)
from gubernator_tpu.obs import ledger as ledger_mod
from gubernator_tpu.obs import trace
from gubernator_tpu.obs.anomaly import AnomalyEngine
from gubernator_tpu.obs.events import FlightRecorder
from gubernator_tpu.obs.history import MetricsHistory
from gubernator_tpu.obs.keyspace import KeyspaceCartographer
from gubernator_tpu.obs.trace import Tracer
from gubernator_tpu.service import deadline as deadline_mod
from gubernator_tpu.service.autopilot import Autopilot
from gubernator_tpu.service.combiner import BackendCombiner
from gubernator_tpu.service.deadline import (
    AdmissionRejectedError,
    DeadlineExceededError,
)
from gubernator_tpu.service.config import BehaviorConfig, InstanceConfig
from gubernator_tpu.service.global_manager import GlobalManager
from gubernator_tpu.service.leases import LeaseManager
from gubernator_tpu.service.multiregion import MultiRegionManager
from gubernator_tpu.service.reshard import ReshardManager
from gubernator_tpu.service.peer_client import (
    CIRCUIT_CLOSED,
    CircuitOpenError,
    PeerClient,
    PeerNotReadyError,
)
from gubernator_tpu.types import (
    MAX_BATCH_SIZE,
    Behavior,
    HealthCheckResp,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
    Status,
    has_behavior,
    set_behavior,
    without_behavior,
)
from gubernator_tpu.utils.lru import CacheItem, LRUCache

log = logging.getLogger("gubernator_tpu.instance")


class ApiError(Exception):
    """Whole-call failure surfaced as a gRPC status (OUT_OF_RANGE for batch
    overflow, reference: gubernator.go:113-116)."""

    def __init__(self, code: str, message: str):
        super().__init__(message)
        self.code = code
        self.message = message


class _GlobalStatus:
    """Mutable non-owner copy of a GLOBAL key's last broadcast, supporting
    optimistic local deduction between broadcasts (stricter than the
    reference's frozen cached answer, gubernator.go:232-240)."""

    __slots__ = ("status", "limit", "remaining", "reset_time")

    def __init__(self, status: int, limit: int, remaining: int, reset_time: int):
        self.status = status
        self.limit = limit
        self.remaining = remaining
        self.reset_time = reset_time


class AdmissionController:
    """Load-shedding gate for one Instance (docs/OPERATIONS.md "Overload &
    deadlines"): weighs the node's pending work — combiner backlog +
    in-flight forwards + GLOBAL pipeline depth, the queues that grow
    without bound when offered load exceeds capacity — against
    GUBER_MAX_PENDING, and rejects new work FAST instead of letting it
    stall in queues whose wait already exceeds any useful deadline.

    Two pressure levels give the brownout order (cheapest work first):

    - BROWNOUT (>= 75% of max_pending): non-owner forwards and GLOBAL
      async broadcasts shed — the client can retry a forward against a
      healthier moment, and a dropped broadcast regenerates on the next
      applied GLOBAL hit; owner-authoritative decisions keep serving.
    - SATURATED (>= max_pending): everything sheds
      (`RESOURCE_EXHAUSTED` / HTTP 429 + Retry-After) — admitting more
      work can only push the whole queue past its deadlines.

    `max_pending <= 0` disables the controller entirely: every check is
    one attribute read, and serving is bit-identical to the pre-admission
    code. Thresholds read live from the BehaviorConfig, so tests and
    future hot-reload can tune a running node."""

    ADMIT, BROWNOUT, SATURATED = 0, 1, 2
    # fallback when the BehaviorConfig predates brownout_fraction; the
    # live knob is GUBER_BROWNOUT_FRACTION (brownout_fraction property)
    BROWNOUT_FRACTION = 0.75
    RETRY_AFTER_S = 1.0

    _LEVEL_NAMES = {0: "admit", 1: "brownout", 2: "saturated"}

    def __init__(self, instance: "Instance", metrics=None):
        self.instance = instance
        self.metrics = metrics
        self.stats = {"shed_forward": 0, "shed_broadcast": 0,
                      "shed_ingress": 0, "shed_peer": 0}
        # last level seen by level() — the brownout enter/exit edge the
        # flight recorder timestamps (racy reads lose nothing: a lost
        # edge re-fires on the next level() call)
        self._last_level = self.ADMIT

    @property
    def max_pending(self) -> int:
        return getattr(self.instance.conf.behaviors, "max_pending", 0)

    @property
    def brownout_fraction(self) -> float:
        """Live brownout threshold (GUBER_BROWNOUT_FRACTION): the
        fraction of max_pending past which non-owner forwards and
        GLOBAL broadcasts shed. Read per check so operators (and the
        autopilot) can tune a running node."""
        return getattr(self.instance.conf.behaviors, "brownout_fraction",
                       self.BROWNOUT_FRACTION)

    @property
    def enabled(self) -> bool:
        return self.max_pending > 0

    def pending(self) -> int:
        """The pending-work reading, from live counters the metric
        families already export (combiner backlog, forward pool,
        global_queue_depth)."""
        inst = self.instance
        n = inst.combiner.backlog + inst._forward_inflight  # noqa: SLF001
        gm = getattr(inst, "global_manager", None)
        if gm is not None:
            hits, bcast = gm.depths()
            n += hits + bcast
        return n

    def level(self) -> int:
        """Current pressure level; ADMIT when disabled."""
        cap = self.max_pending
        if cap <= 0:
            return self.ADMIT
        pending = self.pending()
        if pending >= cap:
            lvl = self.SATURATED
        elif pending >= cap * self.brownout_fraction:
            lvl = self.BROWNOUT
        else:
            lvl = self.ADMIT
        if lvl != self._last_level:
            prev, self._last_level = self._last_level, lvl
            rec = getattr(self.instance, "recorder", None)
            if rec is not None:
                rec.emit(f"admission.{self._LEVEL_NAMES[lvl]}",
                         prev=self._LEVEL_NAMES[prev], pending=pending,
                         max_pending=cap)
        return lvl

    def check_ingress(self, priority: str = "ingress") -> int:
        """The whole-call gate: raises RESOURCE_EXHAUSTED at SATURATED,
        else returns the level so the caller can apply per-class
        brownout shedding."""
        lvl = self.level()
        if lvl >= self.SATURATED:
            self.shed("saturated", priority)
            raise AdmissionRejectedError(
                f"RESOURCE_EXHAUSTED: node saturated "
                f"({self.pending()} pending >= max_pending "
                f"{self.max_pending}); shedding new work",
                retry_after_s=self.RETRY_AFTER_S)
        return lvl

    def shed_broadcast(self) -> bool:
        """GLOBAL broadcast gate (GlobalManager.queue_update): True =
        drop this broadcast — it is regenerated by the next applied
        GLOBAL hit once pressure clears, so it is the cheapest work on
        the node to not do."""
        if self.level() >= self.BROWNOUT:
            self.shed("brownout", "broadcast")
            return True
        return False

    def shed(self, reason: str, priority: str, n: int = 1) -> None:
        self.stats[f"shed_{priority}"] = \
            self.stats.get(f"shed_{priority}", 0) + n
        if self.metrics is not None:
            try:
                self.metrics.admission_shed.labels(
                    reason=reason, priority=priority).inc(n)
            except Exception:  # noqa: BLE001 — metrics must not break
                pass

    def shed_response(self, owner_addr: str) -> RateLimitResp:
        """The per-request brownout answer for a shed forward: an error
        the client can recognize and retry (HTTP clients see the same
        text; whole-call saturation instead maps to the RPC status)."""
        return RateLimitResp(
            error=f"RESOURCE_EXHAUSTED: admission shed "
                  f"(pending {self.pending()} of max_pending "
                  f"{self.max_pending}); retry later",
            metadata={"owner": owner_addr, "shed": "admission"})


class Instance:
    """One serving process (reference: gubernator.go:41-48)."""

    def __init__(self, conf: Optional[InstanceConfig] = None,
                 advertise_address: str = ""):
        conf = conf or InstanceConfig()
        conf.validate()
        self.conf = conf
        self.advertise_address = advertise_address
        self.data_center = conf.data_center

        if conf.backend is None:
            from gubernator_tpu.models.engine import Engine

            conf.backend = Engine()
        self.backend = conf.backend
        # continuous profiling plane (obs/profile.py): Engine and
        # ShardedEngine carry their own profiler; backends without one
        # (stubs) get an Instance-level fallback so the endpoints and
        # debug sections are wired on every deployment shape. conf.profile_enabled None
        # defers to GUBER_PROFILE; an explicit bool overrides the env.
        from gubernator_tpu.obs.profile import Profiler

        self.profiler = getattr(self.backend, "profiler", None)
        if self.profiler is None:
            self.profiler = Profiler(enabled=conf.profile_enabled)
        elif conf.profile_enabled is not None:
            self.profiler.enabled = bool(conf.profile_enabled)
        self.profiler.capture_min_interval_s = float(conf.profile_capture_s)
        # always present; sample 0 (the default) keeps every trace site a
        # guarded no-op — daemons wire GUBER_TRACE_SAMPLE through here
        self.tracer = conf.tracer or Tracer()
        # slow-request log entries carry the last minute's cycle
        # decomposition (obs/trace.py _log_slow)
        self.tracer.profile_snapshot = self.profiler.recent
        # flight recorder (obs/events.py): always constructed so every
        # subsystem hook is one attribute test; GUBER_FLIGHT_RECORDER=0
        # turns each emit into a single bool read
        self.recorder = conf.recorder or FlightRecorder()
        # a background unit of work over 100 ms is an event there
        self.profiler.recorder = self.recorder
        # decision ledger (obs/ledger.py): every admitted hit attributed
        # at decision time to its source of authority; the conservation
        # auditor runs off the serving path (anomaly ticker / scenario
        # sweeps force it). conf.ledger_enabled None defers to
        # GUBER_LEDGER; an explicit bool overrides the env.
        self.ledger = ledger_mod.DecisionLedger(
            enabled=conf.ledger_enabled, emit=self.recorder.emit)
        try:
            # the engine's window hooks read this attribute (one None
            # test per window when off); stub backends without the slot
            # simply never feed the window path
            self.backend.ledger = self.ledger
        except Exception:  # noqa: BLE001 — observability must not break wiring
            pass
        # concurrent callers merge into pipelined kernel launches: up to
        # GUBER_PIPELINE_DEPTH window groups ride the link/device while
        # further windows pool up and pack (service/combiner.py)
        self.combiner = BackendCombiner(
            self.backend, metrics=conf.metrics, tracer=self.tracer,
            depth=conf.pipeline_depth, scan=conf.pipeline_scan,
            recorder=self.recorder)

        self.local_picker = conf.local_picker or ReplicatedConsistentHashPicker()
        # The cross-region picker must route exactly like the DESTINATION
        # region's own local picker (same algorithm, same hash, same vnode
        # count — GUBER_PEER_PICKER is a fleet-wide contract, as in the
        # reference): multi-region replication targets a key's owner in
        # the other region, and a mismatched ring lands the hits on a
        # node that region does not route the key to (caught by
        # tests/test_multiregion_e2e.py). Template from the local picker
        # unless explicitly configured.
        self.region_picker = conf.region_picker or RegionPicker(
            self.local_picker.new())
        self._peer_lock = witness.make_rlock("instance.peers")

        # overload safety (service/deadline.py): in-flight forward count
        # feeds the admission controller's pending-work reading; the
        # controller itself gates ingress/forward/broadcast work against
        # GUBER_MAX_PENDING (0 disables — checks become one int read)
        self._forward_inflight = 0
        self._forward_lock = witness.make_lock("instance.forward")
        self.admission = AdmissionController(self, metrics=conf.metrics)
        # last deadline budget observed per surface (debug/test witness;
        # the request_budget_ms histogram is the production view)
        self.last_budget_ms: Dict[str, float] = {}

        # hot-key lease tier (service/leases.py): always constructed so
        # every hook is one `enabled` check; the detector only attaches to
        # the backend when GUBER_HOT_LEASES is set (arm())
        self.leases = LeaseManager(self)
        if getattr(conf.behaviors, "hot_leases", False):
            self.leases.arm()

        # live-resharding handoff plane (service/reshard.py): always
        # constructed so every serving hook is one `active` bool test;
        # GUBER_RESHARD enables it, and with it off membership changes
        # keep today's counter-amnesty semantics bit-identical
        self.reshard = ReshardManager(self)

        self.global_manager = GlobalManager(
            self, conf.behaviors, metrics=conf.metrics,
            admission=self.admission,
        )
        self.multiregion_manager = MultiRegionManager(self, conf.behaviors)
        # non-owner cache of GLOBAL statuses (reference: gubernator.go:251-264)
        self._global_cache = LRUCache()
        self._forward_pool = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="forward"
        )
        # optional collective (device-fabric) GLOBAL transport; when attached
        # it absorbs queue_hit/queue_update and the gRPC pipelines remain the
        # fallback (service/collective_global.py)
        self.collective_global = None
        self._collective_group = None  # None = every peer is in the group
        self._collective_covers = True
        self._peer_listeners = []
        # per-stage deadline-expired counts: the metrics-independent
        # signal the anomaly engine's deadline_burst detector diffs
        self.deadline_expired_stats: Dict[str, int] = {}
        # metrics history ring (obs/history.py): curated counter/gauge
        # snapshots every tick — serves /v1/debug/history, the bundle
        # run-up tail, and the anomaly engine's burn/rate windows
        self.history = MetricsHistory(
            self, tick_s=conf.history_tick_s,
            retention_s=conf.history_retention_s,
            enabled=conf.history_enabled)
        # keyspace cartographer (obs/keyspace.py): periodic off-path
        # device-table harvest — heavy hitters, concentration, occupancy,
        # HBM bytes — plus the headroom forecast over the history ring
        self.keyspace = KeyspaceCartographer(
            self, interval_s=conf.keyspace_interval_s,
            top_k=conf.keyspace_top_k, enabled=conf.keyspace_scan)
        # anomaly watchers (obs/anomaly.py): always constructed; sweeps
        # run from health_check/scrape piggybacks (maybe_check) and, in
        # daemons, a background ticker the daemon starts. The daemon also
        # wires bundle_writer so rising edges capture diagnostic bundles.
        self.bundle_writer = None
        self.anomaly = AnomalyEngine(
            self, metrics=conf.metrics, recorder=self.recorder,
            interval_s=conf.anomaly_interval_s,
            slo_target_ms=conf.slo_target_ms,
            slo_objective=conf.slo_objective,
            history=self.history,
            capacity_horizon_s=conf.capacity_horizon_s)
        # autopilot (service/autopilot.py): bounded closed-loop
        # controllers over the live knobs. Always constructed so every
        # hook is one attribute test; GUBER_AUTOPILOT (or
        # behaviors.autopilot) arms it — off, the decision stream is
        # bit-identical to static knobs.
        self.autopilot = Autopilot(
            self, metrics=conf.metrics, recorder=self.recorder)
        self._closed = False

    def attach_collective(self, sync, group_peers=None) -> None:
        """Wire a CollectiveGlobalSync (multi-host daemons only).

        `group_peers` lists the advertise addresses of the daemons in the
        jax.distributed process group. The collective only reaches THOSE
        hosts — in a mixed fleet (peers outside the group: reference nodes,
        staged rollouts) the gRPC broadcast must keep running for the
        others or their GLOBAL caches stay empty (ADVICE r2 #3). None means
        the whole fleet is in the group (the homogeneous default)."""
        self.collective_global = sync
        self._collective_group = (
            None if group_peers is None else frozenset(group_peers))
        self._recompute_collective_coverage()

    def profile_capture(self, seconds: float = 0.25) -> dict:
        """On-demand deep capture (/v1/debug/profile?capture=1): a
        rate-limited jax.profiler trace (wall-clock sampler fallback off
        TPU) written next to the diagnostic bundles when a bundle dir is
        configured, else the system tempdir."""
        import tempfile

        writer = getattr(self, "bundle_writer", None)
        out_dir = getattr(writer, "directory", None) or tempfile.gettempdir()
        return self.profiler.capture(out_dir, seconds=seconds)

    def columnar_backend(self):
        """The backend when it offers the zero-object columnar serving
        path (models/engine.py submit_columnar), else None. Used by the
        peerlink server to keep wire columns columnar end to end."""
        b = self.backend
        try:
            return b if b.supports_columnar() else None
        except AttributeError:
            return None

    def is_sole_owner(self) -> bool:
        """True when this node owns every key (no other local-region
        peers): public-surface requests need no routing, so the lean link
        can serve them through the owner fast paths."""
        with self._peer_lock:
            return self.local_picker.size() <= 1

    def on_peers_change(self, cb) -> None:
        """Register a callback fired after every set_peers rebuild (the
        peerlink service re-arms its native fast paths on it)."""
        self._peer_listeners.append(cb)

    def off_peers_change(self, cb) -> None:
        """Unregister (a closing service MUST remove its callback — a
        stale one would poke freed native state on the next rebuild)."""
        try:
            self._peer_listeners.remove(cb)
        except ValueError:
            pass

    def _in_collective_group(self, address: str) -> bool:
        g = self._collective_group
        return g is None or address in g or address == self.advertise_address

    def _recompute_collective_coverage(self) -> None:
        """Cache 'does the process group cover every local picker peer'
        (refreshed on membership change): only then may the collective
        replace the gRPC GLOBAL broadcast entirely."""
        if self._collective_group is None:
            self._collective_covers = True
            return
        with self._peer_lock:
            self._collective_covers = all(
                self._in_collective_group(p.info.address)
                for p in self.local_picker.peers())

    # ----------------------------------------------------------- public API

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        """Route one client batch (reference: gubernator.go:110-224).

        Timed end to end as one decision-latency observation for the SLO
        burn-rate engine (obs/anomaly.py); rejections (saturation,
        expired deadlines) burn error budget."""
        t0 = time.perf_counter()
        ok = False
        try:
            out = self._route_batch(requests, now_ms=now_ms)
            ok = True
            return out
        finally:
            self.anomaly.observe((time.perf_counter() - t0) * 1e3,
                                 error=not ok)

    def _route_batch(
        self, requests: Sequence[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        if len(requests) > MAX_BATCH_SIZE:
            raise ApiError(
                "OUT_OF_RANGE",
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'",
            )
        # one ContextVar read each per call — the entire routing-path cost
        # of tracing/deadlines when off; both are handed explicitly to the
        # forward pool (contexts do not cross its threads)
        span = trace.current()
        dl = deadline_mod.current()
        if dl is not None and dl.expired():
            # late work is the cheapest work to drop: the client stopped
            # waiting, so dispatching would only delay live requests
            self._count_expired(deadline_mod.STAGE_INGRESS)
            raise DeadlineExceededError(
                f"request budget ({dl.budget_ms:.0f} ms) exhausted before "
                "dispatch")
        # SATURATED rejects the whole call in microseconds; BROWNOUT lets
        # owner-local work through and sheds the non-owner forwards below
        admission = self.admission
        brownout = (admission.enabled
                    and admission.check_ingress() >= admission.BROWNOUT)
        responses: List[Optional[RateLimitResp]] = [None] * len(requests)
        local: List[int] = []
        remote: Dict[str, tuple] = {}  # owner addr -> (peer, [batch indices])

        for i, req in enumerate(requests):
            if not req.unique_key:
                responses[i] = RateLimitResp(error="field 'unique_key' cannot be empty")
                continue
            if not req.name:
                responses[i] = RateLimitResp(error="field 'namespace' cannot be empty")
                continue
            key = req.hash_key()
            try:
                peer = self.get_peer(key)
            except PickerEmptyError:
                # standalone mode: no peer list yet — we own everything
                local.append(i)
                continue
            except Exception as e:  # noqa: BLE001
                responses[i] = RateLimitResp(
                    error=f"while finding peer that owns rate limit '{key}' - '{e}'"
                )
                continue
            if log.isEnabledFor(logging.DEBUG):
                log.debug("route key=%s -> %s is_owner=%s behavior=%d",
                          key, peer.info.address, peer.info.is_owner,
                          req.behavior)
            if peer.info.is_owner:
                local.append(i)
            elif has_behavior(req.behavior, Behavior.GLOBAL):
                responses[i] = self._get_global_rate_limit(req, peer)
            elif (leased := self.leases.try_consume(
                    req, peer.info.address)) is not None:
                # held hot-key lease: answered from leased budget, hits
                # drain to the owner asynchronously (service/leases.py).
                # Checked BEFORE brownout — a lease answer is pure local
                # work, strictly cheaper than the shed response
                responses[i] = leased
            elif brownout:
                # brownout order: non-owner forwards shed FIRST — the
                # client can retry them against any moment or node, while
                # owner-local decisions have nowhere else to go
                admission.shed("brownout", "forward")
                responses[i] = admission.shed_response(peer.info.address)
            else:
                remote.setdefault(peer.info.address, (peer, []))[1].append(i)

        futures = []
        for peer, idxs in remote.values():
            if len(idxs) == 1:
                req = requests[idxs[0]]
                fut = self._forward_pool.submit(
                    self._forward_as_list, req, req.hash_key(), span, dl)
            else:
                fut = self._forward_pool.submit(
                    self._forward_group, peer,
                    [requests[i] for i in idxs], span, dl)
            self._track_forward(fut, len(idxs))
            futures.append((idxs, fut))

        if local:
            batch = [requests[i] for i in local]
            out = self.apply_owner_batch(batch, now_ms=now_ms)
            for i, resp in zip(local, out):
                responses[i] = resp
        for idxs, fut in futures:
            for i, resp in zip(idxs, fut.result()):
                responses[i] = resp
        return responses  # type: ignore[return-value]

    def get_peer_rate_limits(
        self, requests: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        """Owner-side application of a forwarded batch
        (reference: gubernator.go:267-284) — one kernel call, not a loop."""
        if len(requests) > MAX_BATCH_SIZE:
            raise ApiError(
                "OUT_OF_RANGE",
                f"'PeerRequest.rate_limits' list too large; max size is "
                f"'{MAX_BATCH_SIZE}'",
            )
        dl = deadline_mod.current()
        if dl is not None and dl.expired():
            self._count_expired(deadline_mod.STAGE_INGRESS)
            raise DeadlineExceededError(
                f"hop budget ({dl.budget_ms:.0f} ms) exhausted before "
                "owner apply")
        if self.admission.enabled:
            # forwarded owner batches are owner work (shed LAST, only at
            # saturation); the forwarding node gets a fast
            # RESOURCE_EXHAUSTED it can surface without a timeout stall
            self.admission.check_ingress(priority="peer")
        responses = self.apply_owner_batch(list(requests), from_peer_rpc=True)
        if self.leases.enabled:
            # owner side of the lease tier: hot keys' responses carry a
            # budget grant in their metadata (every metadata-bearing wire;
            # the peerlink client asks via its carrier lane instead)
            self.leases.attach_grants(requests, responses)
        return responses

    def update_peer_globals(self, updates) -> None:
        """Receive an owner's GLOBAL broadcast (reference: gubernator.go:251-264).
        `updates` are peers_pb.UpdatePeerGlobal messages."""
        for g in updates:
            self.apply_global_state(
                g.key, int(g.algorithm), int(g.status.status),
                g.status.limit, g.status.remaining, g.status.reset_time)

    def apply_global_state(self, key: str, algorithm: int, status: int,
                           limit: int, remaining: int, reset_time: int) -> None:
        """Install one key's authoritative GLOBAL state into the local cache
        — the broadcast receive path, shared by the gRPC transport
        (update_peer_globals) and the collective transport."""
        self._global_cache.add(
            CacheItem(
                key=key,
                value=_GlobalStatus(
                    status=status,
                    limit=limit,
                    remaining=remaining,
                    reset_time=reset_time,
                ),
                expire_at=reset_time,
                algorithm=algorithm,
            )
        )

    # health message bounds: under sustained failure the raw join of every
    # retained error (100/peer x peers, 5-minute TTL) produced multi-KB
    # health responses; report per-peer COUNTS plus capped samples instead
    HEALTH_SAMPLES_PER_PEER = 2
    HEALTH_SAMPLE_CHARS = 160
    HEALTH_MESSAGE_CHARS = 2048

    def health_check(self) -> HealthCheckResp:
        """Accumulate recent peer errors (reference: gubernator.go:287-325),
        bounded: one line per failing peer with its error COUNT, circuit
        state, and up to HEALTH_SAMPLES_PER_PEER deduped samples; the whole
        message is capped at HEALTH_MESSAGE_CHARS."""
        parts: List[str] = []
        adm = self.admission
        if adm.enabled:
            lvl = adm.level()
            if lvl > adm.ADMIT:
                state = "saturated" if lvl >= adm.SATURATED else "brownout"
                sheds = ", ".join(
                    f"{k[5:]}={v}" for k, v in sorted(adm.stats.items())
                    if v)
                parts.append(
                    f"admission {state}: pending {adm.pending()} of "
                    f"max_pending {adm.max_pending}"
                    + (f" (shed {sheds})" if sheds else ""))
        if self.collective_global is not None:
            err = self.collective_global.health_error()
            if err:
                parts.append(err)
        with self._peer_lock:
            peers = self.local_picker.peers() + self.region_picker.peers()
            peer_count = self.local_picker.size() + self.region_picker.size()
        for peer in peers:
            errs = peer.get_last_err()  # LRU-deduped per peer already
            circuit = getattr(peer, "circuit", None)
            circuit_note = ""
            if circuit is not None and circuit.state != CIRCUIT_CLOSED:
                circuit_note = f", circuit {circuit.state_name}"
            if not errs and not circuit_note:
                continue
            prefix = f"{peer.info.address}: "
            samples = "; ".join(
                (e[len(prefix):] if e.startswith(prefix)
                 else e)[:self.HEALTH_SAMPLE_CHARS]
                for e in errs[:self.HEALTH_SAMPLES_PER_PEER])
            line = f"{peer.info.address}: {len(errs)} errors{circuit_note}"
            if samples:
                line += f" ({samples})"
            parts.append(line)
        # lease-tier and anomaly state are annotation only: both flag
        # conditions worth investigating, and neither may flip a node
        # unhealthy by itself (the underlying failures already do)
        lease_note = self.leases.health_note()
        self.anomaly.maybe_check()  # health probes keep detection fresh
        anomaly_note = self.anomaly.health_note()
        if anomaly_note:
            lease_note = (f"{lease_note} | {anomaly_note}" if lease_note
                          else anomaly_note)
        if parts:
            message = " | ".join(parts)
            if len(message) > self.HEALTH_MESSAGE_CHARS:
                message = (message[:self.HEALTH_MESSAGE_CHARS]
                           + f"... [{len(parts)} peers reporting]")
            if lease_note:
                message += f" | {lease_note}"
            return HealthCheckResp(
                status="unhealthy", message=message, peer_count=peer_count
            )
        return HealthCheckResp(status="healthy", peer_count=peer_count,
                               message=lease_note)

    def set_peers(self, peer_infos: Sequence[PeerInfo]) -> None:
        """Rebuild pickers on membership change, reusing live PeerClients and
        draining removed ones (reference: gubernator.go:349-417)."""
        with self._peer_lock:
            new_local = self.local_picker.new()
            new_region = self.region_picker.new()
            for info in peer_infos:
                info = PeerInfo(
                    address=info.address,
                    datacenter=info.datacenter,
                    is_owner=info.is_owner
                    or (bool(self.advertise_address)
                        and info.address == self.advertise_address),
                )
                if info.datacenter and info.datacenter != self.data_center:
                    peer = self.region_picker.get_by_peer_info(info)
                    if peer is None:
                        peer = PeerClient(self.conf.behaviors, info,
                                          metrics=self.conf.metrics,
                                          recorder=self.recorder)
                    new_region.add(peer)
                    continue
                peer = self.local_picker.get_by_peer_info(info)
                if peer is None:
                    peer = PeerClient(self.conf.behaviors, info,
                                      metrics=self.conf.metrics,
                                      recorder=self.recorder)
                    # the micro-batched per-request path flushes inside the
                    # client's worker thread, out of Instance's sight — the
                    # advisor lets that flush attach a hot-key lease ask to
                    # its batch exactly like _forward_group does inline
                    peer.lease_advisor = self.leases.want
                else:
                    peer.info = info
                new_local.add(peer)

            old_local, self.local_picker = self.local_picker, new_local
            old_region, self.region_picker = self.region_picker, new_region
            log.info(
                "peers updated: %d local, %d region, self=%s",
                new_local.size(), new_region.size(),
                self.advertise_address or "?")
            # handoff plane: capture the ring diff synchronously (fast —
            # no RPC under the lock; planning + streaming happen on the
            # manager's own thread) so the first request routed under the
            # new ring already sees the planning/grace window
            self.reshard.on_peers_changed(old_local, new_local)
        self._recompute_collective_coverage()
        for cb in self._peer_listeners:
            try:
                cb()
            except Exception:  # noqa: BLE001 — listeners must not break
                log.exception("peer-change listener failed")

        shutdown = [
            p for p in old_local.peers()
            if self.local_picker.get_by_peer_info(p.info) is None
        ] + [
            p for p in old_region.peers()
            if self.region_picker.get_by_peer_info(p.info) is None
        ]
        for p in shutdown:
            try:
                p.shutdown(timeout_s=self.conf.behaviors.batch_timeout_s)
            except Exception:  # noqa: BLE001
                log.exception("while shutting down peer %s", p.info.address)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.autopilot.stop()
        self.reshard.stop()
        self.anomaly.stop()
        self.history.stop()
        self.keyspace.stop()
        if self.collective_global is not None:
            self.collective_global.close()
        self.global_manager.close()
        self.multiregion_manager.close()
        self._forward_pool.shutdown(wait=False)
        with self._peer_lock:
            for p in self.local_picker.peers() + self.region_picker.peers():
                try:
                    p.shutdown(timeout_s=0.5)
                except Exception:  # noqa: BLE001
                    pass
        self.combiner.close()
        if hasattr(self.backend, "close"):
            self.backend.close()

    # ------------------------------------------------------------- plumbing

    def get_peer(self, key: str) -> PeerClient:
        """Owner peer for a key (reference: gubernator.go:420-427)."""
        with self._peer_lock:
            return self.local_picker.get(key)

    def _track_forward(self, fut, n: int) -> None:
        """Count `n` requests as in-flight forwards until `fut` resolves
        — the forward-pool term of the admission pending reading."""
        with self._forward_lock:
            self._forward_inflight += n

        def _untrack(_f, n=n):
            with self._forward_lock:
                self._forward_inflight -= n

        fut.add_done_callback(_untrack)

    def _count_expired(self, stage: str) -> None:
        self.deadline_expired_stats[stage] = \
            self.deadline_expired_stats.get(stage, 0) + 1
        if self.conf.metrics is not None:
            try:
                self.conf.metrics.deadline_expired.labels(stage=stage).inc()
            except Exception:  # noqa: BLE001 — metrics must not break
                pass

    def observe_budget(self, surface: str, budget_ms: float) -> None:
        """Record a captured deadline budget (public ingress or the
        decremented hop budget a peer surface received) — the
        request_budget_ms histogram plus a last-value witness the wire
        round-trip tests read."""
        self.last_budget_ms[surface] = budget_ms
        if self.conf.metrics is not None:
            try:
                self.conf.metrics.request_budget_ms.labels(
                    surface=surface).observe(budget_ms)
            except Exception:  # noqa: BLE001 — metrics must not break
                pass

    def local_peers(self) -> List[PeerClient]:
        with self._peer_lock:
            return self.local_picker.peers()

    def all_peer_clients(self) -> List[PeerClient]:
        """Every live PeerClient (local + region) — health/metrics walk."""
        with self._peer_lock:
            return self.local_picker.peers() + self.region_picker.peers()

    def region_pickers(self) -> Dict[str, object]:
        with self._peer_lock:
            return dict(self.region_picker.pickers())

    def apply_owner_batch(
        self, requests: List[RateLimitReq], now_ms: Optional[int] = None,
        from_peer_rpc: bool = False,
    ) -> List[RateLimitResp]:
        """Apply requests we own to the TPU backend in one batched call,
        queueing GLOBAL broadcasts / multi-region replication first
        (reference: gubernator.go:327-347)."""
        rm = self.reshard
        if not rm.active:
            return self.combiner.submit(
                self._strip_owner_batch(requests, from_peer_rpc),
                now_ms=now_ms)
        # handoff window: enter the apply gate FIRST so the exporter's cut
        # settle (fence + barrier) can never interleave with a batch that
        # already passed the intercept; the plan's network legs (redirect/
        # proxy) resolve in finish(), outside the gate
        rm.apply_enter()
        try:
            plan = rm.intercept_owner_batch(requests, from_peer_rpc)
            if plan is None:
                return self.combiner.submit(
                    self._strip_owner_batch(requests, from_peer_rpc),
                    now_ms=now_ms)
            local = [requests[i] for i in plan.local_idx]
            local_out = self.combiner.submit(
                self._strip_owner_batch(local, from_peer_rpc),
                now_ms=now_ms) if local else []
        finally:
            rm.apply_exit()
        return plan.finish(local_out, now_ms)

    def apply_owner_batch_direct(
        self, requests: List[RateLimitReq], now_ms: Optional[int] = None,
        from_peer_rpc: bool = False,
    ) -> List[RateLimitResp]:
        """apply_owner_batch minus the combiner hop, for callers that
        already aggregated a batch (the peerlink workers): the engine's own
        lock serializes concurrent windows, and skipping the combiner saves
        two thread handoffs on the lone-request latency path."""
        if self.admission.enabled:
            # the peerlink hop's admission gate (the gRPC hop checks in
            # get_peer_rate_limits): shed at saturation only — owner work
            # goes last in the brownout order
            self.admission.check_ingress(priority="peer")
        return self._apply_owner_direct(requests, now_ms=now_ms,
                                        from_peer_rpc=from_peer_rpc)

    def _apply_owner_direct(
        self, requests: List[RateLimitReq], now_ms: Optional[int] = None,
        from_peer_rpc: bool = False,
    ) -> List[RateLimitResp]:
        """The combiner-free owner apply: the backend call runs on THIS
        thread (the engine lock serializes concurrent windows), so
        calling-thread context — the ledger's authority scope in
        particular — reaches the engine's staging hooks. Used by the
        peerlink workers (via apply_owner_batch_direct, which adds the
        admission gate) and by the degraded/reshard serve paths, which
        are already inside admitted work."""
        rm = self.reshard
        if not rm.active:
            return self.backend.get_rate_limits(
                self._strip_owner_batch(requests, from_peer_rpc),
                now_ms=now_ms)
        rm.apply_enter()
        try:
            plan = rm.intercept_owner_batch(requests, from_peer_rpc)
            if plan is None:
                return self.backend.get_rate_limits(
                    self._strip_owner_batch(requests, from_peer_rpc),
                    now_ms=now_ms)
            local = [requests[i] for i in plan.local_idx]
            local_out = self.backend.get_rate_limits(
                self._strip_owner_batch(local, from_peer_rpc),
                now_ms=now_ms) if local else []
        finally:
            rm.apply_exit()
        return plan.finish(local_out, now_ms)

    def _strip_owner_batch(
        self, requests: List[RateLimitReq], from_peer_rpc: bool = False
    ) -> List[RateLimitReq]:
        stripped = []
        for req in requests:
            if has_behavior(req.behavior, Behavior.GLOBAL):
                cg = self.collective_global
                covered = cg is not None and cg.queue_update(req)
                # The collective may skip the gRPC broadcast only for
                # owner-LOCAL traffic with the whole fleet in the process
                # group. A GLOBAL request arriving over peer RPC is itself
                # proof that some peer is NOT riding the collective for
                # this key (key-level FALLBACK on its side, first touch,
                # out-of-group node) — that peer's cache is fed by gRPC
                # broadcasts alone, so keep them flowing. Collective-tier
                # owner applies never re-enter here (the tick strips
                # GLOBAL first), and in-group hosts installing the same
                # authoritative state twice is harmless.
                if from_peer_rpc or not (covered and
                                         self._collective_covers):
                    self.global_manager.queue_update(req)
            if has_behavior(req.behavior, Behavior.MULTI_REGION):
                self.multiregion_manager.queue_hits(req)
            if has_behavior(req.behavior, Behavior.GLOBAL):
                # host tier owns GLOBAL semantics; the backend must treat the
                # request as a plain owned key (see parallel/sharded.py for
                # the standalone-mesh GLOBAL path)
                req = without_behavior(req, Behavior.GLOBAL)
            stripped.append(req)
        return stripped

    # ------------------------------------------------------------ internals

    def _forward(self, req: RateLimitReq, key: str, span=None,
                 dl=None) -> RateLimitResp:
        """Relay to the owning peer, re-picking up to 5 times while peers
        shut down (reference: gubernator.go:149-157,186-205).

        Re-picks back off with jitter and respect a deadline bounded by
        the client's own batch timeout AND the request's remaining budget
        (`dl`, service/deadline.py): a picker that keeps returning the
        same closing peer must not spin the loop hot, the loop must never
        outlive the RPC deadline the caller is already paying, and no
        retry — circuit probe included — may start past a dead budget."""
        last_err = ""
        deadline = time.monotonic() + self.conf.behaviors.batch_timeout_s
        if dl is not None:
            deadline = min(deadline, dl.expires_at)
        for attempt in range(6):
            if dl is not None and dl.expired():
                self._count_expired(deadline_mod.STAGE_FORWARD)
                return RateLimitResp(
                    error=f"DEADLINE_EXCEEDED: budget "
                          f"({dl.budget_ms:.0f} ms) expired while "
                          f"forwarding '{key}' - '{last_err}'")
            try:
                peer = self.get_peer(key)
            except Exception as e:  # noqa: BLE001
                return RateLimitResp(
                    error=f"while finding peer that owns rate limit '{key}' - '{e}'"
                )
            if peer.info.is_owner:  # membership changed under us
                token = trace.use(span) if span is not None else None
                dtoken = deadline_mod.use(dl) if dl is not None else None
                try:
                    return self.apply_owner_batch([req])[0]
                except DeadlineExceededError as e:
                    return RateLimitResp(error=f"DEADLINE_EXCEEDED: {e}")
                finally:
                    if dtoken is not None:
                        deadline_mod.reset(dtoken)
                    if token is not None:
                        trace.reset(token)
            t0 = time.time_ns() if span is not None else 0
            try:
                resp = peer.get_peer_rate_limit(req, trace_span=span,
                                                deadline=dl)
                resp.metadata["owner"] = peer.info.address
                if self.leases.enabled:
                    self.leases.note_forwards((req,))
                    self.leases.install_from_responses(
                        (req,), (resp,), peer.info.address)
                if span is not None:
                    self.tracer.record_span(
                        "peer.hop", span, t0, time.time_ns(),
                        {"peer": peer.info.address})
                return resp
            except CircuitOpenError:
                # the owner's circuit is open: nothing was sent, so serve
                # degraded-local (when enabled) or fail fast — either way
                # in microseconds, never a batch_timeout_s stall
                return self._degrade_or_error([req], peer, dl=dl)[0]
            except DeadlineExceededError as e:
                # the budget died in flight: no re-pick can help, and the
                # caller has already stopped listening — surface it
                return RateLimitResp(error=f"DEADLINE_EXCEEDED: {e}")
            except PeerNotReadyError as e:
                last_err = str(e)
                now = time.monotonic()
                if now >= deadline or attempt == 5:
                    break
                # jittered backoff before the re-pick: membership updates
                # need a beat to land, and zero-sleep spins pin a core
                time.sleep(min(0.002 * (1 << attempt) * (0.5 + random.random()),
                               0.05, deadline - now))
                continue
            except Exception as e:  # noqa: BLE001
                return RateLimitResp(
                    error=f"while fetching rate limit '{key}' from peer - '{e}'"
                )
        return RateLimitResp(
            error=f"GetPeer() keeps returning peers that are not connected for "
            f"'{key}' - '{last_err}'"
        )

    def _forward_as_list(self, req: RateLimitReq, key: str, span=None,
                         dl=None) -> List[RateLimitResp]:
        return [self._forward(req, key, span, dl)]

    def _forward_group(
        self, peer: PeerClient, reqs: List[RateLimitReq], span=None, dl=None
    ) -> List[RateLimitResp]:
        """Forward several same-owner requests as ONE ordered batch.

        Same-batch requests to one owner ride a single GetPeerRateLimits
        RPC, preserving the client's submission order for duplicate keys.
        The reference forwards each request independently (goroutine fan-out
        + per-peer micro-batch, gubernator.go:126-213), so two same-key
        requests in one client batch can be applied in either order there;
        grouping restores the single-node rounds semantics across the
        forwarding hop and costs one RPC per owner instead of one per
        request. Single-request groups keep the micro-batched per-request
        path so lone callers still amortize into the 500 µs peer window.

        Failure handling mirrors _forward's: not-ready means the RPC was
        never sent — or was cancelled by our own shutdown() when a
        membership change removed the peer, where a re-forward at worst
        over-counts one in-flight batch — so re-forwarding per request
        (with owner re-picks) is safe and fails fast; any OTHER error may
        mean the owner already applied the batch, so re-sending would
        double-count hits — those surface as error responses, exactly
        like the per-request path."""
        t0 = time.time_ns() if span is not None else 0
        lease_want = None
        if self.leases.enabled:
            # non-owner half of the lease tier: count these forwards into
            # the local hot window and, when one of the keys is local-hot,
            # ask the owner for a lease (the peerlink wire carries the ask
            # as a reserved carrier; the gRPC wire grants unprompted)
            self.leases.note_forwards(reqs)
            lease_want = self.leases.want(reqs)
        try:
            resps = peer.get_peer_rate_limits(reqs, trace_span=span,
                                              deadline=dl,
                                              lease_want=lease_want)
        except CircuitOpenError:
            # owner circuit open: pre-send by construction, so the whole
            # group may degrade locally in ONE owner-batch apply
            return self._degrade_or_error(reqs, peer, dl=dl)
        except DeadlineExceededError as e:
            return [RateLimitResp(error=f"DEADLINE_EXCEEDED: {e}")
                    for _ in reqs]
        except PeerNotReadyError:
            return [self._forward(r, r.hash_key(), span, dl) for r in reqs]
        except Exception as e:  # noqa: BLE001
            return [RateLimitResp(
                error=f"while fetching rate limit '{r.hash_key()}' "
                      f"from peer - '{e}'")
                for r in reqs]
        if len(resps) != len(reqs):
            return [RateLimitResp(
                error=f"peer returned {len(resps)} responses for "
                      f"{len(reqs)} requests")
                for _ in reqs]
        if span is not None:
            self.tracer.record_span(
                "peer.hop", span, t0, time.time_ns(),
                {"peer": peer.info.address, "requests": len(reqs)})
        for r in resps:
            r.metadata["owner"] = peer.info.address
        if self.leases.enabled:
            self.leases.install_from_responses(reqs, resps,
                                               peer.info.address)
        return resps

    def _degrade_or_error(
        self, reqs: Sequence[RateLimitReq], peer: PeerClient, dl=None
    ) -> List[RateLimitResp]:
        """The owner's circuit is OPEN (a pre-send condition: nothing
        reached the wire, so local application cannot double-count).

        With GUBER_DEGRADED_LOCAL on, apply the requests here as-if-owner —
        the same owner-pipeline behavior-stripping the GLOBAL owner-down
        fallback uses (GLOBAL broadcast and MULTI_REGION replication are
        the real owner's job; running them off this node's partial view
        would poison every peer's mirror) — and mark each response
        metadata[degraded]=true so callers can tell enforced-but-approximate
        answers from owner-authoritative ones. Off, fail fast with a
        distinct error (still no batch_timeout_s stall: the breaker already
        paid the timeout that opened it)."""
        addr = peer.info.address
        if not getattr(self.conf.behaviors, "degraded_local", False):
            return [RateLimitResp(
                error=f"circuit open to owner '{addr}' for "
                      f"'{r.hash_key()}' - failing fast "
                      f"(GUBER_DEGRADED_LOCAL=1 serves these locally)")
                for r in reqs]
        local = [without_behavior(r, Behavior.GLOBAL, Behavior.MULTI_REGION)
                 for r in reqs]
        dtoken = deadline_mod.use(dl) if dl is not None else None
        try:
            if dl is not None and dl.expired():
                # mirror the combiner's dequeue-time shed: a dead budget
                # must not occupy a device window
                self._count_expired(deadline_mod.STAGE_QUEUE)
                raise DeadlineExceededError(
                    f"request budget ({dl.budget_ms:.0f} ms) expired "
                    "before the degraded-local window")
            # same-thread apply so the ledger attributes these windows to
            # the degraded-local authority (the combiner hop would lose
            # the calling thread's authority scope)
            with ledger_mod.authority("degraded"):
                resps = self._apply_owner_direct(local)
        except DeadlineExceededError as e:
            # the budget died before the degraded window ran: same
            # per-request error shape as every other forward failure
            return [RateLimitResp(error=f"DEADLINE_EXCEEDED: {e}")
                    for _ in reqs]
        finally:
            if dtoken is not None:
                deadline_mod.reset(dtoken)
        if self.conf.metrics is not None:
            try:
                self.conf.metrics.degraded_local.inc(len(resps))
            except Exception:  # noqa: BLE001 — metrics must not break serving
                pass
        for r in resps:
            r.metadata["owner"] = addr
            r.metadata["degraded"] = "true"
        return resps

    def _get_global_rate_limit(
        self, req: RateLimitReq, owner_peer: PeerClient
    ) -> RateLimitResp:
        """Non-owner GLOBAL path: answer from the broadcast cache with
        optimistic deduction and queue the hits; on a cache miss, relay the
        first touch to the real owner (deviation: the reference processes a
        miss locally as-if-owner, double-counting its hits,
        gubernator.go:226-247)."""
        cached: Optional[RateLimitResp] = None
        with self._global_cache.lock:
            item = self._global_cache.get_item(req.hash_key())
            if item is not None:
                st: _GlobalStatus = item.value
                status = st.status
                if req.hits > 0:
                    if st.remaining == 0 or req.hits > st.remaining:
                        status = int(Status.OVER_LIMIT)
                    else:
                        st.remaining -= req.hits
                        status = st.status
                cg = self.collective_global
                # hits ride the collective only when the OWNER host is in
                # the process group — otherwise nobody would apply the slot
                # (the psum'd deltas would just age out back to gRPC)
                if cg is None or \
                        not self._in_collective_group(
                            owner_peer.info.address) or \
                        not cg.queue_hit(req):
                    self.global_manager.queue_hit(req)
                cached = RateLimitResp(
                    status=status,
                    limit=st.limit,
                    remaining=st.remaining,
                    reset_time=st.reset_time,
                    metadata={"owner": owner_peer.info.address},
                )
        if cached is not None:
            led = self.ledger
            if led is not None and led.enabled and req.hits > 0:
                # attribution OUTSIDE the cache lock: the ledger's bucket
                # lock is a leaf and must not nest under the LRU lock
                led.record_key(req.hash_key(), req.hits, int(cached.status),
                               int(cached.limit), int(cached.reset_time),
                               auth="global_cache")
            return cached
        # first touch: relay synchronously to the owner (its response will
        # also come back to us via the broadcast pipeline)
        try:
            resp = owner_peer.get_peer_rate_limit(req)
            resp.metadata["owner"] = owner_peer.info.address
            if self.collective_global is not None and \
                    self._in_collective_group(owner_peer.info.address):
                # start claiming the key's slot so the owner's collective
                # broadcasts can reach this host's cache (no strings ride
                # the collective — registration is how key<->slot binds);
                # pointless when the owner is outside the process group
                self.collective_global.register_remote(req)
            return resp
        except Exception:  # noqa: BLE001
            # Owner unreachable: process locally as-if-owner so the limit
            # still enforces something (reference fallback,
            # gubernator.go:242-246). Strip GLOBAL and MULTI_REGION first —
            # broadcasting and cross-region replication are the owner's
            # job; queueing them here would push this non-owner's partial
            # view over every peer's mirror, or replicate hits a second
            # time when the owner applied the request before the RPC timed
            # out. (The reference wipes the WHOLE behavior field to
            # NO_BATCHING, which also nukes DURATION_IS_GREGORIAN and
            # silently turns a calendar limit into a milliseconds one; we
            # strip only the owner-pipeline flags.)
            local = without_behavior(
                req, Behavior.GLOBAL, Behavior.MULTI_REGION)
            resp = self.apply_owner_batch([local])[0]
            resp.metadata["owner"] = owner_peer.info.address
            return resp
