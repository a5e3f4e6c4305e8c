"""Service-level configuration (reference: config.go:28-106).

Defaults mirror the reference's SetDefaults exactly — the 500 µs batch
window and 1000-item batch cap are the published performance envelope
(reference: README.md:113-115).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from gubernator_tpu.types import MAX_BATCH_SIZE


@dataclasses.dataclass
class BehaviorConfig:
    """Tuning for the async batching pipelines (reference: config.go:62-84)."""

    # peer forwarding micro-batch (reference: config.go:87-90)
    batch_timeout_s: float = 0.5  # wait for a batched peer response
    batch_wait_s: float = 0.0005  # window before sending a batch
    batch_limit: int = MAX_BATCH_SIZE

    # GLOBAL sync pipelines (reference: config.go:92-94)
    global_timeout_s: float = 0.5
    global_sync_wait_s: float = 0.0005
    global_batch_limit: int = MAX_BATCH_SIZE

    # multi-region replication (reference: config.go:96-98)
    multi_region_timeout_s: float = 0.5
    multi_region_sync_wait_s: float = 1.0
    multi_region_batch_limit: int = MAX_BATCH_SIZE

    # peerlink: the native peer transport (service/peerlink.py). A peer's
    # link listens at its gRPC port + this offset; 0 disables and every
    # peer call rides gRPC. Transparent per-peer fallback to gRPC when the
    # link can't connect (mixed fleets with reference nodes keep working).
    peer_link_offset: int = 1000
    # gRPC-fallback backoff before re-trying a peer's native link, seconds
    # (GUBER_LINK_RETRY_S; jittered ±50% per attempt so a fleet doesn't
    # re-dial a revived link port in one synchronized wave)
    link_retry_s: float = 30.0

    # peer-failure resilience (service/peer_client.py CircuitBreaker,
    # docs/OPERATIONS.md "Failure modes"): a peer circuit opens after
    # `circuit_threshold` CONSECUTIVE transport failures (peerlink and gRPC
    # feed one breaker) and fails calls fast pre-send for `circuit_open_s`,
    # then admits a single half-open probe. 0 disables the breaker.
    circuit_threshold: int = 5
    circuit_open_s: float = 5.0
    # GUBER_DEGRADED_LOCAL: while a key's owner circuit is open, serve
    # ordinary forwards locally as-if-owner (GLOBAL/MULTI_REGION pipeline
    # flags stripped, responses marked metadata[degraded]=true) instead of
    # returning errors. Off by default: split-brain over-admission is a
    # policy choice the operator must opt into.
    degraded_local: bool = False

    # overload safety: deadline budgets + admission control
    # (service/deadline.py, instance.py AdmissionController;
    # docs/OPERATIONS.md "Overload & deadlines").
    # GUBER_DEFAULT_DEADLINE_MS: budget assigned to ingress requests that
    # carry none of their own (gRPC context deadline / X-Request-Deadline-Ms
    # header win when present). 0 = requests without an explicit deadline
    # have no budget — every deadline site is then a None check.
    default_deadline_ms: float = 0.0
    # GUBER_MIN_HOP_BUDGET_MS: floor on the budget a forwarded hop is
    # granted — below it the caller sheds instead of burning a wire round
    # trip on a timeout that cannot succeed.
    min_hop_budget_ms: float = 5.0
    # GUBER_MAX_PENDING: pending-work cap (combiner backlog + in-flight
    # forwards + GLOBAL pipeline depth). Non-owner forwards and GLOBAL
    # broadcasts shed at 75% of it (brownout), everything at 100%
    # (RESOURCE_EXHAUSTED). 0 disables admission control entirely —
    # behavior is then bit-identical to the pre-admission code.
    max_pending: int = 8192
    # GUBER_BROWNOUT_FRACTION: the fraction of max_pending at which the
    # admission controller browns out (sheds non-owner forwards and
    # GLOBAL broadcasts). Read live per check, so both operators and the
    # autopilot's admission controller can tune it without a restart.
    brownout_fraction: float = 0.75

    # hot-key lease tier (service/leases.py; docs/OPERATIONS.md
    # "Skew & leases"). GUBER_HOT_LEASES turns the whole tier on; off
    # (default) keeps every hook a guarded no-op and the serving path
    # bit-identical to the pre-lease tree.
    hot_leases: bool = False
    # GUBER_HOT_LEASE_RATE: hits/s over a detection window that makes a
    # key "hot" — on the owner (apply-window feeds) and on non-owners
    # (their own forward counts, the peerlink lease-ask heuristic).
    hot_lease_rate: float = 500.0
    # GUBER_HOT_LEASE_WINDOW: detection window length, seconds.
    hot_lease_window_s: float = 1.0
    # GUBER_HOT_LEASE_TTL: lease lifetime, seconds. Also the staleness
    # bound: a revoked/partitioned lease over-admits at most its budget
    # and dies unrenewed after this long.
    hot_lease_ttl_s: float = 0.5
    # GUBER_HOT_LEASE_FRACTION: slice of (remaining - outstanding) one
    # grant hands out. Overshoot is bounded by the outstanding budget, so
    # the fraction trades local-serving runway against worst-case
    # over-admission.
    hot_lease_fraction: float = 0.2

    # live resharding (service/reshard.py; docs/OPERATIONS.md "Deploys &
    # resharding"). GUBER_RESHARD arms counter-continuous ownership
    # handoff on membership change; off (default) keeps every hook one
    # attribute test and membership changes bit-identical to the
    # pre-reshard amnesty behavior.
    reshard: bool = False
    # GUBER_RESHARD_TTL: transfer-lease lifetime, seconds. Renewed by
    # every streamed frame; at expiry both sides fail-close — the
    # importer serves fresh (amnesty), the exporter aborts — so a wedged
    # transfer can never wedge serving or mint budget.
    reshard_ttl_s: float = 5.0
    # GUBER_RESHARD_CHUNK_ROWS: rows per transfer frame (also split at
    # ~512 KB of key bytes to stay under the 1 MB RPC frame cap).
    reshard_chunk_rows: int = 2048
    # GUBER_RESHARD_GRACE: how long a new owner keeps proxying gained
    # keys to a previous owner that has not opened a transfer session
    # yet (it may still be planning); after it, gained keys without a
    # session serve fresh.
    reshard_grace_s: float = 1.0

    # autopilot (service/autopilot.py; docs/OPERATIONS.md "Autopilot"):
    # bounded closed-loop controllers that drive the serving knobs from
    # live telemetry. None defers to GUBER_AUTOPILOT at wiring time
    # (default OFF — every hook is then one attribute test and the
    # decision stream bit-identical to static knobs,
    # tests/test_autopilot.py differential).
    autopilot: Optional[bool] = None
    # GUBER_AUTOPILOT_INTERVAL: sweep cadence, seconds.
    autopilot_interval_s: float = 1.0
    # GUBER_AUTOPILOT_DWELL: minimum continuous time a signal must hold
    # past a trip (or below a clear) threshold before a controller
    # engages (or disengages) — the hysteresis dwell.
    autopilot_dwell_s: float = 5.0
    # GUBER_AUTOPILOT_COOLDOWN: minimum seconds between two moves of the
    # same knob — the actuation rate limit.
    autopilot_cooldown_s: float = 10.0
    # GUBER_AUTOPILOT_FREEZE_HOLD: how long a membership flip freezes
    # all actuation (reshard transfers freeze for their whole flight).
    autopilot_freeze_hold_s: float = 5.0


@dataclasses.dataclass
class InstanceConfig:
    """Wiring for one Instance (reference: config.go:28-60)."""

    behaviors: BehaviorConfig = dataclasses.field(default_factory=BehaviorConfig)
    data_center: str = ""
    # backend: models.engine.Engine | parallel.sharded.ShardedEngine;
    # built by the Instance if omitted
    backend: Optional[object] = None
    local_picker: Optional[object] = None  # cluster.pickers.*
    region_picker: Optional[object] = None
    # service.metrics.Metrics; optional — managers observe their histograms
    # through it when present (reference: global.go:45-51,155,238)
    metrics: Optional[object] = None
    # obs.trace.Tracer; optional — the Instance builds a disabled one
    # (sample 0, zero hot-path cost) when omitted
    tracer: Optional[object] = None
    # depth-N pipelined serving loop (service/combiner.py): cycles in
    # flight between launch and readback. None reads GUBER_PIPELINE_DEPTH
    # ('auto' probes; 1 pins the serial lock-step path); pipeline_scan is
    # the max windows coalesced into one scan-group launch
    # (GUBER_PIPELINE_SCAN).
    pipeline_depth: Optional[int] = None
    pipeline_scan: Optional[int] = None
    # obs.events.FlightRecorder; optional — the Instance builds one
    # (enabled unless GUBER_FLIGHT_RECORDER=0) when omitted
    recorder: Optional[object] = None
    # anomaly watchers (obs/anomaly.py): sweep cadence and the decision
    # SLO the burn-rate engine accounts against (GUBER_ANOMALY_INTERVAL /
    # GUBER_SLO_TARGET_MS / GUBER_SLO_OBJECTIVE)
    anomaly_interval_s: float = 5.0
    slo_target_ms: float = 250.0
    slo_objective: float = 0.999
    # capacity & keyspace cartography (obs/history.py, obs/keyspace.py):
    # the metrics-history ring snapshots curated counters/gauges every
    # tick into ~2 h of samples (GUBER_HISTORY / GUBER_HISTORY_TICK_S /
    # GUBER_HISTORY_RETENTION); the cartographer harvests the device
    # table off the serving path every interval (GUBER_KEYSPACE_SCAN /
    # GUBER_KEYSPACE_INTERVAL / GUBER_KEYSPACE_TOP_K); the capacity
    # detector fires when projected time-to-full crosses the horizon
    # (GUBER_CAPACITY_HORIZON). history_enabled=False clamps the ring to
    # what the anomaly engine's burn windows need and nothing more.
    history_enabled: bool = True
    history_tick_s: float = 5.0
    history_retention_s: float = 7200.0
    keyspace_scan: bool = True
    keyspace_interval_s: float = 60.0
    keyspace_top_k: int = 20
    capacity_horizon_s: float = 1800.0
    # continuous profiling plane (obs/profile.py): serving-cycle phase
    # decomposition, per-site lock-wait histograms, kernel dispatch-time
    # tracking, and on-demand deep capture. None defers to GUBER_PROFILE
    # at wiring time; False turns every observation site into a single
    # attribute test and the serving path bit-identical to profiling off.
    profile_enabled: Optional[bool] = None
    # decision ledger & conservation auditor (obs/ledger.py): per-authority
    # admit attribution plus the off-path "never mint budget" audit. None
    # defers to GUBER_LEDGER at wiring time (default ON); False turns every
    # hook into a single attribute/bool test and the serving path
    # bit-identical to ledger off (tests/test_ledger.py differential).
    ledger_enabled: Optional[bool] = None
    # GUBER_PROFILE_CAPTURE_S: minimum seconds between on-demand deep
    # captures (/v1/debug/profile?capture=1) — the rate limiter that keeps
    # a curious dashboard from turning the profiler into a DoS.
    profile_capture_s: float = 60.0

    def validate(self) -> None:
        if self.behaviors.batch_limit > MAX_BATCH_SIZE:
            raise ValueError(
                f"behaviors.batch_limit cannot exceed '{MAX_BATCH_SIZE}'"
            )
        if self.behaviors.circuit_threshold < 0:
            raise ValueError("behaviors.circuit_threshold cannot be negative")
        if self.behaviors.circuit_open_s <= 0:
            raise ValueError("behaviors.circuit_open_s must be positive")
        if self.behaviors.link_retry_s <= 0:
            raise ValueError("behaviors.link_retry_s must be positive")
        if self.behaviors.default_deadline_ms < 0:
            raise ValueError(
                "behaviors.default_deadline_ms cannot be negative")
        if self.behaviors.min_hop_budget_ms <= 0:
            raise ValueError("behaviors.min_hop_budget_ms must be positive")
        if self.behaviors.max_pending < 0:
            raise ValueError("behaviors.max_pending cannot be negative "
                             "(0 disables admission control)")
        if not 0.0 < self.behaviors.brownout_fraction <= 1.0:
            raise ValueError(
                "behaviors.brownout_fraction must be in (0, 1]")
        if self.behaviors.autopilot_interval_s <= 0:
            raise ValueError(
                "behaviors.autopilot_interval_s must be positive")
        if self.behaviors.autopilot_dwell_s <= 0:
            raise ValueError("behaviors.autopilot_dwell_s must be positive")
        if self.behaviors.autopilot_cooldown_s <= 0:
            raise ValueError(
                "behaviors.autopilot_cooldown_s must be positive")
        if self.behaviors.autopilot_freeze_hold_s < 0:
            raise ValueError(
                "behaviors.autopilot_freeze_hold_s cannot be negative")
        if self.behaviors.hot_lease_rate <= 0:
            raise ValueError("behaviors.hot_lease_rate must be positive")
        if self.behaviors.hot_lease_window_s <= 0:
            raise ValueError("behaviors.hot_lease_window_s must be positive")
        if self.behaviors.hot_lease_ttl_s <= 0:
            raise ValueError("behaviors.hot_lease_ttl_s must be positive")
        if not 0.0 < self.behaviors.hot_lease_fraction <= 1.0:
            raise ValueError(
                "behaviors.hot_lease_fraction must be in (0, 1]")
        if self.behaviors.reshard_ttl_s <= 0:
            raise ValueError("behaviors.reshard_ttl_s must be positive")
        if not 0 < self.behaviors.reshard_chunk_rows <= 8192:
            raise ValueError(
                "behaviors.reshard_chunk_rows must be in [1, 8192]")
        if self.behaviors.reshard_grace_s < 0:
            raise ValueError(
                "behaviors.reshard_grace_s cannot be negative")
        if self.anomaly_interval_s <= 0:
            raise ValueError("anomaly_interval_s must be positive")
        if self.slo_target_ms <= 0:
            raise ValueError("slo_target_ms must be positive")
        if not 0.0 < self.slo_objective < 1.0:
            raise ValueError("slo_objective must be in (0, 1)")
        if self.history_tick_s <= 0:
            raise ValueError("history_tick_s must be positive")
        if self.history_retention_s < self.history_tick_s:
            raise ValueError(
                "history_retention_s must be >= history_tick_s")
        if self.keyspace_interval_s <= 0:
            raise ValueError("keyspace_interval_s must be positive")
        if self.keyspace_top_k < 1:
            raise ValueError("keyspace_top_k must be >= 1")
        if self.capacity_horizon_s <= 0:
            raise ValueError("capacity_horizon_s must be positive")
        if self.profile_capture_s <= 0:
            raise ValueError("profile_capture_s must be positive")
