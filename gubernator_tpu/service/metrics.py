"""Prometheus metrics exposition.

Metric names mirror the reference so dashboards carry over
(reference: prometheus.go:51-64 grpc stats; cache.go:87-95 cache collectors;
global.go:45-51 GLOBAL histograms), plus TPU-specific engine metrics
(decision throughput, kernel rounds) the reference has no analogue for.
"""

from __future__ import annotations

import time
from typing import Optional

import grpc
from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

CONTENT_TYPE_LATEST = "text/plain; version=0.0.4; charset=utf-8"


class Metrics:
    """One registry per daemon (keeps in-process cluster tests isolated)."""

    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        # (reference: prometheus.go:51-60)
        self.grpc_request_counts = Counter(
            "grpc_request_counts", "GRPC requests by status.",
            ["status", "method"], registry=self.registry,
        )
        self.grpc_request_duration = Histogram(
            "grpc_request_duration_milliseconds",
            "GRPC request durations in milliseconds.",
            ["method"], registry=self.registry,
            buckets=(0.1, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 500, 1000),
        )
        # (reference: cache.go:87-95)
        self.cache_size = Gauge(
            "cache_size", "The number of items in the cache.",
            registry=self.registry,
        )
        self.cache_access_count = Counter(
            "cache_access_count", "Cache access counts.",
            ["type"], registry=self.registry,
        )
        # (reference: global.go:45-51)
        self.async_durations = Histogram(
            "async_durations", "The duration of GLOBAL async sends in seconds.",
            registry=self.registry,
        )
        self.broadcast_durations = Histogram(
            "broadcast_durations",
            "The duration of GLOBAL broadcasts to peers in seconds.",
            registry=self.registry,
        )
        # combiner batch window (service/combiner.py — live counters, the
        # combiner increments these directly; no mirroring)
        self.combiner_submissions = Counter(
            "combiner_submissions_total",
            "Caller submissions into the flat-combining batch window.",
            registry=self.registry,
        )
        self.combiner_windows = Counter(
            "combiner_windows_total",
            "Batch windows executed against the device backend.",
            registry=self.registry,
        )
        self.combiner_merged_windows = Counter(
            "combiner_merged_windows_total",
            "Windows that merged more than one submission.",
            registry=self.registry,
        )
        self.combiner_wait_ms = Histogram(
            "combiner_wait_milliseconds",
            "Per-submission enqueue->launch wait inside the combiner.",
            registry=self.registry,
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100),
        )
        self.combiner_window_items = Histogram(
            "combiner_window_items",
            "Requests per executed combiner window (batch occupancy).",
            registry=self.registry,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096),
        )
        # depth-N pipelined serving loop (service/combiner.py — live)
        self.combiner_pipeline_depth = Gauge(
            "combiner_pipeline_depth",
            "Configured cycles-in-flight bound of the pipelined combiner "
            "(1 = serial lock-step).",
            registry=self.registry,
        )
        self.combiner_pipeline_inflight = Gauge(
            "combiner_pipeline_inflight",
            "Launches currently in flight between dispatch and readback.",
            registry=self.registry,
        )
        self.combiner_pipeline_occupancy = Histogram(
            "combiner_pipeline_occupancy",
            "In-flight launches observed at each pipeline launch.",
            registry=self.registry,
            buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16),
        )
        self.combiner_fill_stalls = Counter(
            "combiner_fill_stalls_total",
            "Launches that blocked on the in-flight backpressure cap.",
            registry=self.registry,
        )
        self.combiner_pipelined_windows = Counter(
            "combiner_pipelined_windows_total",
            "Windows launched through the depth-N pipeline (vs the serial "
            "lock-step path).",
            registry=self.registry,
        )
        self.combiner_group_windows = Histogram(
            "combiner_group_windows",
            "Windows coalesced into one scan-group device launch.",
            registry=self.registry,
            buckets=(1, 2, 4, 8, 16, 32),
        )
        # engine hot-path phase instrumentation (models/engine.py — live)
        self.engine_device_dispatch_ms = Histogram(
            "engine_device_dispatch_milliseconds",
            "Per-window device kernel dispatch + readback wall time.",
            registry=self.registry,
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 500),
        )
        self.engine_window_lanes = Histogram(
            "engine_window_lanes",
            "Live lanes per dispatched kernel window.",
            registry=self.registry,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096, 8192),
        )
        self.engine_kernel_dispatches = Counter(
            "engine_kernel_dispatch_total",
            "Device kernel windows by kernel variant and staging width "
            "(process-wide: in-process clusters share the jit caches and "
            "this registry with them).",
            ["kernel", "width"], registry=self.registry,
        )
        self.engine_key_table_size = Gauge(
            "engine_key_table_size",
            "Distinct keys currently holding a device table slot.",
            registry=self.registry,
        )
        # the non-owner GLOBAL broadcast mirror (cache_size itself now
        # reports the engine key table — the authoritative cache here)
        self.global_cache_size = Gauge(
            "global_cache_size",
            "Non-owner GLOBAL statuses cached from owner broadcasts.",
            registry=self.registry,
        )
        # host-tier GLOBAL pipelines (service/global_manager.py)
        self.global_queue_depth = Gauge(
            "global_queue_depth",
            "Keys pending in the GLOBAL pipelines at scrape time.",
            ["pipeline"], registry=self.registry,
        )
        self.global_manager = {
            name: Counter(
                f"global_{name}_total", help_, registry=self.registry)
            for name, help_ in (
                ("hits_sent", "Aggregated GLOBAL hits relayed to owners."),
                ("broadcasts_sent",
                 "GLOBAL broadcast pushes delivered to peers."),
                ("broadcast_errors", "Failed GLOBAL broadcast pushes."),
            )
        }
        # native peerlink transport (service/peerlink.py)
        self.peerlink = {
            name: Counter(
                f"peerlink_{name}_total", help_, registry=self.registry)
            for name, help_ in (
                ("batches", "Aggregated pulls served by the link workers."),
                ("requests", "Requests carried by those pulls."),
                ("errors", "Worker batch/send failures."),
                ("leftover_items", "Items a columnar chunk handed back as "
                 "leftovers for the request-object path (later occurrences "
                 "of a key in the chunk, flagged or invalid lanes)."),
            )
        }
        self.peerlink_stage_ms = Histogram(
            "peerlink_stage_milliseconds",
            "Peerlink worker time per pull: decode, handle and post.",
            ["stage"], registry=self.registry,
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 100),
        )
        # depth-N pipelined columnar serving (service/peerlink.py
        # _columnar_chunk — the zero-object twin of the combiner_pipeline_*
        # families; knobs are shared, see docs/OPERATIONS.md)
        self.peerlink_columnar_depth = Gauge(
            "peerlink_columnar_depth",
            "Configured in-flight bound of the pipelined columnar path "
            "(1 = serial lock-step submit/complete).",
            registry=self.registry,
        )
        self.peerlink_columnar_windows = Counter(
            "peerlink_columnar_windows_total",
            "Columnar sub-windows launched through the depth-N pipeline.",
            registry=self.registry,
        )
        self.peerlink_columnar_group_windows = Histogram(
            "peerlink_columnar_group_windows",
            "Columnar sub-windows coalesced into one scan-group launch.",
            registry=self.registry,
            buckets=(1, 2, 4, 8, 16, 32),
        )
        self.peerlink_columnar_occupancy = Histogram(
            "peerlink_columnar_occupancy",
            "In-flight columnar launches observed at each launch.",
            registry=self.registry,
            buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16),
        )
        self.peerlink_columnar_fill_stalls = Counter(
            "peerlink_columnar_fill_stalls_total",
            "Columnar launches that waited on a readback because the "
            "in-flight bound was reached (the link, not host prep, gates "
            "the wire path).",
            registry=self.registry,
        )
        self.peerlink_columnar_cuts = Counter(
            "peerlink_columnar_cuts_total",
            "Scan groups cut by the leftover-demotion barrier (duplicate "
            "keys, gregorian, GLOBAL lanes force a pipeline drain).",
            registry=self.registry,
        )
        # wire contract v2 (docs/wire.md; service/peerlink.py _worker).
        # pull_boundary_stalls counts the moments the worker had launches
        # in flight and nothing new to pull, and fell back to draining the
        # oldest readback: it fires when the link itself runs dry.
        self.peerlink_pull_boundary_stalls = Counter(
            "peerlink_pull_boundary_stalls_total",
            "Worker iterations stalled at a pull boundary waiting on "
            "readbacks with no new requests to launch.",
            registry=self.registry,
        )
        self.peerlink_wire_version = Gauge(
            "peerlink_wire_version",
            "Negotiated peerlink wire contract per peer (0 = no live "
            "link, 1 = whole-frame, 2 = partial posts).",
            ["peer"], registry=self.registry,
        )
        self.peerlink_partial_span_items = Histogram(
            "peerlink_partial_span_items",
            "Rows per pls_send_partial post (v2 sub-window spans).",
            registry=self.registry,
            buckets=(1, 8, 32, 64, 128, 256, 512, 1024),
        )
        # peer-failure resilience (service/peer_client.py CircuitBreaker +
        # instance.py degraded-local serving; docs/OPERATIONS.md "Failure
        # modes"). circuit_open_total is LIVE (the breaker increments it at
        # the open transition); circuit_state refreshes at exposition.
        self.circuit_state = Gauge(
            "circuit_state",
            "Per-peer circuit breaker state (0=closed, 1=half-open, "
            "2=open).",
            ["peer"], registry=self.registry,
        )
        self.circuit_open = Counter(
            "circuit_open_total",
            "Circuit-breaker transitions to open, per peer (closed->open "
            "on consecutive transport failures, half-open->open on a "
            "failed recovery probe).",
            ["peer"], registry=self.registry,
        )
        self.degraded_local = Counter(
            "degraded_local_total",
            "Forwarded requests served locally as-if-owner because the "
            "owner's circuit was open (GUBER_DEGRADED_LOCAL=1).",
            registry=self.registry,
        )
        # deadline budgets + admission control (service/deadline.py,
        # instance.py AdmissionController; docs/OPERATIONS.md "Overload &
        # deadlines"). All incremented live at the choke points.
        self.deadline_expired = Counter(
            "deadline_expired_total",
            "Requests shed because their deadline budget expired, by "
            "stage (ingress = surface pre-dispatch, queue = combiner "
            "dequeue, forward = router/peer-call pre-send, batch = "
            "micro-batch flush).",
            ["stage"], registry=self.registry,
        )
        self.admission_shed = Counter(
            "admission_shed_total",
            "Work refused by the admission controller, by pressure level "
            "(reason: brownout = 75% of GUBER_MAX_PENDING, saturated = "
            "at/over it) and work class (priority: forward = non-owner "
            "forwards, broadcast = GLOBAL async broadcasts, peer = "
            "forwarded owner batches, ingress = whole public calls).",
            ["reason", "priority"], registry=self.registry,
        )
        self.admission_pending = Gauge(
            "admission_pending",
            "Pending work the admission controller weighs against "
            "GUBER_MAX_PENDING: combiner backlog + in-flight forwards + "
            "GLOBAL pipeline depth (refreshed at scrape).",
            registry=self.registry,
        )
        # hot-key lease tier (service/leases.py; docs/OPERATIONS.md
        # "Skew & leases"). Counters increment live at the lease manager;
        # the gauges refresh at scrape (observe_instance).
        self.lease_grants = Counter(
            "lease_grants_total",
            "Hot-key lease grants minted by this node as an owner (each "
            "hands a budget slice of the key's remaining limit to a "
            "non-owner for one TTL).",
            registry=self.registry,
        )
        self.lease_installs = Counter(
            "lease_installs_total",
            "Lease grants installed/renewed by this node as a non-owner "
            "(arrived on forward responses or async-hit drain responses).",
            registry=self.registry,
        )
        self.lease_local_answers = Counter(
            "lease_local_answers_total",
            "Requests answered locally from held lease budget instead of "
            "forwarding to the owner.",
            registry=self.registry,
        )
        self.lease_drained_hits = Counter(
            "lease_drained_hits_total",
            "Hits consumed against held leases and drained back to their "
            "owners through the GLOBAL async-hit pipeline.",
            registry=self.registry,
        )
        self.lease_expired = Counter(
            "lease_expired_total",
            "Held leases that died at their TTL without renewal (the "
            "fail-closed path: an unreachable or browned-out owner stops "
            "renewing and the key falls back to strict forwarding).",
            registry=self.registry,
        )
        self.lease_shed = Counter(
            "lease_shed_total",
            "Lease grants/renewals refused by reason (brownout = grants "
            "shed first under admission pressure).",
            ["reason"], registry=self.registry,
        )
        self.lease_outstanding_budget = Gauge(
            "lease_outstanding_budget",
            "Unexpired granted budget outstanding on this owner — the "
            "node's current worst-case over-admission bound "
            "(limit + this value).",
            registry=self.registry,
        )
        self.lease_held_keys = Gauge(
            "lease_held_keys",
            "Keys this non-owner currently serves from a live lease.",
            registry=self.registry,
        )
        self.lease_hot_keys = Gauge(
            "lease_hot_keys",
            "Keys the hot-key tracker currently flags as over the "
            "GUBER_HOT_LEASE_RATE detection threshold.",
            registry=self.registry,
        )
        # live-resharding handoff plane (service/reshard.py;
        # docs/OPERATIONS.md "Deploys & resharding"). Counters increment
        # live at the reshard manager; the gauge refreshes at scrape.
        self.reshard_transfers = Counter(
            "reshard_transfers_total",
            "Handoff sessions opened, by role (export = this node is the "
            "departing owner streaming rows out; import = receiving).",
            ["role"], registry=self.registry,
        )
        self.reshard_committed = Counter(
            "reshard_committed_total",
            "Handoff sessions that completed: every planned key streamed "
            "and acknowledged, ownership fully transferred.",
            ["role"], registry=self.registry,
        )
        self.reshard_aborted = Counter(
            "reshard_aborted_total",
            "Handoff sessions that failed-closed, by reason (ttl_expired, "
            "frame_failed, superseded, shutdown, ...). Aborted keys "
            "degrade to the pre-reshard amnesty, never to over-admission.",
            ["role", "reason"], registry=self.registry,
        )
        self.reshard_rows_moved = Counter(
            "reshard_rows_moved_total",
            "Counter rows carried across handoff transfer frames.",
            ["role"], registry=self.registry,
        )
        self.reshard_transfer_bytes = Counter(
            "reshard_transfer_bytes_total",
            "Transfer-frame payload bytes moved by the handoff plane.",
            ["role"], registry=self.registry,
        )
        self.reshard_frames = Counter(
            "reshard_frames_total",
            "Sequence-numbered transfer frames sent (export) or accepted "
            "(import); each accepted frame renews the transfer lease.",
            ["role"], registry=self.registry,
        )
        self.reshard_proxied = Counter(
            "reshard_proxied_total",
            "Requests resolved over the handoff double-write window: "
            "import = a new owner asked the previous owner to decide a "
            "not-yet-transferred key; export = a departing owner forwarded "
            "a stale arrival to the new owner.",
            ["role"], registry=self.registry,
        )
        self.reshard_fresh_serves = Counter(
            "reshard_fresh_serves_total",
            "Moving keys served from a fresh bucket because the handoff "
            "protocol was dead for them, by reason — the bounded amnesty "
            "the protocol fail-closes to, never over-admission.",
            ["reason"], registry=self.registry,
        )
        self.reshard_cut_wait_timeouts = Counter(
            "reshard_cut_wait_timeouts_total",
            "Requests that waited out the in-flight-chunk cap before the "
            "key's transferred row landed and served fresh instead.",
            registry=self.registry,
        )
        self.reshard_double_write_window_s = Histogram(
            "reshard_double_write_window_seconds",
            "Wall-clock length of each handoff session's double-write "
            "window (begin to commit/abort).",
            ["role"], registry=self.registry,
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
        )
        self.reshard_active = Gauge(
            "reshard_active",
            "1 while this node has a handoff in flight (planning, "
            "streaming, lingering, or inside the importer grace window).",
            registry=self.registry,
        )
        # observability plane (obs/events.py flight recorder, obs/anomaly.py
        # watchers; docs/OPERATIONS.md "Incident response"). Recorder totals
        # refresh at scrape from the ring's own counters; anomaly gauges are
        # written by the engine on every check AND refreshed at scrape so a
        # metrics-only deployment still sees them.
        self.flight_recorder_events = Counter(
            "flight_recorder_events_total",
            "Structured events emitted into the flight-recorder ring since "
            "boot (the ring itself only retains the newest window).",
            registry=self.registry,
        )
        self.flight_recorder_dropped = Counter(
            "flight_recorder_dropped_total",
            "Flight-recorder events evicted by the bounded ring (oldest "
            "out as newer events arrive).",
            registry=self.registry,
        )
        self.anomaly_active = Gauge(
            "anomaly_active",
            "Anomaly watcher state per detector (1 = currently firing). "
            "Rising edges also write a diagnostic bundle when "
            "GUBER_BUNDLE_DIR is set.",
            ["detector"], registry=self.registry,
        )
        self.anomaly_trips = Counter(
            "anomaly_trips_total",
            "Rising-edge anomaly detections per detector since boot.",
            ["detector"], registry=self.registry,
        )
        self.slo_burn_rate = Gauge(
            "slo_burn_rate",
            "Error-budget burn rate of the serving SLO over the fast/slow "
            "alert windows (1.0 = burning exactly the sustainable rate; "
            "the slo_burn detector fires when BOTH windows exceed their "
            "thresholds).",
            ["window"], registry=self.registry,
        )
        self.bundles_written = Counter(
            "debug_bundles_written_total",
            "Diagnostic bundles written to GUBER_BUNDLE_DIR (anomaly "
            "triggers plus explicit /v1/debug/bundle?write=1 requests).",
            registry=self.registry,
        )
        # capacity & keyspace cartography (obs/history.py, obs/keyspace.py;
        # docs/observability.md "Capacity & keyspace"). The scrape itself
        # drives the cartographer's piggyback harvest (maybe_harvest), so a
        # metrics-only deployment still gets fresh cartography; gauges
        # refresh from the newest harvest + forecast at exposition.
        self.history_samples = Gauge(
            "history_samples",
            "Samples currently held by the on-node metrics-history ring "
            "(/v1/debug/history).",
            registry=self.registry,
        )
        self.keyspace_harvests = Counter(
            "keyspace_harvests_total",
            "Keyspace cartography harvests completed since boot.",
            registry=self.registry,
        )
        self.keyspace_fill_fraction = Gauge(
            "keyspace_fill_fraction",
            "Key-table occupancy as a fraction of device-table capacity "
            "(from the newest keyspace harvest).",
            registry=self.registry,
        )
        self.keyspace_free_slots = Gauge(
            "keyspace_free_slots",
            "Device-table slots still unclaimed at the newest harvest.",
            registry=self.registry,
        )
        self.keyspace_evictions = Counter(
            "keyspace_evictions_total",
            "Cumulative key-directory LRU evictions (slots recycled "
            "because the table was full).",
            registry=self.registry,
        )
        self.keyspace_hit_share = Gauge(
            "keyspace_hit_share",
            "Share of tracked hit mass concentrated in the hottest keys, "
            "by bucket (top1/top10/top100).",
            ["bucket"], registry=self.registry,
        )
        self.keyspace_zipf_exponent = Gauge(
            "keyspace_zipf_exponent",
            "Zipf exponent fitted over the head of the rank/count curve "
            "(higher = more skew; ~0 = uniform).",
            registry=self.registry,
        )
        self.hbm_table_bytes = Gauge(
            "hbm_table_bytes",
            "Device memory held by the backend's table arrays, by "
            "component (state; fps/touch on the devdir engine).",
            ["component"], registry=self.registry,
        )
        self.keyspace_growth = Gauge(
            "keyspace_growth_keys_per_s",
            "Net key-table growth fitted over the metrics-history ring "
            "(keys/second; negative while the table drains).",
            registry=self.registry,
        )
        self.capacity_time_to_full = Gauge(
            "capacity_time_to_full_seconds",
            "Projected seconds until the key table is full at the fitted "
            "growth rate (-1 = not projectable / not growing).",
            registry=self.registry,
        )
        self.capacity_time_to_pressure = Gauge(
            "capacity_time_to_pressure_seconds",
            "Projected seconds until the table crosses the eviction-"
            "pressure watermark (0 = already there or actively evicting; "
            "-1 = not projectable / not growing).",
            registry=self.registry,
        )
        # autopilot (service/autopilot.py; docs/observability.md
        # "Autopilot"). The scrape drives maybe_tick for threadless
        # deployments (same contract as anomaly.maybe_check).
        self.autopilot_moves = Counter(
            "autopilot_moves_total",
            "Knob moves the autopilot actually applied, by controller "
            "and knob (every one is also an autopilot.move recorder "
            "event with the triggering signal attached).",
            ["controller", "knob"], registry=self.registry,
        )
        self.autopilot_clamps = Counter(
            "autopilot_clamps_total",
            "Autopilot move proposals limited by a knob's declared "
            "[floor, ceiling] band or absolute validity range.",
            ["controller", "knob"], registry=self.registry,
        )
        self.autopilot_freezes = Counter(
            "autopilot_freezes_total",
            "Actuation freeze windows entered (reshard transfer in "
            "flight or membership flip); frozen intents are dropped.",
            registry=self.registry,
        )
        self.autopilot_frozen = Gauge(
            "autopilot_frozen",
            "1 while the autopilot is holding all knobs still (reshard "
            "transfer or membership-change hold window).",
            registry=self.registry,
        )
        self.autopilot_engaged = Gauge(
            "autopilot_engaged",
            "Per-controller engagement state (1 = the controller's "
            "signal tripped and held past the dwell; it is steering its "
            "knobs toward the engaged side of the band).",
            ["controller"], registry=self.registry,
        )
        self.autopilot_knob = Gauge(
            "autopilot_knob",
            "Live value of each controller-actuated knob (the same "
            "value the serving path reads from conf.behaviors).",
            ["knob"], registry=self.registry,
        )
        self.request_budget_ms = Histogram(
            "request_budget_ms",
            "Deadline budget observed at capture, by surface (public = "
            "ingress gRPC/HTTP, peer = decremented hop budget received "
            "over gRPC metadata or the peerlink carrier).",
            ["surface"], registry=self.registry,
            buckets=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000,
                     10000),
        )
        # TPU-native engine metrics (no reference analogue)
        self.engine_decisions = Counter(
            "engine_decisions_total",
            "Rate-limit decisions applied by the device kernel.",
            registry=self.registry,
        )
        self.engine_kernel_rounds = Counter(
            "engine_kernel_rounds_total",
            "Device kernel launches (collision-free rounds).",
            registry=self.registry,
        )
        self.engine_over_limit = Counter(
            "engine_over_limit_total", "Decisions that returned OVER_LIMIT.",
            registry=self.registry,
        )
        self.engine_stage_seconds = Counter(
            "engine_stage_seconds_total",
            "Cumulative wall-clock per engine pipeline stage "
            "(prep/lookup/pack/device/demux).",
            ["stage"], registry=self.registry,
        )
        # sharded-backend GLOBAL pipeline (parallel/sharded.py stats)
        self.engine_global_syncs = Counter(
            "engine_global_syncs_total",
            "GLOBAL psum sync windows run by the mesh backend.",
            registry=self.registry,
        )
        self.engine_global_mirror_answers = Counter(
            "engine_global_mirror_answers_total",
            "GLOBAL requests answered from the replicated mirror.",
            registry=self.registry,
        )
        self.engine_global_hits_queued = Counter(
            "engine_global_hits_queued_total",
            "GLOBAL hits queued for the next mesh sync window.",
            registry=self.registry,
        )
        self.engine_global_evictions = Counter(
            "engine_global_evictions_total",
            "GLOBAL registry entries evicted (idle sweep or LRU-on-full).",
            registry=self.registry,
        )
        self.engine_global_registry_fallbacks = Counter(
            "engine_global_registry_fallbacks_total",
            "New GLOBAL keys served authoritatively because every registry "
            "slot still held unsynced hits.",
            registry=self.registry,
        )
        self.engine_global_registry_size = Gauge(
            "engine_global_registry_size",
            "Registered GLOBAL keys currently tracked by the mesh backend.",
            registry=self.registry,
        )
        # cross-host collective GLOBAL transport (collective_global.py)
        self.cross_host = {
            name: Counter(
                f"cross_host_{name}_total", help_, registry=self.registry)
            for name, help_ in (
                ("ticks", "Lockstep collective GLOBAL sync ticks."),
                ("hits_synced", "GLOBAL hits delivered over the collective."),
                ("deltas_applied",
                 "Remote GLOBAL hits applied by this owner host."),
                ("broadcasts_applied",
                 "Authoritative GLOBAL states installed from the collective."),
                ("conflicts", "Slot claim conflicts (keys demoted to gRPC)."),
                ("fallbacks", "GLOBAL keys using the gRPC pipelines."),
                ("hunt_moves",
                 "Non-owner candidate moves hunting the owner's slot."),
                ("repromotions",
                 "Demoted keys re-promoted to the collective tier."),
            )
        }
        self.cross_host_fallback_fraction = Gauge(
            "cross_host_fallback_fraction",
            "Fraction of registered GLOBAL keys currently demoted to the "
            "gRPC pipelines (0 = every key rides the collective).",
            registry=self.registry,
        )
        # multi-region replication loss accounting (multiregion.py)
        self.multiregion = {
            name: Counter(
                f"multiregion_{name}_total", help_, registry=self.registry)
            for name, help_ in (
                ("replicated", "Aggregates replicated to foreign regions."),
                ("errors", "Failed region replication sends."),
                ("refunded_hits",
                 "Hits deferred into the region's next window after a "
                 "PRE-send failure (may still drop if the retry fails)."),
                ("dropped_hits",
                 "Hits lost to a region: delivery-uncertain send failure, "
                 "failed retry of a deferred window, or unroutable."),
            )
        }
        # continuous profiling plane (obs/profile.py): cumulative phase
        # time mirrors of the live per-phase histograms, refreshed at
        # scrape — rate(profile_phase_seconds_total[1m]) /
        # rate(profile_phase_windows_total[1m]) is the live mean
        self.profile_phase_seconds = Counter(
            "profile_phase_seconds_total",
            "Serving-cycle time attributed to each profiler phase.",
            ["phase"],
            registry=self.registry,
        )
        self.profile_phase_windows = Counter(
            "profile_phase_windows_total",
            "Profiler observations per serving-cycle phase.",
            ["phase"],
            registry=self.registry,
        )
        self.engine_lock_wait_seconds = Counter(
            "engine_lock_wait_seconds_total",
            "Engine-lock acquire wait attributed to each call site.",
            ["site"],
            registry=self.registry,
        )
        self.engine_lock_waits = Counter(
            "engine_lock_waits_total",
            "Engine-lock acquisitions timed per call site.",
            ["site"],
            registry=self.registry,
        )
        self.background_seconds = Counter(
            "background_seconds_total",
            "Time the background tickers spent per site (each site's own "
            "time: nested units are counted at their own site).",
            ["site"],
            registry=self.registry,
        )
        self.engine_kernel_dispatch_seconds = Counter(
            "engine_kernel_dispatch_seconds_total",
            "Wall time inside jitted decide-kernel dispatch calls, per "
            "compiled (kernel, width) program.",
            ["kernel", "width"],
            registry=self.registry,
        )
        # decision ledger & budget-conservation audit plane (obs/ledger.py;
        # docs/observability.md "Decision ledger"). Cumulative mirrors of the
        # ledger's lock-free totals, refreshed at scrape; the per-authority
        # admit split is the "who let this hit through" attribution.
        self.ledger_admits = Counter(
            "ledger_admits_total",
            "Admitted hits attributed at decision time to their source of "
            "authority (owner = owner-window device decision, lease = held "
            "lease slice, degraded = degraded-local as-if-owner, reshard = "
            "handoff double-write/amnesty, global_cache = non-owner GLOBAL "
            "broadcast cache, mint = test-only drill authority).",
            ["authority"], registry=self.registry,
        )
        self.ledger_attempted_hits = Counter(
            "ledger_attempted_hits_total",
            "Hits attempted against windows the ledger observed "
            "(admitted + rejected).",
            registry=self.registry,
        )
        self.ledger_rejected_hits = Counter(
            "ledger_rejected_hits_total",
            "Hits the ledger observed being rejected (OVER_LIMIT).",
            registry=self.registry,
        )
        self.ledger_minted_budget = Counter(
            "ledger_minted_budget_total",
            "Lease budget minted to this node by owners (recorded at "
            "grant install/renewal) — the declared extra admission "
            "headroom the conservation audit allows.",
            registry=self.registry,
        )
        self.ledger_windows_audited = Counter(
            "ledger_windows_audited_total",
            "Closed key-windows rolled through the conservation audit.",
            registry=self.registry,
        )
        self.ledger_violations = Counter(
            "ledger_violations_total",
            "Audited key-windows whose admitted hits exceeded "
            "limit + minted budget + declared slack — the 'never mint "
            "budget' invariant observed failing.",
            registry=self.registry,
        )
        self.ledger_overshoot_hits = Counter(
            "ledger_overshoot_hits_total",
            "Total hits admitted beyond limit + minted budget across "
            "audited windows (the over-admission mass, before slack).",
            registry=self.registry,
        )
        self.ledger_keys_tracked = Gauge(
            "ledger_keys_tracked",
            "Distinct key-windows currently held by the ledger between "
            "audits.",
            registry=self.registry,
        )

    def set_native_front(self, hits_fn) -> None:
        """Register the native gRPC front's IO-thread decision counter
        (RPCs answered entirely in C never reach the Python counters)."""
        self._native_front_hits = hits_fn

    def set_peerlink_stats(self, stats_fn) -> None:
        """Register a PeerLinkService's stats-dict supplier so the link's
        batch/request/error totals export as peerlink_* families."""
        self._peerlink_stats = stats_fn

    def observe_instance(self, instance) -> None:
        """Refresh gauges from live objects before exposition."""
        hits_fn = getattr(self, "_native_front_hits", None)
        if hits_fn is not None:
            try:
                self._set_counter(
                    self.grpc_request_counts.labels(
                        status="ok", method="GetRateLimits/native"),
                    float(hits_fn()))
            except Exception:  # noqa: BLE001 — a closing front must not
                pass           # break /metrics
        stats = getattr(instance.backend, "stats", None)
        if stats is not None:
            d = stats.as_dict() if hasattr(stats, "as_dict") else dict(stats)
            self._set_counter(self.engine_decisions, d.get("requests", 0))
            self._set_counter(self.engine_kernel_rounds, d.get("rounds", 0))
            self._set_counter(self.engine_over_limit, d.get("over_limit", 0))
            from gubernator_tpu.models.engine import EngineStats

            for stage in EngineStats.STAGES:
                ns = d.get(f"{stage}_ns")
                if ns is not None:
                    self._set_counter(
                        self.engine_stage_seconds.labels(stage=stage),
                        ns / 1e9)
            self._set_counter(
                self.engine_global_syncs, d.get("global_syncs", 0))
            self._set_counter(
                self.engine_global_mirror_answers,
                d.get("global_mirror_answers", 0))
            self._set_counter(
                self.engine_global_hits_queued,
                d.get("global_hits_queued", 0))
            self._set_counter(
                self.engine_global_evictions,
                d.get("global_evictions", 0))
            self._set_counter(
                self.engine_global_registry_fallbacks,
                d.get("global_registry_fallbacks", 0))
        registry_size = getattr(instance.backend, "global_registry_size", None)
        if callable(registry_size):
            self.engine_global_registry_size.set(registry_size())
        # kernel dispatch mix (ops/decide.py kernel_telemetry)
        from gubernator_tpu.ops.decide import kernel_telemetry

        for (kernel, width), n in kernel_telemetry.counts().items():
            self._set_counter(
                self.engine_kernel_dispatches.labels(
                    kernel=kernel, width=str(width)), n)
        for (kernel, width), (n, total_ns) in \
                kernel_telemetry.dispatch_totals().items():
            self._set_counter(
                self.engine_kernel_dispatch_seconds.labels(
                    kernel=kernel, width=str(width)), total_ns / 1e9)
        # profiling plane: phase + lock-site cumulative mirrors
        prof = getattr(instance, "profiler", None) \
            or getattr(instance.backend, "profiler", None)
        if prof is not None:
            for phase, t in prof.totals().items():
                self._set_counter(
                    self.profile_phase_seconds.labels(phase=phase),
                    t["total_ns"] / 1e9)
                self._set_counter(
                    self.profile_phase_windows.labels(phase=phase),
                    float(t["n"]))
            for site, t in prof.site_totals().items():
                self._set_counter(
                    self.engine_lock_wait_seconds.labels(site=site),
                    t["total_ns"] / 1e9)
                self._set_counter(
                    self.engine_lock_waits.labels(site=site),
                    float(t["n"]))
            for site, t in prof.background_totals().items():
                self._set_counter(
                    self.background_seconds.labels(site=site),
                    t["total_ns"] / 1e9)
        # live key-table occupancy: the engine directory IS the cache here,
        # so cache_size (reference: cache.go:87-95) reports it
        from gubernator_tpu.obs.introspect import key_table_size

        occupancy = key_table_size(instance.backend)
        if occupancy is not None:
            self.engine_key_table_size.set(occupancy)
            self.cache_size.set(occupancy)
        all_peers = getattr(instance, "all_peer_clients", None)
        if callable(all_peers):
            for peer in all_peers():
                circuit = getattr(peer, "circuit", None)
                if circuit is not None:
                    self.circuit_state.labels(
                        peer=peer.info.address).set(circuit.state)
                wv = getattr(peer, "link_wire_version", None)
                if callable(wv):
                    self.peerlink_wire_version.labels(
                        peer=peer.info.address).set(wv())
        adm = getattr(instance, "admission", None)
        if adm is not None:
            self.admission_pending.set(adm.pending())
        rec = getattr(instance, "recorder", None)
        if rec is not None:
            d = rec.debug()
            self._set_counter(
                self.flight_recorder_events,
                float(sum(d.get("counts", {}).values())))
            self._set_counter(
                self.flight_recorder_dropped, float(d.get("dropped", 0)))
        an = getattr(instance, "anomaly", None)
        if an is not None:
            try:
                # scrapes double as the check tick for threadless
                # deployments (in-process clusters never call start())
                an.maybe_check()
            except Exception:  # noqa: BLE001 — watchers must not break
                pass           # /metrics
            d = an.debug()
            active = set(d.get("active", ()))
            for det in d.get("trips", {}):
                self.anomaly_active.labels(detector=det).set(
                    1.0 if det in active else 0.0)
                self._set_counter(
                    self.anomaly_trips.labels(detector=det),
                    float(d["trips"][det]))
            self.slo_burn_rate.labels(window="fast").set(
                d.get("burn_fast", 0.0))
            self.slo_burn_rate.labels(window="slow").set(
                d.get("burn_slow", 0.0))
        ap = getattr(instance, "autopilot", None)
        if ap is not None and ap.enabled:
            try:
                # scrapes double as the controller tick for threadless
                # deployments (same contract as anomaly.maybe_check);
                # the tick itself refreshes the autopilot gauges
                ap.maybe_tick()
            except Exception:  # noqa: BLE001 — control must not break
                pass           # /metrics
        bw = getattr(instance, "bundle_writer", None)
        if bw is not None:
            self._set_counter(
                self.bundles_written,
                float(bw.stats.get("written", 0)))
        hist = getattr(instance, "history", None)
        if hist is not None:
            try:
                # scrapes double as the history tick for threadless
                # deployments (same contract as anomaly.maybe_check)
                if hist.enabled:
                    hist.tick()
                self.history_samples.set(hist.sample_count())
            except Exception:  # noqa: BLE001 — the ring must not break
                pass           # /metrics
        carto = getattr(instance, "keyspace", None)
        if carto is not None:
            try:
                carto.maybe_harvest()
            except Exception:  # noqa: BLE001 — cartography must not
                pass           # break /metrics
            self._set_counter(self.keyspace_harvests,
                              float(carto.harvests))
            rep = carto.last_report()
            if rep is not None:
                occ = rep.get("occupancy") or {}
                if occ.get("fill_fraction") is not None:
                    self.keyspace_fill_fraction.set(occ["fill_fraction"])
                if occ.get("free_slots") is not None:
                    self.keyspace_free_slots.set(occ["free_slots"])
                ev = (rep.get("evictions") or {}).get("total")
                if ev is not None:
                    self._set_counter(self.keyspace_evictions, float(ev))
                hm = rep.get("hit_mass") or {}
                for bucket in ("top1", "top10", "top100"):
                    share = hm.get(f"{bucket}_share")
                    if share is not None:
                        self.keyspace_hit_share.labels(
                            bucket=bucket).set(share)
                if hm.get("zipf_exponent") is not None:
                    self.keyspace_zipf_exponent.set(hm["zipf_exponent"])
                for comp, nbytes in ((rep.get("hbm") or {}).get(
                        "arrays") or {}).items():
                    self.hbm_table_bytes.labels(component=comp).set(nbytes)
            fc = carto.forecast()
            if fc.get("growth_keys_per_s") is not None:
                self.keyspace_growth.set(fc["growth_keys_per_s"])
            ttf = fc.get("time_to_full_s")
            self.capacity_time_to_full.set(
                ttf if ttf is not None else -1.0)
            ttp = fc.get("time_to_pressure_s")
            self.capacity_time_to_pressure.set(
                ttp if ttp is not None else -1.0)
        gm = getattr(instance, "global_manager", None)
        if gm is not None:
            hits_depth, bcast_depth = gm.depths()
            self.global_queue_depth.labels(pipeline="hits").set(hits_depth)
            self.global_queue_depth.labels(
                pipeline="broadcast").set(bcast_depth)
            for name, counter in self.global_manager.items():
                self._set_counter(counter, gm.stats.get(name, 0))
        link = getattr(self, "_peerlink_stats", None)
        if link is not None:
            for name, counter in self.peerlink.items():
                self._set_counter(counter, link().get(name, 0))
        collective = getattr(instance, "collective_global", None)
        if collective is not None:
            for name, counter in self.cross_host.items():
                self._set_counter(counter, collective.stats.get(name, 0))
            self.cross_host_fallback_fraction.set(
                collective.fallback_fraction())
        mr = getattr(instance, "multiregion_manager", None)
        if mr is not None:
            for name, counter in self.multiregion.items():
                self._set_counter(counter, mr.stats.get(name, 0))
        lm = getattr(instance, "leases", None)
        if lm is not None and lm.enabled:
            self.lease_outstanding_budget.set(lm.outstanding())
            self.lease_held_keys.set(lm.held_count())
            tracker = lm.tracker()
            if tracker is not None:
                self.lease_hot_keys.set(len(tracker.snapshot()))
        rm = getattr(instance, "reshard", None)
        if rm is not None:
            self.reshard_active.set(1 if rm.poll_active() else 0)
        led = getattr(instance, "ledger", None)
        if led is not None and getattr(led, "enabled", False):
            try:
                # scrapes double as the audit tick for threadless
                # deployments (same contract as anomaly.maybe_check)
                led.maybe_audit(getattr(instance, "backend", None))
            except Exception:  # noqa: BLE001 — the audit must not break
                pass           # /metrics
            lt = led.totals()
            for auth, n in lt.get("admits", {}).items():
                self._set_counter(
                    self.ledger_admits.labels(authority=auth), float(n))
            other = lt.get("admits_other", 0)
            if other:  # mint-drill / unknown authorities, folded as "other"
                self._set_counter(
                    self.ledger_admits.labels(authority="other"),
                    float(other))
            self._set_counter(
                self.ledger_attempted_hits, float(lt.get("attempted", 0)))
            self._set_counter(
                self.ledger_rejected_hits, float(lt.get("rejected", 0)))
            self._set_counter(
                self.ledger_minted_budget,
                float(lt.get("minted_budget", 0)))
            self._set_counter(
                self.ledger_windows_audited,
                float(lt.get("windows_rolled", 0)))
            self._set_counter(
                self.ledger_violations, float(lt.get("violations", 0)))
            self._set_counter(
                self.ledger_overshoot_hits,
                float(lt.get("overshoot_hits", 0)))
            self.ledger_keys_tracked.set(float(lt.get("keys_tracked", 0)))
        cache = getattr(instance, "_global_cache", None)
        if cache is not None:
            self.global_cache_size.set(len(cache))
            if occupancy is None:  # no countable engine directory: keep
                self.cache_size.set(len(cache))  # the legacy LRU reading

    @staticmethod
    def _set_counter(counter, value: float) -> None:
        # prometheus counters only go up; engines report monotonic totals
        current = counter._value.get()  # noqa: SLF001
        if value > current:
            counter.inc(value - current)

    def render(self, instance=None) -> bytes:
        if instance is not None:
            self.observe_instance(instance)
        return generate_latest(self.registry)


class GRPCStatsInterceptor(grpc.ServerInterceptor):
    """Per-RPC duration + status counters (reference: prometheus.go:29-138,
    implemented as an interceptor instead of a stats.Handler)."""

    def __init__(self, metrics: Metrics):
        self.metrics = metrics

    def intercept_service(self, continuation, handler_call_details):
        handler = continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler
        method = handler_call_details.method.rsplit("/", 1)[-1]
        inner = handler.unary_unary
        metrics = self.metrics

        def wrapped(request, context):
            start = time.perf_counter()
            try:
                resp = inner(request, context)
                metrics.grpc_request_counts.labels(status="ok", method=method).inc()
                return resp
            except Exception:
                metrics.grpc_request_counts.labels(
                    status="failed", method=method
                ).inc()
                raise
            finally:
                metrics.grpc_request_duration.labels(method=method).observe(
                    (time.perf_counter() - start) * 1e3
                )

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )
