"""GUBER_* environment configuration (reference: cmd/gubernator/config.go).

Same variable names and defaults as the reference daemon, plus TPU-specific
extras (backend selection, table capacity/widths). A `--config` file of
KEY=VALUE lines is loaded INTO the environment before reading, exactly like
the reference (config.go:91-96,306-334).
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import List, Optional

from gubernator_tpu.service.config import BehaviorConfig

_DUR_RE = re.compile(r"(\d+(?:\.\d+)?)(ns|us|µs|ms|s|m|h)")
_DUR_UNITS = {
    "ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3, "s": 1.0,
    "m": 60.0, "h": 3600.0,
}


def parse_duration(text: str) -> float:
    """Go-style duration ('500us', '30s', '1m30s') -> seconds."""
    text = text.strip()
    if not text:
        raise ValueError("empty duration")
    pos = 0
    total = 0.0
    for m in _DUR_RE.finditer(text):
        if m.start() != pos:
            raise ValueError(f"invalid duration {text!r}")
        total += float(m.group(1)) * _DUR_UNITS[m.group(2)]
        pos = m.end()
    if pos != len(text):
        raise ValueError(f"invalid duration {text!r}")
    return total


def load_env_file(path: str) -> None:
    """KEY=VALUE lines -> os.environ (reference: config.go:306-334)."""
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" not in line:
                raise ValueError(f"malformed key=value on line '{lineno}'")
            key, _, value = line.partition("=")
            os.environ[key.strip()] = value.strip()


def _env_str(name: str, default: str = "") -> str:
    return os.environ.get(name, "") or default


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name, "")
    return int(v) if v else default


def _env_dur(name: str, default: float) -> float:
    v = os.environ.get(name, "")
    return parse_duration(v) if v else default


def _env_slice(name: str) -> List[str]:
    v = os.environ.get(name, "")
    return [s.strip() for s in v.split(",") if s.strip()] if v else []


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name, "")
    return float(v) if v else default


def _env_pipeline_depth() -> int:
    """GUBER_PIPELINE_DEPTH: 'auto' (default) -> 0, else a non-negative
    int (1 pins the serial lock-step combiner path)."""
    v = os.environ.get("GUBER_PIPELINE_DEPTH", "").strip().lower()
    if v in ("", "auto"):
        return 0
    depth = int(v)
    if depth < 0:
        raise ValueError(
            f"'GUBER_PIPELINE_DEPTH={v}' is invalid; must be 'auto' or a "
            "non-negative integer")
    return depth


def _env_bool(name: str) -> bool:
    """Go strconv.ParseBool semantics for security-relevant flags: 'false'
    must mean false. (The reference treats ANY non-empty
    GUBER_ETCD_TLS_SKIP_VERIFY as true, config.go:254 — a silent inversion
    of an explicit 'false' we don't reproduce.)"""
    v = os.environ.get(name, "").strip().lower()
    if v in ("", "0", "f", "false", "n", "no"):
        return False
    if v in ("1", "t", "true", "y", "yes"):
        return True
    raise ValueError(f"'{name}={v}' is not a boolean")


@dataclasses.dataclass
class DaemonConfig:
    """(reference: cmd/gubernator/config.go:33-65)"""

    grpc_address: str = "0.0.0.0:81"
    http_address: str = "0.0.0.0:80"
    advertise_address: str = ""
    cache_size: int = 50_000
    data_center: str = ""
    behaviors: BehaviorConfig = dataclasses.field(default_factory=BehaviorConfig)

    # discovery
    peers: List[str] = dataclasses.field(default_factory=list)  # static
    peers_file: str = ""
    gossip_bind: str = ""
    gossip_advertise_port: int = 7946
    gossip_known_nodes: List[str] = dataclasses.field(default_factory=list)
    # GUBER_MEMBERLIST_* speaks the hashicorp/memberlist v0.2.0 wire
    # protocol by default (cluster/memberlist.py) so a node can join a
    # reference fleet; =0 selects the leaner gubernator_tpu-only
    # GossipPool (same role, own wire format).
    memberlist_compat: bool = True
    memberlist_node_name: str = ""  # default: hostname
    # base64 AES key(s) for memberlist packet encryption (16/24/32 bytes
    # decoded), primary first — hashicorp SecretKey/Keyring semantics
    memberlist_secret_keys: List[str] = dataclasses.field(
        default_factory=list)
    etcd_endpoints: List[str] = dataclasses.field(default_factory=list)
    etcd_advertise_address: str = ""  # defaults to advertise_address
    etcd_key_prefix: str = ""  # "" -> the pool's /gubernator/peers/ default
    etcd_dial_timeout_s: float = 5.0
    etcd_user: str = ""
    etcd_password: str = ""
    # TLS to etcd (reference: config.go:203-260); enabled when any
    # GUBER_ETCD_TLS_* variable is set
    etcd_tls_enable: bool = False
    etcd_tls_cert: str = ""
    etcd_tls_key: str = ""
    etcd_tls_ca: str = ""
    etcd_tls_skip_verify: bool = False
    k8s_selector: str = ""
    k8s_namespace: str = ""  # empty -> in-cluster service-account namespace
    k8s_pod_ip: str = ""
    k8s_pod_port: str = ""

    # picker
    peer_picker: str = ""  # "" | consistent-hash | replicated-hash
    peer_picker_hash: str = ""
    replicated_hash_replicas: int = 512

    # TPU backend (no reference analogue): auto | engine | sharded
    backend: str = "auto"
    # serve the public gRPC address from the native HTTP/2 front
    # (native/peerlink.cpp) when available; "0" reverts to grpcio
    grpc_native: bool = True
    device_directory: bool = False  # on-chip key directory (engine only)
    min_batch_width: int = 64
    max_batch_width: int = 8192
    # depth-N pipelined serving loop (service/combiner.py): cycles in
    # flight between kernel launch and readback. 0 = auto (boot-time 3/6
    # probe against the live link); 1 pins the serial lock-step path.
    # pipeline_scan caps the windows coalesced into one scan-group launch.
    pipeline_depth: int = 0
    pipeline_scan: int = 8
    # durable bucket snapshot: load at boot, save at shutdown (FileLoader;
    # the reference leaves persistence to the user, README.md:159-175)
    snapshot_path: str = ""
    snapshot_format: str = "binary"  # or "jsonl" (legacy text format)
    # device-level tracing (no reference analogue): live profiler server
    # port, and a dir for a capture spanning the daemon's lifetime
    profile_port: int = 0
    profile_dir: str = ""
    # request tracing + introspection (obs/; no reference analogue):
    # trace_sample 0.0 disables tracing entirely (hard no-op hot path);
    # slow_request_ms logs a structured JSON event for any traced root
    # request slower than the threshold (0 disables);
    # debug_endpoints gates /v1/debug/vars and /v1/debug/traces
    trace_sample: float = 0.0
    slow_request_ms: float = 0.0
    debug_endpoints: bool = True
    # observability plane (obs/events.py, obs/anomaly.py, obs/bundle.py):
    # flight_recorder is the always-on black box (=0 is the escape hatch);
    # bundle_dir enables anomaly-triggered diagnostic bundles;
    # slow_log_path/max_mb bound the slow-request JSON log on disk
    flight_recorder: bool = True
    flight_recorder_capacity: int = 4096
    bundle_dir: str = ""
    bundle_interval_s: float = 60.0
    bundle_keep: int = 20
    slow_log_path: str = ""
    slow_log_max_mb: float = 64.0
    anomaly_interval_s: float = 5.0
    slo_target_ms: float = 250.0
    slo_objective: float = 0.999
    # capacity & keyspace cartography (obs/history.py, obs/keyspace.py):
    # history is the on-node metrics-history ring (=0 keeps only what the
    # anomaly engine's burn windows need); keyspace_scan is the periodic
    # device-table harvest behind /v1/debug/keyspace (=0 disables);
    # capacity_horizon is how far ahead a projected table-full must land
    # to trip the `capacity` anomaly detector
    history: bool = True
    history_tick_s: float = 5.0
    history_retention_s: float = 7200.0
    keyspace_scan: bool = True
    keyspace_interval_s: float = 60.0
    keyspace_top_k: int = 20
    capacity_horizon_s: float = 1800.0
    # continuous profiling plane (obs/profile.py): profile_enabled is the
    # always-on serving-cycle meter (=0 is the escape hatch — every
    # observation site degrades to one attribute test and the serving
    # path is bit-identical to profiling removed); profile_capture_s
    # rate-limits on-demand deep captures (/v1/debug/profile?capture=1)
    profile_enabled: bool = True
    profile_capture_s: float = 60.0
    # decision ledger & budget-conservation audit plane (obs/ledger.py):
    # per-authority admit attribution on the hot path plus the
    # off-serving-path conservation auditor (=0 is the escape hatch —
    # every record site degrades to one attribute test and decisions
    # are bit-identical to the ledger removed)
    ledger_enabled: bool = True
    # GLOBAL-sync collective implementation for the sharded backend:
    # "psum" (XLA) is the only one; the knob stays so that a deployment
    # still asking for the removed Pallas ring fails at boot, not silently
    collectives: str = "psum"
    # multi-host device process group (parallel/multihost.py); num_hosts <= 1
    # means single-host, no group formed
    coordinator_address: str = ""
    num_hosts: int = 1
    host_id: int = 0
    # cross-host collective GLOBAL transport (service/collective_global.py);
    # active whenever num_hosts > 1. Interval is the lockstep tick cadence —
    # every host in the process group must use the same value.
    cross_host_sync_s: float = 0.1
    cross_host_capacity: int = 1024
    cross_host_candidates: int = 4
    cross_host_stall_s: float = 10.0
    cross_host_secret: str = ""
    cross_host_group: List[str] = dataclasses.field(default_factory=list)
    # deterministic fault injection (service/faults.py): an armed plan
    # fails/delays the Nth transport call per peer — chaos drills and
    # failure-mode rehearsal ONLY, never production serving
    fault_spec: str = ""
    debug: bool = False


def config_from_env(args: Optional[List[str]] = None) -> DaemonConfig:
    """(reference: cmd/gubernator/config.go:67-214 confFromEnv)"""
    import argparse

    parser = argparse.ArgumentParser("gubernator-tpu")
    parser.add_argument("--config", default="", help="key=value env file")
    parser.add_argument("--debug", action="store_true")
    opts, _ = parser.parse_known_args(args)
    if opts.config:
        load_env_file(opts.config)

    b = BehaviorConfig()
    b.batch_timeout_s = _env_dur("GUBER_BATCH_TIMEOUT", b.batch_timeout_s)
    b.batch_limit = _env_int("GUBER_BATCH_LIMIT", b.batch_limit)
    b.batch_wait_s = _env_dur("GUBER_BATCH_WAIT", b.batch_wait_s)
    b.global_timeout_s = _env_dur("GUBER_GLOBAL_TIMEOUT", b.global_timeout_s)
    b.global_batch_limit = _env_int("GUBER_GLOBAL_BATCH_LIMIT", b.global_batch_limit)
    b.global_sync_wait_s = _env_dur("GUBER_GLOBAL_SYNC_WAIT", b.global_sync_wait_s)
    b.multi_region_timeout_s = _env_dur(
        "GUBER_MULTI_REGION_TIMEOUT", b.multi_region_timeout_s)
    b.multi_region_batch_limit = _env_int(
        "GUBER_MULTI_REGION_BATCH_LIMIT", b.multi_region_batch_limit)
    b.multi_region_sync_wait_s = _env_dur(
        "GUBER_MULTI_REGION_SYNC_WAIT", b.multi_region_sync_wait_s)
    b.peer_link_offset = _env_int("GUBER_PEER_LINK_OFFSET", b.peer_link_offset)
    b.link_retry_s = _env_float("GUBER_LINK_RETRY_S", b.link_retry_s)

    # peer-failure resilience (service/peer_client.py CircuitBreaker)
    b.circuit_threshold = _env_int("GUBER_CIRCUIT_THRESHOLD",
                                   b.circuit_threshold)
    b.circuit_open_s = _env_dur("GUBER_CIRCUIT_OPEN", b.circuit_open_s)
    b.degraded_local = _env_bool("GUBER_DEGRADED_LOCAL")

    # overload safety: deadline budgets + admission control
    # (service/deadline.py, instance.py AdmissionController)
    b.default_deadline_ms = _env_float("GUBER_DEFAULT_DEADLINE_MS",
                                       b.default_deadline_ms)
    b.min_hop_budget_ms = _env_float("GUBER_MIN_HOP_BUDGET_MS",
                                     b.min_hop_budget_ms)
    b.max_pending = _env_int("GUBER_MAX_PENDING", b.max_pending)
    b.brownout_fraction = _env_float("GUBER_BROWNOUT_FRACTION",
                                     b.brownout_fraction)

    # hot-key lease tier (service/leases.py)
    b.hot_leases = _env_bool("GUBER_HOT_LEASES")
    b.hot_lease_rate = _env_float("GUBER_HOT_LEASE_RATE", b.hot_lease_rate)
    b.hot_lease_window_s = _env_dur("GUBER_HOT_LEASE_WINDOW",
                                    b.hot_lease_window_s)
    b.hot_lease_ttl_s = _env_dur("GUBER_HOT_LEASE_TTL", b.hot_lease_ttl_s)
    b.hot_lease_fraction = _env_float("GUBER_HOT_LEASE_FRACTION",
                                      b.hot_lease_fraction)

    # live resharding (service/reshard.py)
    b.reshard = _env_bool("GUBER_RESHARD")
    b.reshard_ttl_s = _env_dur("GUBER_RESHARD_TTL", b.reshard_ttl_s)
    b.reshard_chunk_rows = _env_int("GUBER_RESHARD_CHUNK_ROWS",
                                    b.reshard_chunk_rows)
    b.reshard_grace_s = _env_dur("GUBER_RESHARD_GRACE", b.reshard_grace_s)

    # autopilot (service/autopilot.py): bounded closed-loop control.
    # GUBER_AUTOPILOT resolved here (not left None) so the daemon and
    # every harness-spawned node see one consistent answer.
    b.autopilot = _env_bool("GUBER_AUTOPILOT")
    b.autopilot_interval_s = _env_dur("GUBER_AUTOPILOT_INTERVAL",
                                      b.autopilot_interval_s)
    b.autopilot_dwell_s = _env_dur("GUBER_AUTOPILOT_DWELL",
                                   b.autopilot_dwell_s)
    b.autopilot_cooldown_s = _env_dur("GUBER_AUTOPILOT_COOLDOWN",
                                      b.autopilot_cooldown_s)
    b.autopilot_freeze_hold_s = _env_dur("GUBER_AUTOPILOT_FREEZE_HOLD",
                                         b.autopilot_freeze_hold_s)

    conf = DaemonConfig(
        grpc_address=_env_str("GUBER_GRPC_ADDRESS", "0.0.0.0:81"),
        grpc_native=_env_str("GUBER_GRPC_NATIVE", "1") != "0",
        http_address=_env_str("GUBER_HTTP_ADDRESS", "0.0.0.0:80"),
        advertise_address=_env_str("GUBER_ADVERTISE_ADDRESS"),
        cache_size=_env_int("GUBER_CACHE_SIZE", 50_000),
        data_center=_env_str("GUBER_DATA_CENTER"),
        behaviors=b,
        peers=_env_slice("GUBER_PEERS"),
        peers_file=_env_str("GUBER_PEERS_FILE"),
        gossip_bind=_env_str("GUBER_MEMBERLIST_ADVERTISE_ADDRESS"),
        gossip_advertise_port=_env_int("GUBER_MEMBERLIST_ADVERTISE_PORT", 7946),
        gossip_known_nodes=_env_slice("GUBER_MEMBERLIST_KNOWN_NODES"),
        memberlist_compat=_env_str("GUBER_MEMBERLIST_COMPAT", "1") != "0",
        memberlist_node_name=_env_str("GUBER_MEMBERLIST_NODE_NAME"),
        memberlist_secret_keys=_env_slice("GUBER_MEMBERLIST_SECRET_KEYS"),
        etcd_endpoints=_env_slice("GUBER_ETCD_ENDPOINTS"),
        etcd_advertise_address=_env_str("GUBER_ETCD_ADVERTISE_ADDRESS"),
        etcd_key_prefix=_env_str("GUBER_ETCD_KEY_PREFIX"),
        etcd_dial_timeout_s=_env_dur("GUBER_ETCD_DIAL_TIMEOUT", 5.0),
        etcd_user=_env_str("GUBER_ETCD_USER"),
        etcd_password=_env_str("GUBER_ETCD_PASSWORD"),
        etcd_tls_enable=any(
            k.startswith("GUBER_ETCD_TLS_") and os.environ[k]
            for k in os.environ),
        etcd_tls_cert=_env_str("GUBER_ETCD_TLS_CERT"),
        etcd_tls_key=_env_str("GUBER_ETCD_TLS_KEY"),
        etcd_tls_ca=_env_str("GUBER_ETCD_TLS_CA"),
        etcd_tls_skip_verify=_env_bool("GUBER_ETCD_TLS_SKIP_VERIFY"),
        k8s_selector=_env_str("GUBER_K8S_ENDPOINTS_SELECTOR"),
        k8s_namespace=_env_str("GUBER_K8S_NAMESPACE"),
        k8s_pod_ip=_env_str("GUBER_K8S_POD_IP"),
        k8s_pod_port=_env_str("GUBER_K8S_POD_PORT"),
        peer_picker=_env_str("GUBER_PEER_PICKER"),
        peer_picker_hash=_env_str("GUBER_PEER_PICKER_HASH"),
        replicated_hash_replicas=_env_int("GUBER_REPLICATED_HASH_REPLICAS", 512),
        backend=_env_str("GUBER_BACKEND", "auto"),
        device_directory=_env_bool("GUBER_DEVICE_DIRECTORY"),
        min_batch_width=_env_int("GUBER_MIN_BATCH_WIDTH", 64),
        max_batch_width=_env_int("GUBER_MAX_BATCH_WIDTH", 8192),
        pipeline_depth=_env_pipeline_depth(),
        pipeline_scan=_env_int("GUBER_PIPELINE_SCAN", 8),
        snapshot_path=_env_str("GUBER_SNAPSHOT_PATH"),
        snapshot_format=_env_str("GUBER_SNAPSHOT_FORMAT", "binary"),
        profile_port=_env_int("GUBER_PROFILE_PORT", 0),
        profile_dir=_env_str("GUBER_PROFILE_DIR"),
        trace_sample=_env_float("GUBER_TRACE_SAMPLE", 0.0),
        slow_request_ms=_env_float("GUBER_SLOW_REQUEST_MS", 0.0),
        debug_endpoints=_env_str("GUBER_DEBUG_ENDPOINTS", "1") != "0",
        flight_recorder=_env_str("GUBER_FLIGHT_RECORDER", "1") not in
        ("0", "f", "false", "no", "off"),
        flight_recorder_capacity=_env_int(
            "GUBER_FLIGHT_RECORDER_CAPACITY", 4096),
        bundle_dir=_env_str("GUBER_BUNDLE_DIR"),
        bundle_interval_s=_env_dur("GUBER_BUNDLE_INTERVAL", 60.0),
        bundle_keep=_env_int("GUBER_BUNDLE_KEEP", 20),
        slow_log_path=_env_str("GUBER_SLOW_LOG_PATH"),
        slow_log_max_mb=_env_float("GUBER_SLOW_LOG_MAX_MB", 64.0),
        anomaly_interval_s=_env_dur("GUBER_ANOMALY_INTERVAL", 5.0),
        slo_target_ms=_env_float("GUBER_SLO_TARGET_MS", 250.0),
        slo_objective=_env_float("GUBER_SLO_OBJECTIVE", 0.999),
        history=_env_str("GUBER_HISTORY", "1") not in
        ("0", "f", "false", "no", "off"),
        history_tick_s=_env_dur("GUBER_HISTORY_TICK_S", 5.0),
        history_retention_s=_env_dur("GUBER_HISTORY_RETENTION", 7200.0),
        keyspace_scan=_env_str("GUBER_KEYSPACE_SCAN", "1") not in
        ("0", "f", "false", "no", "off"),
        keyspace_interval_s=_env_dur("GUBER_KEYSPACE_INTERVAL", 60.0),
        keyspace_top_k=_env_int("GUBER_KEYSPACE_TOP_K", 20),
        capacity_horizon_s=_env_dur("GUBER_CAPACITY_HORIZON", 1800.0),
        profile_enabled=_env_str("GUBER_PROFILE", "1") not in
        ("0", "f", "false", "no", "off"),
        profile_capture_s=_env_dur("GUBER_PROFILE_CAPTURE_S", 60.0),
        ledger_enabled=_env_str("GUBER_LEDGER", "1") not in
        ("0", "f", "false", "no", "off"),
        # GUBER_LOCK_WITNESS (default off) arms the runtime lock-order
        # witness (obs/witness.py) — it is resolved there at
        # lock-construction time, before any config object can exist,
        # so it deliberately has no DaemonConfig field; it is listed
        # here because this file is the knob inventory. daemon startup
        # logs when a process is serving with the witness armed.
        collectives=_env_str("GUBER_COLLECTIVES", "psum"),
        coordinator_address=_env_str("GUBER_COORDINATOR_ADDRESS"),
        num_hosts=_env_int("GUBER_NUM_HOSTS", 1),
        host_id=_env_int("GUBER_HOST_ID", 0),
        cross_host_sync_s=_env_dur("GUBER_CROSS_HOST_SYNC", 0.1),
        cross_host_capacity=_env_int("GUBER_CROSS_HOST_CAPACITY", 1024),
        cross_host_candidates=_env_int("GUBER_CROSS_HOST_CANDIDATES", 4),
        cross_host_stall_s=_env_dur("GUBER_CROSS_HOST_STALL", 10.0),
        cross_host_secret=_env_str("GUBER_CROSS_HOST_SECRET"),
        cross_host_group=_env_slice("GUBER_CROSS_HOST_GROUP"),
        fault_spec=_env_str("GUBER_FAULT_SPEC"),
        debug=opts.debug or bool(os.environ.get("GUBER_DEBUG")),
    )
    if conf.collectives != "psum":
        raise ValueError(
            f"'GUBER_COLLECTIVES={conf.collectives}' is invalid; "
            "choices are ['psum'] (the Pallas ring was removed: the TPU "
            "compiler refuses it)")
    if conf.pipeline_scan < 1:
        raise ValueError(
            f"'GUBER_PIPELINE_SCAN={conf.pipeline_scan}' is invalid; "
            "must be >= 1")
    if not 0.0 <= conf.trace_sample <= 1.0:
        raise ValueError(
            f"'GUBER_TRACE_SAMPLE={conf.trace_sample}' is invalid; "
            "must be a fraction in [0, 1]")
    if b.circuit_threshold < 0:
        raise ValueError(
            f"'GUBER_CIRCUIT_THRESHOLD={b.circuit_threshold}' is invalid; "
            "must be >= 0 (0 disables the breaker)")
    if b.circuit_open_s <= 0:
        raise ValueError(
            f"'GUBER_CIRCUIT_OPEN={b.circuit_open_s}' is invalid; "
            "must be a positive duration")
    if b.link_retry_s <= 0:
        raise ValueError(
            f"'GUBER_LINK_RETRY_S={b.link_retry_s}' is invalid; "
            "must be positive seconds")
    if b.default_deadline_ms < 0:
        raise ValueError(
            f"'GUBER_DEFAULT_DEADLINE_MS={b.default_deadline_ms}' is "
            "invalid; must be >= 0 ms (0 = no default budget)")
    if b.min_hop_budget_ms <= 0:
        raise ValueError(
            f"'GUBER_MIN_HOP_BUDGET_MS={b.min_hop_budget_ms}' is invalid; "
            "must be positive milliseconds")
    if b.max_pending < 0:
        raise ValueError(
            f"'GUBER_MAX_PENDING={b.max_pending}' is invalid; "
            "must be >= 0 (0 disables admission control)")
    if not 0.0 < b.brownout_fraction <= 1.0:
        raise ValueError(
            f"'GUBER_BROWNOUT_FRACTION={b.brownout_fraction}' is invalid; "
            "must be a fraction in (0, 1]")
    if b.autopilot_interval_s <= 0:
        raise ValueError(
            f"'GUBER_AUTOPILOT_INTERVAL={b.autopilot_interval_s}' is "
            "invalid; must be a positive duration")
    if b.autopilot_dwell_s <= 0:
        raise ValueError(
            f"'GUBER_AUTOPILOT_DWELL={b.autopilot_dwell_s}' is invalid; "
            "must be a positive duration")
    if b.autopilot_cooldown_s <= 0:
        raise ValueError(
            f"'GUBER_AUTOPILOT_COOLDOWN={b.autopilot_cooldown_s}' is "
            "invalid; must be a positive duration")
    if b.autopilot_freeze_hold_s < 0:
        raise ValueError(
            f"'GUBER_AUTOPILOT_FREEZE_HOLD={b.autopilot_freeze_hold_s}' is "
            "invalid; must be >= 0 seconds")
    if conf.flight_recorder_capacity < 16:
        raise ValueError(
            f"'GUBER_FLIGHT_RECORDER_CAPACITY="
            f"{conf.flight_recorder_capacity}' is invalid; must be >= 16")
    if conf.bundle_interval_s < 0:
        raise ValueError(
            f"'GUBER_BUNDLE_INTERVAL={conf.bundle_interval_s}' is invalid; "
            "must be >= 0 seconds (0 = no rate limit)")
    if conf.bundle_keep < 1:
        raise ValueError(
            f"'GUBER_BUNDLE_KEEP={conf.bundle_keep}' is invalid; "
            "must be >= 1")
    if conf.slow_log_max_mb <= 0:
        raise ValueError(
            f"'GUBER_SLOW_LOG_MAX_MB={conf.slow_log_max_mb}' is invalid; "
            "must be positive megabytes")
    if conf.anomaly_interval_s <= 0:
        raise ValueError(
            f"'GUBER_ANOMALY_INTERVAL={conf.anomaly_interval_s}' is "
            "invalid; must be a positive duration")
    if conf.slo_target_ms <= 0:
        raise ValueError(
            f"'GUBER_SLO_TARGET_MS={conf.slo_target_ms}' is invalid; "
            "must be positive milliseconds")
    if not 0.0 < conf.slo_objective < 1.0:
        raise ValueError(
            f"'GUBER_SLO_OBJECTIVE={conf.slo_objective}' is invalid; "
            "must be a fraction in (0, 1)")
    if conf.history_tick_s <= 0:
        raise ValueError(
            f"'GUBER_HISTORY_TICK_S={conf.history_tick_s}' is invalid; "
            "must be a positive duration")
    if conf.history_retention_s < conf.history_tick_s:
        raise ValueError(
            f"'GUBER_HISTORY_RETENTION={conf.history_retention_s}' is "
            "invalid; must be >= GUBER_HISTORY_TICK_S")
    if conf.keyspace_interval_s <= 0:
        raise ValueError(
            f"'GUBER_KEYSPACE_INTERVAL={conf.keyspace_interval_s}' is "
            "invalid; must be a positive duration")
    if conf.keyspace_top_k < 1:
        raise ValueError(
            f"'GUBER_KEYSPACE_TOP_K={conf.keyspace_top_k}' is invalid; "
            "must be >= 1")
    if conf.capacity_horizon_s <= 0:
        raise ValueError(
            f"'GUBER_CAPACITY_HORIZON={conf.capacity_horizon_s}' is "
            "invalid; must be a positive duration")
    if conf.profile_capture_s <= 0:
        raise ValueError(
            f"'GUBER_PROFILE_CAPTURE_S={conf.profile_capture_s}' is "
            "invalid; must be a positive duration")
    if conf.fault_spec:
        # a typo'd chaos plan must fail the boot loudly, not inject nothing
        from gubernator_tpu.service.faults import parse_spec

        parse_spec(conf.fault_spec)
    return conf


def build_picker(conf: DaemonConfig):
    """(reference: cmd/gubernator/config.go:137-169)"""
    from gubernator_tpu.cluster.pickers import (
        ConsistentHashPicker,
        ReplicatedConsistentHashPicker,
        crc32_hash,
        fnv1_32,
        fnv1a_32,
    )
    from gubernator_tpu.utils.fnv import fnv1_64, fnv1a_64

    if conf.peer_picker in ("", "replicated-hash"):
        fns = {"fnv1a": fnv1a_64, "fnv1": fnv1_64, "": None}
        if conf.peer_picker_hash not in fns:
            raise ValueError(
                f"'GUBER_PEER_PICKER_HASH={conf.peer_picker_hash}' is invalid; "
                f"choices are [fnv1a, fnv1]"
            )
        return ReplicatedConsistentHashPicker(
            fns[conf.peer_picker_hash],
            replicas=conf.replicated_hash_replicas,
        )
    if conf.peer_picker == "consistent-hash":
        fns = {"crc32": crc32_hash, "fnv1a": fnv1a_32, "fnv1": fnv1_32, "": None}
        if conf.peer_picker_hash not in fns:
            raise ValueError(
                f"'GUBER_PEER_PICKER_HASH={conf.peer_picker_hash}' is invalid; "
                f"choices are [crc32, fnv1a, fnv1]"
            )
        return ConsistentHashPicker(fns[conf.peer_picker_hash])
    raise ValueError(
        f"'GUBER_PEER_PICKER={conf.peer_picker}' is invalid; "
        f"choices are [consistent-hash, replicated-hash]"
    )
