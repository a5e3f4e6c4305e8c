"""The serving daemon: `python -m gubernator_tpu.cmd.daemon`.

Wires everything the reference daemon does (reference:
cmd/gubernator/main.go:41-160): env config, TPU backend, gRPC server with
stats interceptor, discovery pool selection, HTTP gateway with /metrics,
and signal handling — plus the TPU-specific steps the reference has no
analogue for: backend selection (single-table engine vs mesh-sharded) and
kernel warmup before serving.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import sys
import threading

from gubernator_tpu.cmd.envconf import DaemonConfig, build_picker, config_from_env
from gubernator_tpu.obs import witness
from gubernator_tpu.service.config import InstanceConfig
from gubernator_tpu.service.http_gateway import HttpGateway
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.service.metrics import GRPCStatsInterceptor, Metrics
from gubernator_tpu.service.server import make_server
from gubernator_tpu.types import PeerInfo

log = logging.getLogger("gubernator_tpu.daemon")


def build_backend(conf: DaemonConfig):
    """Pick the device backend: mesh-sharded when >1 local device, else the
    single-table engine. (TPU-specific; no reference analogue.)"""
    import jax

    from gubernator_tpu.utils.platform import compile_cache_dir

    # before the first compile; JAX_COMPILATION_CACHE_DIR wins
    log.info("compile cache: %s", compile_cache_dir())
    # size by ADDRESSABLE devices: after a multi-host initialize_from_env,
    # jax.devices() spans every host but this daemon's engine owns only its
    # local mesh (cross-host request routing stays at the gRPC tier)
    n_dev = len(jax.local_devices())
    backend = conf.backend
    if backend == "auto":
        backend = "sharded" if n_dev > 1 else "engine"
    if backend == "sharded":
        if conf.device_directory:
            raise ValueError(
                "GUBER_DEVICE_DIRECTORY supports the single-table engine "
                "only; the sharded backend keeps the host directory "
                "(set GUBER_BACKEND=engine, or unset the flag)")
        from gubernator_tpu.parallel.mesh import make_mesh
        from gubernator_tpu.parallel.sharded import ShardedEngine

        cap = max(conf.cache_size // n_dev, 1024)
        eng = ShardedEngine(
            mesh=make_mesh(n_shards=n_dev, devices=jax.local_devices()),
            capacity_per_shard=cap,
            min_width=conf.min_batch_width,
            max_width=conf.max_batch_width,
            loader=_make_loader(conf),
        )
        log.info("backend: sharded over %d devices, %d slots/shard",
                 n_dev, cap)
        return eng
    if conf.device_directory:
        # on-chip key directory: zero host round trips per key; no
        # Store/Loader (the device keeps no key strings) — a loader
        # config fails loudly here rather than silently dropping state
        from gubernator_tpu.models.devdir_engine import DevDirEngine

        eng = DevDirEngine(
            capacity=conf.cache_size,
            min_width=conf.min_batch_width,
            max_width=conf.max_batch_width,
            loader=_make_loader(conf),
        )
        log.info("backend: DEVICE-directory engine, %d slots",
                 conf.cache_size)
        return eng
    from gubernator_tpu.models.engine import Engine

    eng = Engine(
        capacity=conf.cache_size,
        min_width=conf.min_batch_width,
        max_width=conf.max_batch_width,
        loader=_make_loader(conf),
    )
    log.info("backend: single-table engine, %d slots", conf.cache_size)
    return eng


def _make_loader(conf: DaemonConfig):
    """Durable bucket snapshots via GUBER_SNAPSHOT_PATH (both backends).

    Binary slab format by default (10×+ faster at production scale;
    restore time is boot time after a crash) — a legacy JSONL file at the
    path still restores (auto-detected) and is migrated binary on the
    next save. GUBER_SNAPSHOT_FORMAT=jsonl pins the text format."""
    if not conf.snapshot_path:
        return None
    if conf.snapshot_format not in ("binary", "jsonl"):
        raise ValueError(
            f"GUBER_SNAPSHOT_FORMAT={conf.snapshot_format!r}: must be"
            " 'binary' or 'jsonl'")
    if conf.snapshot_format == "jsonl":
        from gubernator_tpu.store import FileLoader

        return FileLoader(conf.snapshot_path)
    from gubernator_tpu.store import BinarySnapshotLoader

    return BinarySnapshotLoader(conf.snapshot_path)


def build_pool(conf: DaemonConfig, instance: Instance):
    """Discovery selection, k8s > memberlist > etcd > file > static
    (reference: cmd/gubernator/main.go:87-121)."""
    from gubernator_tpu.cluster import discovery

    def on_update(peers):
        instance.set_peers(peers)

    if conf.k8s_selector:
        from gubernator_tpu.cluster.k8s import K8sPool

        grpc_port = (conf.advertise_address or conf.grpc_address).rsplit(":", 1)[-1]
        return K8sPool(
            on_update=on_update,
            selector=conf.k8s_selector,
            # None -> read the in-cluster service-account namespace file
            namespace=conf.k8s_namespace or None,
            pod_ip=conf.k8s_pod_ip,
            pod_port=conf.k8s_pod_port or grpc_port,
        )
    if conf.gossip_bind or conf.gossip_known_nodes:
        bind = conf.gossip_bind or "0.0.0.0"
        if ":" not in bind:
            # GUBER_MEMBERLIST_ADVERTISE_PORT completes a bare address
            # (reference: config.go:126-127)
            bind = f"{bind}:{conf.gossip_advertise_port}"
        if conf.memberlist_compat:
            # the default: the hashicorp/memberlist v0.2.0 wire protocol,
            # joinable by/of reference fleets (reference: memberlist.go)
            import socket as _socket

            from gubernator_tpu.cluster.memberlist import MemberlistPool

            # a port-less advertise address falls back to the gRPC bind
            # port (which always has one — default 0.0.0.0:81)
            grpc_addr = conf.advertise_address or conf.grpc_address
            try:
                guber_port = int(grpc_addr.rsplit(":", 1)[-1])
            except ValueError:
                guber_port = int(conf.grpc_address.rsplit(":", 1)[-1])
            import base64 as _b64

            ring = [_b64.b64decode(k)
                    for k in conf.memberlist_secret_keys]
            return MemberlistPool(
                bind_address=bind,
                node_name=conf.memberlist_node_name
                or _socket.gethostname(),
                on_update=on_update,
                gubernator_port=guber_port,
                known_nodes=conf.gossip_known_nodes,
                datacenter=conf.data_center,
                secret_key=ring[0] if ring else b"",
                secret_keys=ring[1:],
            )
        if conf.memberlist_secret_keys:
            # the operator asked for encrypted gossip; silently dropping
            # the keyring would ship cleartext membership traffic
            raise ValueError(
                "GUBER_MEMBERLIST_SECRET_KEYS is set but "
                "GUBER_MEMBERLIST_COMPAT=0 selects GossipPool, which "
                "cannot encrypt; unset the keys or use the "
                "memberlist-compatible pool (GUBER_MEMBERLIST_COMPAT=1)")
        return discovery.GossipPool(
            bind_address=bind,
            grpc_address=conf.advertise_address or conf.grpc_address,
            datacenter=conf.data_center,
            known_nodes=conf.gossip_known_nodes,
            on_update=on_update,
        )
    if conf.etcd_endpoints:
        from gubernator_tpu.cluster.etcd import build_tls_credentials

        credentials, channel_options, factory = None, (), None
        if conf.etcd_tls_enable:
            if conf.etcd_tls_skip_verify:
                # per-endpoint: pinning must fetch each endpoint's own cert
                def factory(target, _conf=conf):
                    return build_tls_credentials(
                        ca_file=_conf.etcd_tls_ca,
                        cert_file=_conf.etcd_tls_cert,
                        key_file=_conf.etcd_tls_key,
                        skip_verify=True,
                        endpoint=target,
                    )
            else:
                credentials, channel_options = build_tls_credentials(
                    ca_file=conf.etcd_tls_ca,
                    cert_file=conf.etcd_tls_cert,
                    key_file=conf.etcd_tls_key,
                )
        kwargs = {}
        if conf.etcd_key_prefix:
            base = conf.etcd_key_prefix
            kwargs["base_key"] = base if base.endswith("/") else base + "/"
        return discovery.EtcdPool(
            endpoints=conf.etcd_endpoints,
            advertise_address=(conf.etcd_advertise_address
                               or conf.advertise_address or conf.grpc_address),
            on_update=on_update,
            dial_timeout_s=conf.etcd_dial_timeout_s,
            credentials=credentials,
            channel_options=channel_options,
            credentials_factory=factory,
            username=conf.etcd_user,
            password=conf.etcd_password,
            **kwargs,
        )
    if conf.peers_file:
        return discovery.FilePool(conf.peers_file, on_update)
    peers = conf.peers or [conf.advertise_address or conf.grpc_address]
    return discovery.StaticPool(
        [PeerInfo(address=a, datacenter=conf.data_center) for a in peers],
        on_update,
    )


def main(argv=None) -> int:
    conf = config_from_env(argv)
    logging.basicConfig(
        level=logging.DEBUG if conf.debug else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
        stream=sys.stderr,
    )

    if conf.fault_spec:
        # chaos drills: arm the deterministic fault plan before any peer
        # client exists, and say so LOUDLY — an armed plan in production
        # serving is an outage you configured
        from gubernator_tpu.service import faults

        faults.install(conf.fault_spec)
        log.warning("FAULT INJECTION ACTIVE (GUBER_FAULT_SPEC): %s",
                    conf.fault_spec)

    # form the cross-host device process group BEFORE the first backend use;
    # no-op for single-host deployments
    from gubernator_tpu.parallel.multihost import initialize_from_env

    multi_host = initialize_from_env(
        conf.coordinator_address, conf.num_hosts, conf.host_id)

    backend = build_backend(conf)
    log.info("warming up decision kernel (compiling width buckets)...")
    if hasattr(backend, "warmup"):
        backend.warmup()

    advertise = conf.advertise_address or conf.grpc_address
    metrics = Metrics()
    # engine phase histograms (device dispatch, window lanes) feed the
    # same per-daemon registry the RPC tiers use
    backend.metrics = metrics
    from gubernator_tpu.obs.trace import Tracer

    tracer = Tracer(sample=conf.trace_sample, slow_ms=conf.slow_request_ms,
                    service=advertise)
    if conf.trace_sample > 0:
        log.info("request tracing on: sample=%.3g slow_request_ms=%.0f",
                 conf.trace_sample, conf.slow_request_ms)
    if conf.behaviors.circuit_threshold > 0:
        log.info(
            "peer circuit breaker: threshold=%d cooldown=%.1fs "
            "degraded_local=%s",
            conf.behaviors.circuit_threshold, conf.behaviors.circuit_open_s,
            "on" if conf.behaviors.degraded_local else "off")
    if conf.behaviors.max_pending > 0:
        log.info(
            "admission control: max_pending=%d (brownout at %.0f%%) "
            "default_deadline_ms=%.0f min_hop_budget_ms=%.1f",
            conf.behaviors.max_pending,
            conf.behaviors.brownout_fraction * 100.0,
            conf.behaviors.default_deadline_ms,
            conf.behaviors.min_hop_budget_ms)
    else:
        log.warning(
            "admission control DISABLED (GUBER_MAX_PENDING=0): a "
            "saturated node will stall in its queues instead of shedding")
    if conf.behaviors.hot_leases:
        log.info(
            "hot-key lease tier: rate=%.0f/s window=%.1fs ttl=%.0fms "
            "fraction=%.2f",
            conf.behaviors.hot_lease_rate, conf.behaviors.hot_lease_window_s,
            conf.behaviors.hot_lease_ttl_s * 1000.0,
            conf.behaviors.hot_lease_fraction)
    # observability plane (obs/): the flight recorder is the always-on
    # black box; the slow-request log gets a size-rotated file sink when
    # a path is configured
    from gubernator_tpu.obs.events import FlightRecorder
    from gubernator_tpu.obs.trace import install_slow_log_file

    recorder = FlightRecorder(capacity=conf.flight_recorder_capacity,
                              enabled=conf.flight_recorder)
    if not conf.flight_recorder:
        log.info("flight recorder OFF (GUBER_FLIGHT_RECORDER=0)")
    if conf.slow_log_path:
        if install_slow_log_file(conf.slow_log_path,
                                 max_mb=conf.slow_log_max_mb) is not None:
            log.info("slow-request log: %s (rotate at %.0f MB)",
                     conf.slow_log_path, conf.slow_log_max_mb)
    instance = Instance(
        InstanceConfig(
            behaviors=conf.behaviors,
            data_center=conf.data_center,
            backend=backend,
            local_picker=build_picker(conf),
            metrics=metrics,
            tracer=tracer,
            recorder=recorder,
            anomaly_interval_s=conf.anomaly_interval_s,
            slo_target_ms=conf.slo_target_ms,
            slo_objective=conf.slo_objective,
            history_enabled=conf.history,
            history_tick_s=conf.history_tick_s,
            history_retention_s=conf.history_retention_s,
            keyspace_scan=conf.keyspace_scan,
            keyspace_interval_s=conf.keyspace_interval_s,
            keyspace_top_k=conf.keyspace_top_k,
            capacity_horizon_s=conf.capacity_horizon_s,
            profile_enabled=conf.profile_enabled,
            profile_capture_s=conf.profile_capture_s,
            ledger_enabled=conf.ledger_enabled,
            pipeline_depth=conf.pipeline_depth or None,  # 0 -> env/auto
            pipeline_scan=conf.pipeline_scan,
        ),
        advertise_address=advertise,
    )
    if conf.bundle_dir:
        from gubernator_tpu.obs.bundle import BundleWriter

        instance.bundle_writer = BundleWriter(
            conf.bundle_dir, min_interval_s=conf.bundle_interval_s,
            keep=conf.bundle_keep)
        log.info("anomaly diagnostic bundles -> %s (keep %d, min %.0fs "
                 "apart)", conf.bundle_dir, conf.bundle_keep,
                 conf.bundle_interval_s)
        # kernel recompile check: fingerprint the canonical decide
        # programs and compare against the last boot's record — an HLO
        # change (new jaxlib, flag drift, shape change) is exactly the
        # event a profile regression investigation wants pinned in the
        # flight recorder (obs/profile.py check_recompile)
        fps_fn = getattr(backend, "kernel_fingerprints", None)
        if callable(fps_fn):
            from gubernator_tpu.obs.profile import check_recompile

            rc = check_recompile(
                fps_fn(),
                os.path.join(conf.bundle_dir, "kernel_fingerprints.json"),
                recorder=recorder)
            if rc.get("changed"):
                log.warning("kernel HLO fingerprints changed since last "
                            "boot: %s", sorted(rc["changed"]))
    # background detector sweep; in-process/test clusters instead ride
    # the maybe_check() piggyback on health probes and metric scrapes
    instance.anomaly.start()
    # capacity & keyspace cartography: background tickers for the metrics
    # ring and the table harvest (in-process clusters ride the scrape
    # piggybacks instead)
    if conf.history:
        instance.history.start()
        log.info("metrics history ring: tick=%.1fs retention=%.0fs "
                 "(/v1/debug/history)", conf.history_tick_s,
                 conf.history_retention_s)
    else:
        log.info("metrics history ring OFF (GUBER_HISTORY=0)")
    if conf.keyspace_scan:
        instance.keyspace.start()
        log.info("keyspace cartographer: interval=%.0fs top_k=%d "
                 "(/v1/debug/keyspace)", conf.keyspace_interval_s,
                 conf.keyspace_top_k)
    else:
        log.info("keyspace scan OFF (GUBER_KEYSPACE_SCAN=0)")
    if conf.profile_enabled:
        log.info("serving-cycle profiler on: capture >=%.0fs apart "
                 "(/v1/debug/profile)", conf.profile_capture_s)
    else:
        log.info("serving-cycle profiler OFF (GUBER_PROFILE=0)")
    if conf.ledger_enabled:
        log.info("decision ledger on: conservation audit rides harvest "
                 "cadence (/v1/debug/ledger)")
    else:
        log.info("decision ledger OFF (GUBER_LEDGER=0)")
    if witness.witness_enabled():
        log.warning("lock-order witness ARMED (GUBER_LOCK_WITNESS=1) — "
                    "test-rig instrument; every lock carries order "
                    "bookkeeping, do not run production traffic this way")
    columnar_pipe = (conf.pipeline_depth != 1
                     and getattr(backend, "supports_columnar",
                                 lambda: False)())
    if instance.combiner.pipelined or columnar_pipe:
        # compile the burst scan shapes up front (a cold compile inside a
        # live window stalls it for the whole compile) — the object and
        # columnar pipelines dispatch the same scan-group shapes
        if hasattr(backend, "warmup_pipeline"):
            backend.warmup_pipeline(max_group=conf.pipeline_scan)
    if instance.combiner.pipelined:
        # resolve an 'auto' depth against the live link with no-op
        # windows; depth 1 in the probe set auto-degrades to lock-step
        depth = instance.combiner.autotune()
        log.info("pipelined serving loop on: depth=%d scan<=%d",
                 depth, conf.pipeline_scan)
    # the columnar wire path rides the combiner's RESOLVED depth (the
    # autotune winner), so both protocols share one pipelining decision
    columnar_depth = instance.combiner.depth if columnar_pipe else 1
    # autopilot ticker AFTER autotune so the pipeline controller's
    # baseline is the probed depth, not the pre-probe placeholder
    if instance.autopilot.enabled:
        instance.autopilot.start()
        log.info("autopilot ON (GUBER_AUTOPILOT=1): interval=%.1fs "
                 "dwell=%.1fs cooldown=%.1fs — bounded closed-loop "
                 "control over max_pending / hot-lease / keyspace "
                 "cadence / pipeline depth (docs/OPERATIONS.md Autopilot)",
                 instance.autopilot.interval_s, instance.autopilot.dwell_s,
                 instance.autopilot.cooldown_s)
    if multi_host:
        # cross-host GLOBAL aggregation rides the device fabric: one
        # lockstep collective per tick replaces the per-peer gRPC pipelines
        # (which stay wired as the fallback transport). Every daemon in the
        # process group runs the same fixed-cadence loop (SPMD).
        from gubernator_tpu.parallel.multihost import CollectiveGlobalChannel
        from gubernator_tpu.service.collective_global import (
            CollectiveGlobalSync,
        )

        channel = CollectiveGlobalChannel(conf.cross_host_capacity)
        collective = CollectiveGlobalSync(
            instance, channel, interval_s=conf.cross_host_sync_s,
            stall_timeout_s=conf.cross_host_stall_s,
            slot_candidates=conf.cross_host_candidates,
            claim_secret=(conf.cross_host_secret or "").encode())
        # GUBER_CROSS_HOST_GROUP lists the advertise addresses inside the
        # process group; unset/empty = the whole fleet is in it (homogeneous)
        instance.attach_collective(
            collective, group_peers=conf.cross_host_group or None)
        collective.start()
        log.info(
            "cross-host GLOBAL collective: %d hosts, %d slots, tick %.0f ms",
            conf.num_hosts, conf.cross_host_capacity,
            conf.cross_host_sync_s * 1e3)

    # Public gRPC surface: the native HTTP/2 front (native/peerlink.cpp)
    # serves the wire-compatible protocol without the GIL when available —
    # hot unary calls parse and (when eligible) decide in C; everything
    # else punts to the same Python servicers grpcio binds. grpcio remains
    # the fallback (GUBER_GRPC_NATIVE=0, dynamic :0 ports, or native
    # build failure).
    server = None
    peerlink = None
    conf_grpc_port = 0
    try:
        conf_grpc_port = int(conf.grpc_address.rsplit(":", 1)[-1])
    except ValueError:
        pass
    if (conf.grpc_native and conf_grpc_port > 0
            and conf.behaviors.peer_link_offset > 0):
        from gubernator_tpu.service.peerlink import (
            PeerLinkError,
            PeerLinkService,
        )

        conf_grpc_host = conf.grpc_address.rsplit(":", 1)[0]
        try:
            peerlink = PeerLinkService(
                instance,
                port=conf_grpc_port + conf.behaviors.peer_link_offset,
                grpc_port=conf_grpc_port, grpc_host=conf_grpc_host,
                metrics=metrics, pipeline_depth=columnar_depth,
                pipeline_scan=conf.pipeline_scan)
            port = conf_grpc_port
            metrics.set_native_front(peerlink.native_hits)
            log.info("native gRPC front on :%d (peerlink on %d, "
                     "advertised as %s)", port, peerlink.port, advertise)
        except (PeerLinkError, RuntimeError) as e:
            log.warning("native gRPC front unavailable: %s "
                        "(grpcio serves)", e)
            peerlink = None
    if peerlink is None:
        server, port = make_server(
            instance,
            conf.grpc_address,
            stats_handler=GRPCStatsInterceptor(metrics),
        )
        server.start()
        log.info("gRPC serving on %s (advertised as %s)",
                 conf.grpc_address, advertise)
        if conf.behaviors.peer_link_offset > 0:
            # the native peer transport: peers reach it at grpc port +
            # offset (service/peerlink.py; gRPC remains the fallback)
            from gubernator_tpu.service.peerlink import (
                PeerLinkError,
                PeerLinkService,
            )

            link_port = port + conf.behaviors.peer_link_offset
            try:
                peerlink = PeerLinkService(
                    instance, port=link_port, metrics=metrics,
                    pipeline_depth=columnar_depth,
                    pipeline_scan=conf.pipeline_scan)
                log.info("peerlink serving on port %d", peerlink.port)
            except (PeerLinkError, RuntimeError) as e:
                log.warning("peerlink disabled: %s (peer calls ride gRPC)",
                            e)

    gateway = HttpGateway(instance, conf.http_address, metrics=metrics,
                          debug_endpoints=conf.debug_endpoints)
    gateway.start()
    log.info("HTTP gateway on %s (debug endpoints %s)", conf.http_address,
             "on" if conf.debug_endpoints else "off")

    pool = build_pool(conf, instance)

    tracing = start_profiling(conf)

    stop = threading.Event()

    def on_signal(signum, frame):
        log.info("caught signal %s; shutting down", signum)
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    # what this process serves from, as its own arrays and objects say
    # (the same facts sit under engine.device in /v1/debug/vars)
    device = getattr(backend, "device", None)
    if device is not None:
        device["front"] = "grpcio" if server is not None else "native-h2"
        log.info("device: %s", json.dumps(device, sort_keys=True))
    # every program serving needs is compiled by now: one compiled from
    # here on runs inside a request (engine.device.compiles, and a
    # profile.compile event in the flight recorder)
    from gubernator_tpu.utils.platform import CompileWatch

    backend.compile_watch = CompileWatch(recorder)
    print("Ready", flush=True)  # startup sentinel (reference: cmd/gubernator-cluster/main.go:52)
    stop.wait()

    pool.close()
    gateway.close()
    if peerlink is not None:
        peerlink.close()
    if server is not None:
        server.stop(grace=1.0)
    instance.close()
    if tracing:
        import jax

        jax.profiler.stop_trace()
        log.info("XLA trace written to %s", conf.profile_dir)
    if multi_host:
        # jax.distributed's interpreter-exit hooks block synchronizing with
        # the coordinator; when the whole fleet shuts down at once (or the
        # coordinator died first) that wait can outlive any supervisor's
        # grace period. Every flush above is done (loader saved, pipelines
        # drained), so leave hard.
        log.info("multi-host daemon exiting")
        sys.stderr.flush()
        import os

        os._exit(0)
    return 0


def start_profiling(conf: DaemonConfig) -> bool:
    """Device-level tracing/profiling knobs (no reference analogue — the
    reference's only latency observability is RPC histograms, SURVEY §5.1).

    GUBER_PROFILE_PORT starts jax's live profiler server (attach TensorBoard
    or `jax.profiler.trace` remotely); GUBER_PROFILE_DIR captures one XLA
    trace spanning the daemon's lifetime, written at shutdown. Returns
    whether a trace capture is active."""
    if conf.profile_port:
        import jax

        jax.profiler.start_server(conf.profile_port)
        log.info("jax profiler server on port %d", conf.profile_port)
    if conf.profile_dir:
        import jax

        jax.profiler.start_trace(conf.profile_dir)
        log.info("capturing XLA trace to %s", conf.profile_dir)
        return True
    return False


if __name__ == "__main__":
    sys.exit(main())
