"""In-process multi-instance cluster harness.

Mirrors the reference's test strategy (reference: cluster/cluster.go:104-165,
functional_test.go:35-49): N real gRPC servers + Instances on loopback in one
process, peer lists injected directly (discovery bypassed), sync windows
tuned down to 50 ms so GLOBAL tests settle fast
(reference: cluster/cluster.go:57-66). `stop_instance_at` kills one server
WITHOUT updating peer lists, for fault-injection tests
(reference: cluster/cluster.go:93-96).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import grpc

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.service.config import BehaviorConfig, InstanceConfig
from gubernator_tpu.service.grpc_api import close_channels
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.service.metrics import Metrics
from gubernator_tpu.service.server import make_server
from gubernator_tpu.types import PeerInfo


def test_behaviors() -> BehaviorConfig:
    """Batch fast, sync at 50 ms (reference: cluster/cluster.go:57-66)."""
    # Wait windows are tuned down so async tests settle fast; RPC *timeouts*
    # stay generous — a first-touch XLA compile or CPU contention from N
    # in-process servers can exceed 500 ms, and a timed-out forward records a
    # peer error with a 5-minute TTL that poisons HealthCheck for the rest of
    # the cluster's life.
    return BehaviorConfig(
        batch_timeout_s=10.0,
        batch_wait_s=0.01,
        global_timeout_s=10.0,
        global_sync_wait_s=0.05,
        multi_region_timeout_s=10.0,
        multi_region_sync_wait_s=0.05,
        # gRPC ports are dynamic here, so a fixed link offset could collide
        # with another instance's port; peerlink tests wire it explicitly
        peer_link_offset=0,
        # breaker cooldown tracks the bounded channel-reconnect backoff
        # (grpc_api.CHANNEL_OPTIONS, ~1 s): a kill/restart harness reuses
        # PeerClients across the restart, so the production 5 s cooldown
        # would stall recovery past the soak's settle grace
        circuit_open_s=0.5,
    )


@dataclasses.dataclass
class ClusterInstance:
    address: str
    datacenter: str
    instance: Instance
    server: grpc.Server
    # per-instance registry so tests can assert histogram samples the way
    # the reference's GLOBAL test reads Collect() (functional_test.go:311-343)
    metrics: Optional[Metrics] = None

    def stop(self) -> None:
        # wait for full termination: stop() returns before the listening
        # socket closes, so a fault-injection test could still reach a
        # "dead" server for a few ms and flake
        self.server.stop(grace=0.2).wait()
        self.instance.close()
        # drop any cached client channel so a restart on the same port isn't
        # hit through a channel stuck in reconnect backoff
        close_channels(self.address)


def wire_peerlink(cluster: "LocalCluster", old_nodes: Sequence[int] = ()):
    """Attach a peerlink service to every instance at grpc port + one
    shared offset (the daemon's production convention) and point the
    instances' peer clients at it. Returns the service list (callers own
    closing them), or [] when no offset binds cleanly — gRPC then carries
    every peer call, exactly like a fleet with the link disabled. The
    instances indexed by `old_nodes` get a server that never greets (an
    old binary on the wire, for the mixed-version interop tests)."""
    from gubernator_tpu.service.peerlink import PeerLinkError, PeerLinkService

    ports = [int(ci.address.rsplit(":", 1)[1]) for ci in cluster.instances]
    for offset in (1000, 2000, 3000, 5000):
        attempt: List[PeerLinkService] = []
        try:
            for i, ci in enumerate(cluster.instances):
                attempt.append(
                    PeerLinkService(ci.instance, port=ports[i] + offset,
                                    wire_v2=i not in old_nodes))
        except PeerLinkError:
            for svc in attempt:
                svc.close()
            continue
        for ci in cluster.instances:
            ci.instance.conf.behaviors.peer_link_offset = offset
        return attempt
    return []


class LocalCluster:
    """A loopback cluster of real servers (reference: cluster/cluster.go)."""

    def __init__(self):
        self.instances: List[ClusterInstance] = []

    # ------------------------------------------------------------ lifecycle

    def start(self, n: int, datacenters: Optional[Sequence[str]] = None,
              capacity: int = 4096,
              behaviors: Optional[BehaviorConfig] = None) -> "LocalCluster":
        """Boot n instances on dynamic loopback ports and wire full peer
        lists (reference: cluster/cluster.go:104-128)."""
        datacenters = list(datacenters or [""] * n)
        for i in range(n):
            self.start_instance(datacenter=datacenters[i], capacity=capacity,
                                behaviors=behaviors)
        self.sync_peers()
        return self

    def start_instance(self, datacenter: str = "", capacity: int = 4096,
                       fixed_port: int = 0,
                       behaviors: Optional[BehaviorConfig] = None
                       ) -> ClusterInstance:
        """(reference: cluster/cluster.go:138-165)"""
        backend = Engine(capacity=capacity, min_width=32, max_width=256)
        backend.warmup()  # compile all width buckets before serving
        metrics = Metrics()
        backend.metrics = metrics  # engine phase histograms, as the daemon
        inst = Instance(
            InstanceConfig(
                behaviors=dataclasses.replace(behaviors) if behaviors
                else test_behaviors(),
                data_center=datacenter,
                backend=backend,
                metrics=metrics,
            ),
            advertise_address="pending",
        )
        server, port = make_server(inst, f"127.0.0.1:{fixed_port}")
        address = f"127.0.0.1:{port}"
        inst.advertise_address = address
        ci = ClusterInstance(
            address=address, datacenter=datacenter, instance=inst,
            server=server, metrics=metrics,
        )
        server.start()
        # a restart on a fixed port replaces the stopped entry, so
        # sync_peers/instance_for_host never see a dead duplicate address
        for i, old in enumerate(self.instances):
            if old.address == address:
                self.instances[i] = ci
                return ci
        self.instances.append(ci)
        return ci

    def sync_peers(self) -> None:
        """Push the full membership to every live instance
        (reference: cluster/cluster.go:124-127)."""
        infos = [
            PeerInfo(address=ci.address, datacenter=ci.datacenter)
            for ci in self.instances
        ]
        for ci in self.instances:
            ci.instance.set_peers(infos)

    def stop(self) -> None:
        for ci in self.instances:
            ci.stop()
        self.instances = []

    # -------------------------------------------------------------- helpers

    def peers(self) -> List[PeerInfo]:
        return [
            PeerInfo(address=ci.address, datacenter=ci.datacenter)
            for ci in self.instances
        ]

    def instance_for_host(self, address: str) -> Optional[ClusterInstance]:
        """(reference: cluster/cluster.go:84-91)"""
        for ci in self.instances:
            if ci.address == address:
                return ci
        return None

    def stop_instance_at(self, idx: int) -> None:
        """Kill one instance WITHOUT updating peers — fault injection
        (reference: cluster/cluster.go:93-96)."""
        self.instances[idx].stop()

    def owner_of(self, key: str) -> ClusterInstance:
        """The instance whose picker owns `key`."""
        peer = self.instances[0].instance.get_peer(key)
        ci = self.instance_for_host(peer.info.address)
        assert ci is not None
        return ci
