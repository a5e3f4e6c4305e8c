"""Multi-host tier: jax.distributed process groups + DCN collectives.

The reference scales across machines with one flat gRPC peer mesh
(reference: peers.proto:28-34, peer_client.go) — every aggregate flow
(GLOBAL hit forwarding, owner broadcasts) is O(peers) unary RPCs. Here the
host tier keeps gRPC for *request routing* (service/instance.py forwards to
the owning host exactly like the reference), while the *aggregate* flows can
ride XLA collectives across the whole process group:

- `initialize_from_env()` forms the jax.distributed process group
  (GUBER_COORDINATOR_ADDRESS / GUBER_NUM_HOSTS / GUBER_HOST_ID — the same
  role as the reference's discovery wiring, cmd/gubernator/main.go:87-121,
  but for the device fabric rather than the serving fabric). After it, the
  processes share one global device view and collectives cross host
  boundaries over ICI within a pod and DCN between pods.
- `CrossHostHitSync` is the DCN analogue of parallel/global_sync.py's
  intra-host psum: each host contributes its per-global-key hit-delta
  vector; ONE psum leaves every host holding the cluster-total — the
  reference needs a gRPC fan-in to the owner plus a fan-out broadcast
  (global.go:116-156, 219-236) for the same information flow.

Lockstep contract: every participating host must call `step()` the same
number of times (SPMD). Drive it from a fixed-cadence sync loop, never
on-demand; a host that stops ticking stalls the collective on every other
host (jax.distributed surfaces missing-participant errors after its
timeout). This is the standard TPU-fleet pattern — the serving path is
never blocked by the sync loop.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax
import numpy as np


log = logging.getLogger("gubernator_tpu.multihost")

NODE_AXIS = "node"


def initialize_from_env(
    coordinator_address: Optional[str] = None,
    num_hosts: Optional[int] = None,
    host_id: Optional[int] = None,
) -> bool:
    """Form the cross-host process group; no-op for single-host deployments.

    Arguments default to GUBER_COORDINATOR_ADDRESS, GUBER_NUM_HOSTS and
    GUBER_HOST_ID. Returns True when a multi-host group was initialized.
    Must run before the first jax backend use in the process.
    """
    if coordinator_address is None:
        coordinator_address = os.environ.get("GUBER_COORDINATOR_ADDRESS", "")
    if num_hosts is None:
        num_hosts = int(os.environ.get("GUBER_NUM_HOSTS", "1"))
    if host_id is None:
        host_id = int(os.environ.get("GUBER_HOST_ID", "0"))
    if num_hosts <= 1:
        return False
    if not coordinator_address:
        raise ValueError(
            "GUBER_NUM_HOSTS > 1 requires GUBER_COORDINATOR_ADDRESS")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_hosts,
        process_id=host_id,
    )
    log.info(
        "joined process group: host %d/%d, %d global / %d local devices",
        host_id, num_hosts, len(jax.devices()), len(jax.local_devices()),
    )
    return True


def make_node_mesh(devices=None) -> jax.sharding.Mesh:
    """1-D mesh over every device of every host (the collective fabric)."""
    devices = list(devices if devices is not None else jax.devices())
    return jax.sharding.Mesh(np.array(devices, dtype=object), (NODE_AXIS,))


class CollectiveGlobalChannel:
    """One lockstep dispatch carrying the whole cross-host GLOBAL exchange.

    Three logical flows share a single collective step (the reference needs
    two asynchronous gRPC pipelines for the same information movement,
    global.go:73-156 hit fan-in and global.go:159-239 state fan-out):

    - ``delta``  i64[G]: this host's queued hit deltas → psum = cluster total
      per slot, delivered to the slot owner.
    - ``claim``  i64[G]: nonzero key-claim hash per slot this host uses.
      Slots are assigned deterministically (hash of the key), so two hosts
      using the same slot for DIFFERENT keys is possible; the claim triple
      (sum, max, count) lets every host verify agreement — a slot is clean
      for me iff ``sum == count * max and max == my_claim``. Hosts only
      contribute deltas/state on slots verified clean on a PREVIOUS tick,
      so a conflict can never mix two keys' hits.
    - ``state``  i64[5, G]: rows (valid, status, limit, remaining,
      reset_time). The owning host contributes its authoritative post-apply
      state with valid=1; psum hands it to every host. valid != 1 (owner
      missing, or two hosts claiming ownership during a membership change)
      means "do not apply this tick".

    Lockstep contract is the same as CrossHostHitSync: every host calls
    step() in the same sequence, on a fixed cadence.
    """

    def __init__(self, global_capacity: int, mesh=None):
        self.global_capacity = global_capacity
        self.mesh = mesh if mesh is not None else make_node_mesh()
        self._n_local = len(self.mesh.local_devices)
        self._row = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(NODE_AXIS, None))
        self._row3 = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(NODE_AXIS, None, None))

        def _exchange(delta, claim, state):
            # each block sees ONE device's contribution rows
            import jax.numpy as jnp

            d = jax.lax.psum(delta[0], NODE_AXIS)
            c_sum = jax.lax.psum(claim[0], NODE_AXIS)
            c_max = jax.lax.pmax(claim[0], NODE_AXIS)
            c_cnt = jax.lax.psum(
                (claim[0] != 0).astype(jnp.int64), NODE_AXIS)
            st = jax.lax.psum(state[0], NODE_AXIS)
            return d, c_sum, c_max, c_cnt, st

        spec_r = jax.sharding.PartitionSpec(NODE_AXIS, None)
        spec_r3 = jax.sharding.PartitionSpec(NODE_AXIS, None, None)
        self._step = jax.jit(jax.shard_map(
            _exchange, mesh=self.mesh,
            in_specs=(spec_r, spec_r, spec_r3),
            out_specs=(jax.sharding.PartitionSpec(),) * 5,
        ))
        self.steps = 0

    def warm(self, timeout_s: float = 600.0) -> None:
        """Compile the exchange and form the fabric context in LOCKSTEP.

        The backend's first cross-host exchange has a fixed internal
        context-formation deadline (Gloo on CPU: ~30 s). Hosts whose
        compiles serialize — cold caches, shared CPUs, heterogeneous boot
        times — enter their first exchange minutes apart and the earliest
        one times out, killing the whole process group. So: (1) AOT-compile
        the step locally (arbitrary skew is fine), (2) rendezvous every
        host at the coordination service's barrier (already up — the
        process group formed at boot), (3) run one all-zeros exchange with
        every host inside the deadline window. Call at BOOT, before the
        tick cadence starts: a broken fabric fails loudly here instead of
        mid-serving."""
        G = self.global_capacity
        d = np.zeros((self._n_local, G), np.int64)
        s = np.zeros((self._n_local, 5, G), np.int64)
        args = (
            jax.make_array_from_process_local_data(self._row, d),
            jax.make_array_from_process_local_data(self._row, d),
            jax.make_array_from_process_local_data(self._row3, s),
        )
        self._step.lower(*args).compile()  # local compile, no exchange
        try:
            from jax._src import distributed

            client = distributed.global_state.client
        except Exception:  # noqa: BLE001 — older jax layouts
            client = None
        if client is None:
            log.warning(
                "no distributed-client barrier available: hosts enter the "
                "first exchange unsynchronized — serialized cold-cache "
                "compiles can blow the fabric's context-formation deadline")
        else:
            client.wait_at_barrier(
                "guber_collective_warm", int(timeout_s * 1000))
        self.step(np.zeros(G, np.int64), np.zeros(G, np.int64),
                  np.zeros((5, G), np.int64))
        log.info("collective channel warmed (fabric context formed)")

    def step(self, delta: np.ndarray, claim: np.ndarray,
             state: np.ndarray):
        """One collective tick. Returns host arrays
        (total_delta[G], claim_sum[G], claim_max[G], claim_cnt[G],
        state[5, G])."""
        G = self.global_capacity
        d = np.zeros((self._n_local, G), np.int64)
        c = np.zeros((self._n_local, G), np.int64)
        s = np.zeros((self._n_local, 5, G), np.int64)
        d[0], c[0], s[0] = delta, claim, state
        args = (
            jax.make_array_from_process_local_data(self._row, d),
            jax.make_array_from_process_local_data(self._row, c),
            jax.make_array_from_process_local_data(self._row3, s),
        )
        out = self._step(*args)
        self.steps += 1
        return tuple(np.asarray(o) for o in out)


class CrossHostHitSync:
    """Lockstep psum of per-host hit-delta vectors across the process group.

    Layout: a global i64[D, G] array (D = all devices, G = global-key
    capacity) sharded one row per device. Each host writes its delta into
    its FIRST local device's row, zeros elsewhere; the psum over the node
    axis leaves every host the cluster total. Call `step` at a fixed
    cadence from every host (see the lockstep contract in the module doc).
    """

    def __init__(self, global_capacity: int, mesh=None):
        self.global_capacity = global_capacity
        self.mesh = mesh if mesh is not None else make_node_mesh()
        self._n_local = len(self.mesh.local_devices)
        self._row_sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(NODE_AXIS, None))

        def _psum(delta):
            # each shard_map block is ONE device's (1, G) row slice
            return jax.lax.psum(delta[0], NODE_AXIS)

        self._step = jax.jit(jax.shard_map(
            _psum, mesh=self.mesh,
            in_specs=jax.sharding.PartitionSpec(NODE_AXIS, None),
            out_specs=jax.sharding.PartitionSpec(),
        ))
        self.steps = 0

    def step(self, local_delta: np.ndarray) -> np.ndarray:
        """One collective tick: contribute this host's i64[G] delta, return
        the i64[G] total over every host."""
        rows = np.zeros((self._n_local, self.global_capacity), np.int64)
        rows[0] = local_delta
        garr = jax.make_array_from_process_local_data(self._row_sharding, rows)
        out = self._step(garr)
        self.steps += 1
        return np.asarray(out)
