"""Device mesh construction and key-table sharding.

The reference shards its key space across a cluster of Go processes with a
consistent-hash ring: exactly one peer owns each key and all mutation happens
there (reference: architecture.md:13-17, hash.go:83-99). Here the same
ownership idea maps onto a TPU mesh: the key table's slot dimension is
sharded over a 2-D mesh of axes ("region", "shard"); a key's owner chip is a
deterministic hash of the key, and all mutation of that key's row happens in
that chip's HBM shard.

- axis "shard": intra-pod key-space partition (the ICI tier — replaces the
  reference's peer-to-peer gRPC forwarding, peers.proto:28-34).
- axis "region": the DCN tier (replaces the reference's multi-datacenter
  region pickers, region_picker.go:7-95).

Host processes still route *requests* to the owning host (service tier, like
the reference's PeersV1 forwarding) — the mesh shards *state* within the
process group.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.ops.decide import TableState, vacant_rows
from gubernator_tpu.utils.fnv import fnv1a_64_str

REGION_AXIS = "region"
SHARD_AXIS = "shard"


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A mesh plus the table geometry sharded over it."""

    mesh: Mesh
    capacity_per_shard: int

    @property
    def n_regions(self) -> int:
        return self.mesh.devices.shape[0]

    @property
    def n_shards(self) -> int:
        return self.mesh.devices.shape[1]

    @property
    def n_owners(self) -> int:
        return self.n_regions * self.n_shards

    @property
    def capacity(self) -> int:
        return self.n_owners * self.capacity_per_shard

    def state_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(REGION_AXIS, SHARD_AXIS, None, None))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def owner_coords(self, owner: int) -> Tuple[int, int]:
        return divmod(owner, self.n_shards)


def make_mesh(
    n_shards: Optional[int] = None,
    n_regions: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the ("region", "shard") mesh over the available devices."""
    devices = list(devices if devices is not None else jax.devices())
    if n_shards is None:
        if len(devices) % n_regions:
            raise ValueError(
                f"{len(devices)} devices not divisible into {n_regions} regions")
        n_shards = len(devices) // n_regions
    need = n_regions * n_shards
    if need > len(devices):
        raise ValueError(f"need {need} devices, have {len(devices)}")
    arr = np.array(devices[:need], dtype=object).reshape(n_regions, n_shards)
    return Mesh(arr, (REGION_AXIS, SHARD_AXIS))


def shard_of_key(key: str, n_owners: int) -> int:
    """Deterministic owner (linear mesh index) of a rate-limit key.

    The reference's consistent-hash `Get` (reference: hash.go:83-99) serves
    the same role for host peers; for device shards a plain mod is ideal —
    the mesh never resizes without a restart, so ring stability is moot.
    """
    return fnv1a_64_str(key) % n_owners


def make_sharded_table(plan: MeshPlan) -> TableState:
    """Fresh vacant row table u32[R, S, C, 16] sharded over the mesh."""
    shape = (plan.n_regions, plan.n_shards, plan.capacity_per_shard)
    return jax.jit(partial(vacant_rows, shape),
                   out_shardings=plan.state_sharding())()
