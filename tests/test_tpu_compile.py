"""The serving path's device programs, compiled by the TPU's own compiler
for a v5e that is described, not attached (no chip time, nothing runs).

What this guards: every program `Engine.warmup()` needs at the README's
table size (10M rows, u32[10_000_000, 16] = 640 MB) is accepted by the
chip's compiler, updates the donated table in place WITHOUT a table-sized
temp or a 64-bit split/combine of the table (the chip has no 64-bit
integers: an s64 table costs three table-sized passes a launch, PERF.md
PR 29), and — for the mesh tier — splits the table four ways and reduces
GLOBAL hits with a real all-reduce. A pass here is a compile, never a
chip run.

The topology is described inside a module-scoped fixture (never at
import: only one process may load libtpu, and every xdist worker imports
every test file), and this is the only file that does so. The persistent
compile cache is off around the compiles: an entry written for a
described device cannot be read back without the chip.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import gubernator_tpu.models.engine as engine_mod
import gubernator_tpu.ops.decide  # noqa: F401  (the package re-exports the function)

D = sys.modules["gubernator_tpu.ops.decide"]

CAPACITY = 10_000_000  # README: resident keys of one chip
TABLE_BYTES = CAPACITY * 8 * 8
TEMP_LIMIT = 16 << 20  # a launch's scratch: lanes, never the table
WIDTH = 64  # widths 1024/8192 take 34-43 s each: compiled by hand, CHANGES.md
I32, I64, U32 = jnp.int32, jnp.int64, jnp.uint32


def _touches_only_its_rows(compiled):
    """No table-sized temp, and no 64-bit emulation op (X64SplitLow/High,
    X64Combine) over a table-sized operand."""
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < TEMP_LIMIT, mem
    whole = [line.strip()[:200] for line in compiled.as_text().splitlines()
             if "X64" in line and f"[{CAPACITY}," in line]
    assert not whole, whole


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def plan(topo, no_compile_cache):
    from jax.sharding import Mesh

    from gubernator_tpu.parallel.mesh import (
        REGION_AXIS, SHARD_AXIS, MeshPlan)

    mesh = Mesh(np.array(topo.devices, dtype=object).reshape(1, 4),
                (REGION_AXIS, SHARD_AXIS))
    return MeshPlan(mesh=mesh, capacity_per_shard=CAPACITY)


def _window_shapes(name, s):
    """Argument shapes after the table, as Engine.warmup() sends them."""
    now = s((), I64)
    return {
        "decide_packed": (s((9, WIDTH), I64), now),
        "decide_packed_compact": (s((D.COMPACT_ROWS, WIDTH), I32), now),
        "decide_packed_lean": (
            s((WIDTH,), I32), s((D.LEAN_MAX_CFG, 4), I64), now),
        "decide_scan_packed": (s((32, 9, WIDTH), I64), now),
        "decide_scan_carried": (s((32, 9, WIDTH), I64), now),
        "decide_scan_carried_compact": (
            s((32, D.COMPACT_ROWS, WIDTH), I32), now),
        "decide_scan_carried_lean": (
            s((32, WIDTH), I32), s((D.LEAN_MAX_CFG, 4), I64), now),
        # a pull's run of three calls on the lean lane: the launch of
        # every window of onehit10m.batch1000 (PERF.md section 6, PR 46)
        "decide_scan_packed_lean": (
            s((4, WIDTH), I32), s((D.LEAN_MAX_CFG, 4), I64), now),
    }[name]


class TestOneChip:
    @pytest.mark.parametrize("name", [
        "decide_packed", "decide_packed_compact", "decide_packed_lean",
        "decide_scan_packed", "decide_scan_carried",
        "decide_scan_carried_compact", "decide_scan_carried_lean",
        "decide_scan_packed_lean"])
    def test_decide_compiles_and_aliases_the_table(self, one_chip, name):
        table = one_chip((CAPACITY, D.TABLE_ROW_WORDS), U32)
        compiled = jax.jit(getattr(D, name), donate_argnums=(0,)).lower(
            table, *_window_shapes(name, one_chip)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= TABLE_BYTES, mem
        assert mem.argument_size_in_bytes < TABLE_BYTES * 1.01, mem
        _touches_only_its_rows(compiled)

    def test_gather_compiles(self, one_chip):
        """The lone-miss mirror seed's 1-slot gather reads the table and
        hands back 7 one-element columns (tile-padded): one row's words,
        no temp of half the table (320 MB for an s64 table)."""
        compiled = jax.jit(engine_mod._gather_rows).lower(
            one_chip((CAPACITY, D.TABLE_ROW_WORDS), U32),
            one_chip((1,), I32)).compile()
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes < 65536, mem
        _touches_only_its_rows(compiled)

    def test_inject_compiles_and_aliases_the_table(self, one_chip):
        col64, col32 = one_chip((WIDTH,), I64), one_chip((WIDTH,), I32)
        compiled = jax.jit(engine_mod._inject_rows, donate_argnums=(0,)).lower(
            one_chip((CAPACITY, D.TABLE_ROW_WORDS), U32), col32, col32,
            col64, col64, col64, col64, col64, col32).compile()
        assert compiled.memory_analysis().alias_size_in_bytes >= TABLE_BYTES
        _touches_only_its_rows(compiled)


class TestFourChips:
    def test_sharded_decide_holds_a_quarter_each(self, plan):
        from gubernator_tpu.parallel.sharded import make_decide_sharded

        step = make_decide_sharded(plan, donate=True)
        state = jax.ShapeDtypeStruct(
            (1, 4, CAPACITY, D.TABLE_ROW_WORDS), U32,
            sharding=plan.state_sharding())
        packed = jax.ShapeDtypeStruct(
            (1, 4, 9, WIDTH), I64, sharding=plan.state_sharding())
        now = jax.ShapeDtypeStruct((), I64, sharding=plan.replicated())
        compiled = step.lower(state, packed, now).compile()
        mem = compiled.memory_analysis()
        # per-device bytes: a quarter of the 4 x 640 MB table plus one window
        assert TABLE_BYTES <= mem.argument_size_in_bytes < TABLE_BYTES * 1.01
        assert mem.alias_size_in_bytes >= TABLE_BYTES, mem
        _touches_only_its_rows(compiled)
        _touches_only_its_rows(compiled)  # memory_analysis is per device
        # owner-local mutation: the normal path moves nothing between chips
        assert "all-reduce" not in compiled.as_text()

    @pytest.mark.parametrize("name", ["gather", "inject"])
    def test_sharded_row_steps_touch_only_their_rows(self, plan, name):
        """The Store hooks' mesh gather and inject (restore, read-through):
        each chip reads or writes its own lanes' rows, in place."""
        from gubernator_tpu.parallel import sharded

        sh = plan.state_sharding()
        state = jax.ShapeDtypeStruct(
            (1, 4, CAPACITY, D.TABLE_ROW_WORDS), U32, sharding=sh)
        slot = jax.ShapeDtypeStruct(
            (1, 4, WIDTH), I32, sharding=jax.sharding.NamedSharding(
                plan.mesh, jax.sharding.PartitionSpec(*sh.spec[:3])))
        if name == "gather":
            compiled = sharded.make_gather_sharded(plan).lower(
                state, slot).compile()
        else:
            rows = jax.ShapeDtypeStruct((1, 4, 7, WIDTH), I64, sharding=sh)
            compiled = sharded.make_inject_sharded(plan, donate=True).lower(
                state, slot, rows).compile()
            assert compiled.memory_analysis().alias_size_in_bytes \
                >= TABLE_BYTES
        _touches_only_its_rows(compiled)

    def test_global_sync_psum_is_an_all_reduce(self, plan):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from gubernator_tpu.parallel.global_sync import (
            GlobalConfig, make_global_sync)
        from gubernator_tpu.parallel.mesh import REGION_AXIS, SHARD_AXIS

        G = 1024  # ShardedEngine's default global_capacity
        rep = plan.replicated()

        def r(dtype):
            return jax.ShapeDtypeStruct((G,), dtype, sharding=rep)

        state = jax.ShapeDtypeStruct(
            (1, 4, CAPACITY, D.TABLE_ROW_WORDS), U32,
            sharding=plan.state_sharding())
        delta = jax.ShapeDtypeStruct(
            (1, 4, G), I64,
            sharding=NamedSharding(plan.mesh, P(REGION_AXIS, SHARD_AXIS, None)))
        cfg = GlobalConfig(
            slot=r(I32), owner=r(I32), limit=r(I64), duration=r(I64),
            algorithm=r(I32), behavior=r(I32), greg_expire=r(I64),
            greg_interval=r(I64), fresh=r(jnp.bool_))
        now = jax.ShapeDtypeStruct((), I64, sharding=rep)
        sync = make_global_sync(plan, donate=True)
        compiled = sync.lower(state, delta, cfg, now).compile()
        assert "all-reduce" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert TABLE_BYTES <= mem.argument_size_in_bytes < TABLE_BYTES * 1.01
        assert mem.alias_size_in_bytes >= TABLE_BYTES, mem
        _touches_only_its_rows(compiled)
