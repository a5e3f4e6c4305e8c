"""The bucket table's stored layout: u32[..., C, 16] rows on the device,
64-bit fields only on gathered lanes (ops/decide.py make_table / load_rows /
store_rows / load_column / host_rows).

The contract held here: word 2f is the low and word 2f+1 the high half of
field f (little-endian, so the host's i64[n, 8].view("<u4") IS the device
row), every int64 value survives a store/load, padding lanes are dropped,
and nothing host-facing moved — a `.gtslab` snapshot is byte-identical to
the one the i64[C, 8] table wrote (sha256 recorded at commit 14e1acf, the
last tree with that table) and restores to identical answers.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gubernator_tpu as g
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.ops.decide import (
    ROW_ALGO,
    ROW_HITS,
    TABLE_ROW_FIELDS,
    TABLE_ROW_WORDS,
    fetch_column,
    fetch_rows,
    host_rows,
    host_words,
    load_column,
    load_rows,
    make_table,
    store_rows,
)
from gubernator_tpu.store import BinarySnapshotLoader

I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min
C = 32

_STORE = jax.jit(store_rows)
_LOAD = jax.jit(load_rows)


@pytest.mark.parametrize("value", [
    0, 1, -1, -2, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**32 + 1, -2**31,
    -2**32, -2**32 - 1, 2**40 + 5, 1_700_000_000_000, I64_MAX, I64_MIN,
    I64_MAX - 1, I64_MIN + 1, 0x0123456789ABCDEF, -0x0123456789ABCDEF,
], ids=lambda v: f"{v:#x}")
def test_store_then_load_returns_the_value(value):
    """Every field position carries the value through the two words."""
    rows = np.full((3, TABLE_ROW_FIELDS), value, np.int64)
    rows[1] = np.arange(TABLE_ROW_FIELDS) - 3  # a neighbour that differs
    slot = jnp.asarray([5, 0, C - 1], jnp.int32)
    state = _STORE(make_table(C), slot, jnp.asarray(rows))
    assert state.dtype == jnp.uint32
    assert state.shape == (C, TABLE_ROW_WORDS)
    np.testing.assert_array_equal(np.asarray(_LOAD(state, slot)), rows)
    # the device row IS the host's little-endian view of the i64 row
    np.testing.assert_array_equal(
        np.asarray(state)[np.asarray(slot)], rows.view("<u4"))
    np.testing.assert_array_equal(fetch_rows(state, slot), rows)


def test_make_table_is_vacant_rows():
    state = make_table(C)
    assert state.dtype == jnp.uint32
    assert state.shape == (C, TABLE_ROW_WORDS)
    assert state.nbytes == C * 64
    rows = host_rows(state)
    assert rows.shape == (C, TABLE_ROW_FIELDS) and rows.dtype == np.int64
    assert (rows[:, ROW_ALGO] == -1).all()
    assert (rows[:, 1:] == 0).all()
    words = np.asarray(state)
    assert (words[:, :2] == 0xFFFFFFFF).all() and (words[:, 2:] == 0).all()


def test_padding_lanes_are_dropped():
    """slot == -1 must not wrap into the last row (pad_to_drop), and an
    out-of-range-high slot is dropped too."""
    rows = np.arange(4 * TABLE_ROW_FIELDS, dtype=np.int64).reshape(4, -1) + 7
    slot = jnp.asarray([2, -1, C, -1], jnp.int32)
    state = _STORE(make_table(C), slot, jnp.asarray(rows))
    got = host_rows(state)
    np.testing.assert_array_equal(got[2], rows[0])
    untouched = np.delete(got, 2, axis=0)
    np.testing.assert_array_equal(
        untouched, np.delete(host_rows(make_table(C)), 2, axis=0))


def test_host_view_equals_load_rows_of_every_row():
    rng = np.random.default_rng(29)
    rows = rng.integers(I64_MIN, I64_MAX, (C, TABLE_ROW_FIELDS),
                        dtype=np.int64, endpoint=True)
    every = jnp.arange(C, dtype=jnp.int32)
    state = _STORE(make_table(C), every, jnp.asarray(rows))
    np.testing.assert_array_equal(host_rows(state), rows)
    np.testing.assert_array_equal(np.asarray(_LOAD(state, every)), rows)
    np.testing.assert_array_equal(
        np.asarray(state).view(np.int64), np.asarray(_LOAD(state, every)))
    # and back: the host's rows are the device's words
    np.testing.assert_array_equal(host_words(rows), np.asarray(state))


@pytest.mark.parametrize("field", range(TABLE_ROW_FIELDS))
def test_columns_read_like_rows(field):
    rng = np.random.default_rng(field)
    rows = rng.integers(I64_MIN, I64_MAX, (2, 3, C, TABLE_ROW_FIELDS),
                        dtype=np.int64, endpoint=True)
    state = jnp.asarray(host_words(rows))  # a mesh-shaped table [R, S, C, 16]
    np.testing.assert_array_equal(
        np.asarray(jax.jit(load_column, static_argnums=1)(state, field)),
        rows[..., field])
    np.testing.assert_array_equal(fetch_column(state, field),
                                  rows[..., field])


def test_sharded_table_is_the_same_rows():
    from gubernator_tpu.parallel.mesh import make_mesh, make_sharded_table, \
        MeshPlan

    plan = MeshPlan(mesh=make_mesh(n_shards=4), capacity_per_shard=C)
    state = make_sharded_table(plan)
    assert state.dtype == jnp.uint32
    assert state.shape == (1, 4, C, TABLE_ROW_WORDS)
    assert state.sharding == plan.state_sharding()
    np.testing.assert_array_equal(
        host_rows(state)[0, 2], host_rows(make_table(C)))


# ---------------------------------------------------------------- snapshot

NOW = 1_700_000_000_000
# sha256 of the .gtslab file _served_engine() saves, recorded with the
# i64[C, 8] table of commit 14e1acf (same requests, same clock).
SNAPSHOT_SHA256 = (
    "119596f020722701e5a0c6d1a89f2fb3212a0495036570f11b8dc50869f3d65a")
# sha256 of repr(_answers(eng)) on that engine, same commit
ANSWERS_SHA256 = (
    "a2399eed38cea295c545cbc970bbbbdab98f2c9e27533eb077f6de5048bbc510")


def _requests(salt):
    return [g.RateLimitReq(
        name="layout", unique_key=f"k{(i * 7 + salt) % 300}",
        hits=1 + (i + salt) % 3, limit=(10, 100, 5_000_000_000)[i % 3],
        duration=3_600_000 + 1000 * (i % 5),
        algorithm=g.Algorithm(i % 2)) for i in range(200)]


def _served_engine():
    eng = Engine(capacity=512, min_width=64, max_width=256)
    for step in range(4):
        eng.get_rate_limits(_requests(step), now_ms=NOW + step)
    return eng


def _answers(eng):
    return [(r.status, r.limit, r.remaining, r.reset_time)
            for step in range(4, 7)
            for r in eng.get_rate_limits(_requests(step), now_ms=NOW + step)]


def test_snapshot_file_is_byte_identical_and_restores_the_answers(tmp_path):
    path = str(tmp_path / "layout.gtslab")
    eng = _served_engine()
    loader = BinarySnapshotLoader(path)
    loader.save_slabs(eng.snapshot_slabs(include_expired=True))
    with open(path, "rb") as f:
        blob = f.read()
    assert blob[:8] == b"GTSLAB1\n"
    assert hashlib.sha256(blob).hexdigest() == SNAPSHOT_SHA256

    keys = sorted({r.hash_key() for step in range(4)
                   for r in _requests(step)})
    restored = Engine(capacity=512, min_width=64, max_width=256)
    assert restored.load_snapshot_slabs(loader.load_slabs()) == len(keys)
    # the restored rows are the served rows (the hit counter is not in a
    # snapshot: it restarts at 0)
    a = fetch_rows(eng.state, eng.directory.lookup(keys)[0])
    b = fetch_rows(restored.state, restored.directory.lookup(keys)[0])
    np.testing.assert_array_equal(a[:, :ROW_HITS], b[:, :ROW_HITS])
    assert (b[:, ROW_HITS] == 0).all() and (a[:, ROW_HITS] > 0).all()
    answers = _answers(eng)
    assert _answers(restored) == answers
    assert hashlib.sha256(repr(answers).encode()).hexdigest() \
        == ANSWERS_SHA256
