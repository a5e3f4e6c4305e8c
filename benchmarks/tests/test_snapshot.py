"""The snapshot the benchmark writes is one the program restores."""

import json
import os

import numpy as np

from conftest import BENCH
from keymodel import KeyModel, write_snapshot


def test_snapshot_loads_through_the_programs_loader(tmp_path):
    from gubernator_tpu.store import BinarySnapshotLoader

    with open(os.path.join(BENCH, "configs", "node-1chip-10m.json")) as f:
        model = KeyModel(json.load(f)["key_model"], seed=2**31 + 11)
    path = str(tmp_path / "snap.gtslab")
    n = (1 << 20) + 77  # more than one chunk
    write_snapshot(path, model, n, stamp_ms=1_700_000_000_000)
    seen = 0
    for blob, off, rows in BinarySnapshotLoader(path).load_slabs():
        m = len(off) - 1
        ids = np.arange(seen, seen + m, dtype=np.uint64)
        assert np.array_equal(rows, model.resident_rows(ids, 1_700_000_000_000))
        assert bytes(blob[off[0]:off[1]]) == b"rl_acct:%08x" % seen
        assert bytes(blob[off[m - 1]:off[m]]) == b"rl_acct:%08x" % (seen + m - 1)
        seen += m
    assert seen == n
    f = model.fields(np.arange(n))
    assert set(np.unique(f["limit"])) == {10, 100, 1000, 100_000}
    assert set(np.unique(f["algorithm"])) == {0, 1}
    assert set(np.unique(f["hits"])) == {1, 2, 3}
