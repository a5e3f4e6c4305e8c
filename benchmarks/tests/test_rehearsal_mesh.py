"""A traced rehearsal of the four-chip cell: the daemon over `ShardedEngine`
on four virtual CPU devices at a tiny table, the same load generators,
scrapes, checker and result line as on the chip. (Its width-64 ladder cuts
a 1000-item call into 16 spans served launch_/collect_columnar_windows;
the chip run serves one span submit_/complete_columnar.)"""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO, listed, reads_on_a_cpu

CELL = "mesh40m.batch1000"


def test_traced_rehearsal_of_the_mesh_cell_is_well_formed():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(2**31 + 133), "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {ln.get("step", "result"): ln
           for ln in map(json.loads, r.stdout.splitlines())}
    check, result = out["check"], out["result"]
    assert check["sound"] is True, check
    assert check["compared"]["audit_mismatches"]["value"] == 0
    assert check["compared"]["audited_answers"]["value"] >= 1000
    assert result["correct"] is False and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    device = result["device"]
    assert device["platform"] == "cpu" and device["count"] == 4
    assert 0 < device["busy_s"] <= device["window_s"]
    assert set(result["end_to_end"]) == listed(manifest, "end_to_end", CELL)
    # every reader the manifest lists for the cell but the roofline, whose
    # peaks know no CPU
    assert set(result["metrics"]) == set(filter(
        reads_on_a_cpu, listed(manifest, "per_layer", CELL)))
    assert out["reader_skipped"]["name"] == "mesh.decide_roofline"
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["mesh.shard_skew"] >= 1.0
    assert 0 <= m["mesh.device_idle_share"] <= 1
    assert all(m[k] > 0 for k in m if k.endswith("_ms_per_window"))
    # nothing of the run is left behind
    assert subprocess.run(["pgrep", "-f", "[g]ubernator_tpu.cmd.daemon"],
                          capture_output=True).stdout == b""
