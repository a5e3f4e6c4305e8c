"""The rest of a run with the look for a chip skipped: the daemon on the
CPU at a tiny table, the same load generators, scrapes, checker and result
line. With the timed path broken underneath (the table keeps one token too
many per key, as a store that lost hits would) `correct` comes out false
because the comparison fails, not only because this is a rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO


def rehearse(*extra):
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "node10m.batch1000", "--seed", str(2**31 + 99), "--seconds", "3",
         "--rehearse", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()]
    return {ln.get("step", "result"): ln for ln in lines}


@pytest.mark.parametrize("extra,sound", [
    (("--trace", "0"), True),
    (("--trace", "0", "--control", "lost_hits"), False),
])
def test_check_passes_sound_and_fails_broken(extra, sound):
    out = rehearse(*extra)
    check = out["check"]
    assert check["sound"] is sound, check
    assert (check["compared"]["audit_mismatches"]["value"] == 0) is sound
    assert check["compared"]["audited_answers"]["value"] >= 1000
    result = out["result"]
    assert result["correct"] is False and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {
        "decisions_per_s", "call_p50_ms", "call_p99_ms", "daemon_rss_mb",
        "setup_s"}
    assert result["failed"] == 0 and result["attempted"] > 0


def test_traced_rehearsal_reports_layer_metrics():
    out = rehearse("--trace", "1")
    result = out["result"]
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > 0
    assert {"loadgen_cpu_share", "window_fill", "queue_wait_ms",
            "prep_ms_per_window", "rounds_per_window",
            "readback_ms_per_window", "device_ms_per_window",
            "device_idle_share", "ready_s", "restore_s"} <= set(
                result["metrics"])
    assert len(result["breakdown"]["device_ops"]) <= 10
