"""A pull's K one-call windows on the chip: K lock-step windows
(submit_columnar / complete_columnar, a launch each) against ONE scan group
(launch_columnar_windows / collect_columnar_windows), on a restored 10M-slot
table at the one-width ladder 8192. PERF.md section 6, PR 44 has its readings.

    chiprun --chips 1 --timeout 1500 -- python scripts/pull_group_microbench.py

One JSON line a case (`lockstep` or `group`, K = 2, 3, 4), one thread,
every number a mean over the case's pulls, in ms a PULL of K windows:
`launch_ms` (the submit or group launch: lock, C prep, stage, enqueue),
`collect_ms` (the wait for the chip, the copy back, the demux), `wall_ms`,
and from a jax.profiler capture of the same pulls `device_ms` (the chip's
programs) with `programs` (launches a pull). Then `two_workers`: two threads
serving such pulls back to back, as the daemon's two pull workers do, in
windows a second (K = 3 and 4, lock-step and grouped).
Exits non-zero off the chip: a CPU time is not a device time (`--rehearse`
runs the flow tiny on the CPU, for its control flow alone)."""

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from keymodel import HASH_PREFIX, KEY_PREFIX, NAME, KeyModel, key_bytes  # noqa: E402
from trace_reduce import MODULES_LINE, load_events  # noqa: E402

from gubernator_tpu.models import Engine  # noqa: E402

C = 10_000_000
RESIDENTS = 8_000_000
LANES = 1000
PULLS = 200
SEED = 2_147_483_648 + 44
REHEARSE = "--rehearse" in sys.argv[1:]
KEY_MODEL = {"limits": [10, 100, 1000, 100000], "algorithms": [0, 1],
             "hits": [1, 2, 3], "duration_ms": 3_600_000,
             "resident_used_share_max": 0.5}


def restore(eng, model, stamp_ms):
    def slabs():
        for lo in range(0, RESIDENTS, 1 << 20):
            ids = np.arange(lo, min(lo + (1 << 20), RESIDENTS), dtype=np.uint64)
            kb = key_bytes(HASH_PREFIX, ids)
            off = np.arange(len(ids) + 1, dtype=np.int64) * kb.shape[1]
            yield kb.tobytes(), off, model.resident_rows(ids, stamp_ms)
    return eng.load_snapshot_slabs(slabs())


def make_pull(model, rng, k):
    """K windows of LANES resident keys, distinct inside a window (a call
    of the batch1000 cell), as wire columns over one key arena."""
    n = k * LANES
    ids = np.concatenate([rng.choice(RESIDENTS, LANES, replace=False)
                          for _ in range(k)]).astype(np.uint64)
    f = model.fields(ids)
    kb = key_bytes(NAME.encode() + KEY_PREFIX, ids)
    cols = {
        "keys": kb.tobytes(),
        "key_off": (np.arange(n + 1, dtype=np.int64) * kb.shape[1]
                    ).astype(np.int32),
        "name_len": np.full(n, len(NAME), np.int32),
        "hits": f["hits"].astype(np.int64),
        "limit": f["limit"].astype(np.int64),
        "duration": np.full(n, model.duration_ms, np.int64),
        "algorithm": f["algorithm"].astype(np.int32),
        "behavior": np.zeros(n, np.int32),
        "out": [np.zeros(n, np.int32)] + [np.zeros(n, np.int64)
                                          for _ in range(3)],
    }
    return cols


def window(p, i):
    s0, s1 = i * LANES, (i + 1) * LANES
    return (LANES, p["keys"], p["key_off"][s0:s1 + 1], p["name_len"][s0:s1],
            p["hits"][s0:s1], p["limit"][s0:s1], p["duration"][s0:s1],
            p["algorithm"][s0:s1], p["behavior"][s0:s1])


def outs(p, i):
    return tuple(o[i * LANES:(i + 1) * LANES] for o in p["out"])


def serve_lockstep(eng, p, k, _staging):
    tl = tc = 0
    for i in range(k):
        t0 = time.perf_counter_ns()
        h = eng.submit_columnar(*window(p, i), 0)
        t1 = time.perf_counter_ns()
        left = eng.complete_columnar(h, *outs(p, i))
        t2 = time.perf_counter_ns()
        assert not len(left)
        tl += t1 - t0
        tc += t2 - t1
    return tl, tc


def serve_group(eng, p, k, staging):
    t0 = time.perf_counter_ns()
    h = eng.launch_columnar_windows([window(p, i) for i in range(k)], 0,
                                    staging=staging)
    t1 = time.perf_counter_ns()
    assert len(h[0]) == k and h[1] is None
    eng.collect_columnar_windows(h, [outs(p, i) for i in range(k)])
    t2 = time.perf_counter_ns()
    return t1 - t0, t2 - t1


def device_ms(trace_dir):
    # a CPU rehearsal has no `XLA Modules` line: its thunks stand in
    ev = [e for e in load_events(trace_dir, rehearse=REHEARSE)
          if REHEARSE or e[1] == MODULES_LINE]
    return sum(e[4] for e in ev) / 1e6, len(ev)


def case(eng, name, serve, pulls, k):
    staging = {}
    for p in pulls[:20]:
        serve(eng, p, k, staging)
    tl = tc = 0
    t0 = time.perf_counter()
    for p in pulls:
        a, b = serve(eng, p, k, staging)
        tl += a
        tc += b
    wall = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for p in pulls[:50]:
            serve(eng, p, k, staging)
        jax.profiler.stop_trace()
        dev, programs = device_ms(d)
    n = len(pulls)
    print(json.dumps({
        "case": name, "k": k, "pulls": n,
        "launch_ms": tl / n / 1e6, "collect_ms": tc / n / 1e6,
        "wall_ms": wall / n * 1e3, "device_ms": dev / 50,
        "programs": programs / 50}), flush=True)


def two_workers(eng, name, serve, pulls, k, seconds=4.0):
    done = [0, 0]
    stop = time.perf_counter() + seconds

    def work(w):
        staging = {}
        mine = pulls[w::2]
        i = 0
        while time.perf_counter() < stop:
            serve(eng, mine[i % len(mine)], k, staging)
            done[w] += k
            i += 1

    ts = [threading.Thread(target=work, args=(w,)) for w in (0, 1)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    print(json.dumps({"case": "two_workers." + name, "k": k,
                      "windows_per_s": sum(done) / wall}), flush=True)


def main():
    global C, RESIDENTS, PULLS
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "rehearse": REHEARSE}), flush=True)
    if REHEARSE:
        C, RESIDENTS, PULLS = 65536, 32768, 60
    elif dev.platform != "tpu":
        sys.exit("needs the chip: a CPU time is not a device time")
    model = KeyModel(KEY_MODEL, SEED)
    stamp = int(time.time() * 1000)
    eng = Engine(capacity=C, min_width=8192, max_width=8192)
    t0 = time.perf_counter()
    n = restore(eng, model, stamp)
    eng.warmup()
    eng.warmup_pipeline(8)
    print(json.dumps({"restored": n, "setup_s": time.perf_counter() - t0}),
          flush=True)
    from gubernator_tpu.utils.platform import CompileWatch

    watch = CompileWatch()  # everything below runs on warmed shapes
    rng = np.random.default_rng(SEED)
    for k in (2, 3, 4):
        pulls = [make_pull(model, rng, k) for _ in range(PULLS)]
        case(eng, "lockstep", serve_lockstep, pulls, k)
        case(eng, "group", serve_group, pulls, k)
        if k > 2:
            two_workers(eng, "lockstep", serve_lockstep, pulls, k)
            two_workers(eng, "group", serve_group, pulls, k)
    print(json.dumps({"compiles_after_warmup": watch.facts()}), flush=True)


if __name__ == "__main__":
    main()
