#!/usr/bin/env python3
"""Measure the shipped daemon from the client's side, on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run: write the cell's resident keys as a snapshot from the seed, start
`python -m gubernator_tpu.cmd.daemon` on the TPU, drive it from
load-generator processes over gRPC for `--seconds`, hold the answers for
the audited keys to the plain oracle, kill the daemon, print one JSON
object as the last line. See benchmarks/README.md.

This process never initialises a JAX backend: the daemon owns the chip.
Cells, configurations and per-layer metrics are files found by the names in
BENCHMARK.json; this program knows none of them.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import multiprocessing
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import numpy as np  # noqa: E402

import host_pressure  # noqa: E402
import open_math  # noqa: E402
import scrape_math  # noqa: E402
from daemon import Daemon, RunFailed, inspect  # noqa: E402
from open_math import percentile  # noqa: E402

READY_TIMEOUT_S = 1000.0  # a cold compile cache: ~450 s (PERF.md)


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_cell(name: str, repo: str = REPO):
    """(cell entry, configuration file, mix file, manifest) by the names
    in `repo`'s BENCHMARK.json."""
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise RunFailed(f"no workload {name!r} in BENCHMARK.json "
                        f"(has: {sorted(cells)})")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(repo, conf_entry["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(repo, manifest["paths"][0], "workloads",
                           name + ".json")) as f:
        mix = json.load(f)
    if mix["config"] != cell["config"] or mix["traffic"] != cell["traffic"]:
        raise RunFailed(f"workloads/{name}.json disagrees with BENCHMARK.json")
    if mix["transport"] != "grpc" or mix["loop"] not in ("closed", "open"):
        raise RunFailed("only transport grpc and the loops 'closed' and "
                        "'open' are driven yet")
    rate = mix.get("rate_per_s")
    if mix["loop"] == "open" and not (
            isinstance(rate, (int, float)) and rate > 0):
        raise RunFailed(
            f"workloads/{name}.json: an open loop needs a positive rate_per_s "
            "(decisions a second, all clients together; Poisson arrivals)")
    return cell, conf, mix, manifest


def listed(manifest: dict, group: str, cell: str) -> list:
    """The `group` metrics (`end_to_end`, `per_layer`) that the manifest
    lists for `cell`: an entry without a `workloads` list covers every cell."""
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


def load_reader(name: str, here: str = HERE):
    path = os.path.join(here, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_native() -> None:
    from gubernator_tpu import native

    for component in native.COMPONENTS:
        native.build_component(component)


def main() -> int:
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny table on the CPU, for the sandbox; never "
                         "prints correct: true")
    ap.add_argument("--control", choices=("lost_hits",), default=None,
                    help="break the configuration's guarantee underneath "
                         "the run (the table keeps one token too many per "
                         "key); `correct` has to come out false")
    args = ap.parse_args()
    cell, conf, mix, manifest = load_cell(args.workload)
    chips = int(cell["chips"])
    # the daemon's settings: `daemon_env`, and the GUBER_* entries of every
    # group the configuration lists as changed from its source
    settings = dict(conf["daemon_env"])
    for key in conf["reduced"]:
        if isinstance(conf[key], dict):
            settings.update({k: v for k, v in conf[key].items()
                             if k.startswith("GUBER_")})
    residents = int(conf["resident_keys"])
    if args.rehearse:
        settings.update(conf["rehearse"]["daemon_env"])
        residents = int(conf["rehearse"]["resident_keys"])
        # what of the mix a tiny CPU daemon cannot take (an open loop's rate)
        mix = {**mix, **mix.get("rehearse", {})}

    build_native()
    run_dir = os.path.join(REPO, ".bench_run", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    from keymodel import KeyModel, write_snapshot
    from traffic import Traffic

    stamp_ms = int(time.time() * 1000)
    snapshot = os.path.join(run_dir, "snapshot.gtslab")
    t0 = time.time()
    size = write_snapshot(
        snapshot, KeyModel(conf["key_model"], args.seed), residents, stamp_ms,
        extra_tokens=1 if args.control == "lost_hits" else 0)
    say(step="snapshot", residents=residents, bytes=size,
        seconds=time.time() - t0)

    # load-generator processes build their pools while the daemon boots
    daemon = Daemon(REPO, run_dir, settings, chips, args.rehearse, snapshot)
    import loadgen

    ctx = multiprocessing.get_context("spawn")
    n_proc, n_clients = int(mix["processes"]), int(mix["clients"])
    workers = []
    try:
        for p in range(n_proc):
            parent_end, child_end = ctx.Pipe()
            spec = {"mix": mix, "key_params": conf["key_model"],
                    "residents": residents, "seed": args.seed,
                    "clients": list(range(p, n_clients, n_proc)),
                    "address": f"127.0.0.1:{daemon.grpc_port}"}
            proc = ctx.Process(target=loadgen.main, args=(child_end, spec),
                               daemon=True)
            proc.start()
            child_end.close()
            workers.append((proc, parent_end))
        ready_s = daemon.wait_ready(READY_TIMEOUT_S)
        pool_s = [_expect(conn, "ready", 600) for _, conn in workers]
        first = daemon.scrape()
        dev = first["vars"]["engine"]["device"]
        want = "cpu" if args.rehearse else "tpu"
        if dev["platform"] != want or dev["visible_device_count"] < chips:
            raise RunFailed(f"the daemon serves from {dev['platform']} x "
                            f"{dev['visible_device_count']}; the cell needs "
                            f"{want} x {chips}")
        restore_s = daemon.restore_s()
        say(step="ready", ready_s=ready_s, restore_s=restore_s,
            pool_build_s=max(pool_s), device=dev,
            pipeline_depth=first["vars"]["combiner"]["pipeline_depth"],
            compile_cache_dir=daemon.cache_dir, daemon_memory=daemon.rss_mb())

        warm_start = time.time() + 0.3
        win_start = warm_start + float(mix["warm_seconds"])
        win_end = win_start + args.seconds
        for _, conn in workers:
            conn.send((warm_start, win_start, win_end))
        watch = host_pressure.Watch(win_start)
        time.sleep(max(win_start - time.time(), 0))
        before = daemon.scrape()
        host0 = host_pressure.counters()
        capture = None
        if args.trace:
            cap_s = min(float(mix["trace_seconds"]), args.seconds * 0.6)
            time.sleep(max(win_start + (args.seconds - cap_s) / 2
                           - time.time(), 0))
            capture = daemon.capture(cap_s)
            capture["seconds"] = cap_s
        time.sleep(max(win_end - time.time(), 0))
        host = host_pressure.diff(host0, host_pressure.counters())
        host["parent"] = watch.stop(0.0, args.seconds)
        after = daemon.scrape()
        memory = daemon.rss_mb()
        results = [_expect(conn, "done", float(mix["call_timeout_s"]) + 300)
                   for _, conn in workers]
        last = daemon.scrape()
    finally:
        daemon.kill()
        for proc, _ in workers:
            proc.join(timeout=10)
            if proc.is_alive():
                proc.kill()
                proc.join()

    # ---- the window, from the client's side
    lat = np.sort(np.concatenate([r["lat_ns"] for r in results])) / 1e6
    calls = sum(r["calls"] for r in results)
    decisions = sum(r["decisions"] for r in results)
    attempted = sum(r["attempted"] for r in results)
    failed = sum(r["failed"] for r in results)
    if not calls:
        raise RunFailed("no call completed inside the window")
    latency_ms = {"mean": float(lat.mean()), "p50": percentile(lat, 0.5),
                  "p90": percentile(lat, 0.9), "p95": percentile(lat, 0.95),
                  "p99": percentile(lat, 0.99), "max": float(lat[-1])}
    end_to_end = {
        "decisions_per_s": (decisions / args.seconds, "decisions/s"),
        "call_p50_ms": (latency_ms["p50"], "ms"),
        "call_p99_ms": (latency_ms["p99"], "ms"),
        "daemon_rss_mb": (memory["peak_rss_mb"], "MB"),
        "setup_s": (win_start - t_start, "s"),
    }
    open_loop = {}
    if mix["loop"] == "open":
        # the window's calls are those due in it; latency counts from there
        open_loop = {
            "offered_decisions": attempted,
            "unanswered": sum(r["unanswered"] for r in results),
            **open_math.window(
                *(np.concatenate([r[k] for r in results])
                  for k in ("due_ns", "lat_ns", "lag_ns")),
                win_start, args.seconds)}
    say(step="window", seconds=args.seconds, calls=calls,
        latency_samples=len(lat), samples_beyond_p99=int(len(lat) * 0.01),
        latency_ms=latency_ms,
        decisions=decisions, attempted=attempted, failed=failed,
        all_calls=sum(r["all_calls"] for r in results), host=host,
        background=scrape_math.background_units(before, after),
        **open_loop)

    # ---- correct
    import check

    t0 = time.time()
    traffic = Traffic(mix, conf["key_model"], residents, args.seed)
    audits = np.concatenate([r["audits"] for r in results])
    audit = check.check_audits(audits, traffic, stamp_ms)
    if audit["mismatches"]:  # kept for whoever has to find out why
        np.save(os.path.join(run_dir, "audits.npy"), audits)
    checks = inspect(daemon, last, residents)
    compared = {  # each number compared, beside its limit
        "audit_mismatches": {"value": audit["mismatches"], "limit": 0},
        "shape_violations": {
            "value": sum(r["malformed_all"] for r in results), "limit": 0},
        "failed_decisions": {"value": failed, "limit": 0},
        "inspect_failures": {
            "value": sum(not ok for ok in checks.values()), "limit": 0},
        "audited_answers": {"value": audit["audited_answers"],
                            "at_least": int(mix["audit"]["min_answers"])},
    }
    sound = all(c["value"] <= c["limit"] if "limit" in c
                else c["value"] >= c["at_least"] for c in compared.values())
    say(step="check", seconds=time.time() - t0, sound=sound, compared=compared,
        audit={k: v for k, v in audit.items() if k != "examples"},
        examples=audit["examples"]
        + [n for r in results for n in r["notes"]][:5],
        inspect={k: v for k, v in checks.items() if not v})

    # ---- the result line
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["visible_device_count"],
              # the allocator's peak on the fullest chip after the window
              # (`hbm_peak_mb`'s reading); a CPU reports none: its table
              "memory_peak_bytes": int(
                  scrape_math.device_peak_bytes(last)
                  or max(dev["table_bytes_per_device"]))}
    line = {"correct": bool(sound) and not args.rehearse,
            "attempted": attempted, "failed": failed, "device": device}
    if args.rehearse:
        line["rehearsal"] = True
    names = {m["name"] for m in listed(manifest, "end_to_end", args.workload)}
    line["end_to_end" if args.trace else "metrics"] = {
        k: {"value": v, "unit": u} for k, (v, u) in end_to_end.items()
        if k in names}
    if args.trace:
        import trace_reduce

        trace = trace_reduce.reduce_capture(capture, args.rehearse)
        say(step="trace", capture=capture,
            **{k: v for k, v in trace.items() if k != "breakdown"})
        scrapes = {
            "before": before, "after": after, "window_s": args.seconds,
            "settings": settings, "device_kind": dev["device_kind"],
            "loadgen": {"cpu_s": [r["cpu_s"] for r in results],
                        "processes": n_proc,
                        "answered_decisions": decisions, "host": host,
                        **open_loop},
            "boot": {"ready_s": ready_s, "restore_s": restore_s},
            "latency_ms": latency_ms,
        }
        metrics = {}
        for m in listed(manifest, "per_layer", args.workload):
            try:
                value = load_reader(m["name"]).read(scrapes, trace)
            except KeyError as e:
                if not args.rehearse:  # a CPU has no entry in the peaks
                    raise
                say(step="reader_skipped", name=m["name"], why=str(e))
                continue
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    # each number compared beside its limit: last in the result's line, and
    # the last lines on standard error
    line["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def _expect(conn, kind: str, timeout_s: float):
    if not conn.poll(timeout_s):
        raise RunFailed(f"a load generator sent no '{kind}' in {timeout_s} s")
    try:
        got = conn.recv()
    except EOFError:
        raise RunFailed(f"a load generator died before '{kind}'") from None
    if got[0] != kind:
        raise RunFailed(f"load generator: {got[1]}")
    return got[1]


if __name__ == "__main__":
    try:
        sys.exit(main())
    except RunFailed as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
