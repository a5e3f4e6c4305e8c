#!/usr/bin/env python
"""Start the shipped daemon on the attached TPU and hold its answers to the
plain oracle: the quickest proof that the system still serves from the chip.

    python chip_smoke.py              one chip, 10,000,000-slot table
    python chip_smoke.py --chips 4    four chips, 40,000,000 slots sharded
    python chip_smoke.py --rehearse   tiny table on the CPU (never "ok")

This process never initialises a JAX backend. It builds the native
libraries, starts `python -m gubernator_tpu.cmd.daemon` as the one process
that owns the chip(s), speaks HTTP and gRPC to it through the repo's own
clients, and computes every expected answer with the pure-Python oracle
(gubernator_tpu/ops/oracle.py). The daemon reads the wall clock, so each
batch is bracketed by the parent's clock before the send and after the
receive (BracketOracle): fields the clock does not reach are matched
exactly, fields it does reach must lie between the two instants' answers.

Every line of standard output is one JSON object; the last one is the
contract's `{"ok": true, "device": {...}}` with the device as the serving
process reports it. Any step that fails ends the run at once, non-zero,
with the step's name on standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
SLOTS_PER_CHIP = 10_000_000  # README: resident keys of one chip
BATCH = 1000  # the reference's per-request batch cap
HOUR_MS = 3_600_000


class StepFailed(Exception):
    def __init__(self, step: str, why: str):
        super().__init__(f"chip_smoke: step '{step}' failed: {why}")


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def now_ms() -> int:
    return int(time.time() * 1000)


def free_port(offset: int = 0) -> int:
    """A free port p; with `offset`, p+offset is free too (the native
    front listens on the gRPC port, the peerlink on gRPC port +
    GUBER_PEER_LINK_OFFSET)."""
    for _ in range(200):
        with socket.socket() as a:
            a.bind(("127.0.0.1", 0))
            p = a.getsockname()[1]
            if not offset:
                return p
            if p + offset > 65535:
                continue
            with socket.socket() as b:
                try:
                    b.bind(("127.0.0.1", p + offset))
                except OSError:
                    continue
                return p
    raise StepFailed("start", "no free port pair")


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


def rss_mb(pid) -> dict:
    """Resident set now and at its peak (VmHWM), MB, of one process."""
    out = {}
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith(("VmRSS", "VmHWM")):
                out[line[:5]] = int(line.split()[1]) // 1024
    return {"rss_mb": out.get("VmRSS"), "peak_rss_mb": out.get("VmHWM")}


def host_memory_mb() -> int:
    """What this machine lets us use: physical memory, or the cgroup's
    limit where that is lower."""
    with open("/proc/meminfo") as f:
        total = int(f.readline().split()[1]) // 1024
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            total = min(total, int(f.read()) >> 20)
    except (OSError, ValueError):
        pass
    return total


def child_env(chips: int, rehearse: bool) -> dict:
    """Environment of the one child that may touch the device: whatever
    platform pin or virtual-device flag this process inherited is dropped.
    On the chip JAX_PLATFORMS=tpu, so JAX itself refuses to come up
    without it — no CPU by accident."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    if rehearse:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ------------------------------------------------------------------ daemon


class Daemon:
    """The one process that touches the device."""

    def __init__(self, chips: int, rehearse: bool, min_width: int,
                 ready_timeout: float):
        self.chips, self.rehearse = chips, rehearse
        self.grpc_port = free_port(1000)
        self.http_port = free_port()
        slots = (16384 if rehearse else SLOTS_PER_CHIP) * chips
        env = child_env(chips, rehearse)
        env.update(
            GUBER_GRPC_ADDRESS=f"127.0.0.1:{self.grpc_port}",
            GUBER_HTTP_ADDRESS=f"127.0.0.1:{self.http_port}",
            GUBER_CACHE_SIZE=str(slots),
            GUBER_MIN_BATCH_WIDTH=str(min_width),
            GUBER_MAX_BATCH_WIDTH="8192",
        )
        if chips == 1:
            # a four-chip host must not turn the one-chip phase into the mesh
            env["GUBER_BACKEND"] = "engine"
        self.slots = slots
        self.cache_dir = env.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(REPO, ".jax_cache")
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        self.log_path = os.path.join(
            REPO, "chiprun_out", f"chip_smoke_daemon_{chips}_{int(time.time())}.log")
        entries_before = cache_entries(self.cache_dir)
        t0 = time.time()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "gubernator_tpu.cmd.daemon"],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=open(self.log_path, "w"), text=True)
        ready = threading.Event()

        def watch():
            for line in self.proc.stdout:
                if line.strip() == "Ready":
                    ready.set()

        threading.Thread(target=watch, daemon=True).start()
        deadline = t0 + ready_timeout
        while not ready.is_set():
            if self.proc.poll() is not None:
                raise StepFailed(
                    "start", f"daemon exited {self.proc.returncode} before "
                    f"Ready (JAX_PLATFORMS={env['JAX_PLATFORMS']}):\n"
                    + self.log_tail())
            self.check_memory("start")
            if time.time() > deadline:
                self.kill()
                raise StepFailed(
                    "start", f"no Ready within {ready_timeout:.0f} s:\n"
                    + self.log_tail())
            ready.wait(0.2)
        device_line = [ln for ln in open(self.log_path)
                       if " device: {" in ln]
        if not device_line:
            raise StepFailed("start", "daemon logged no device line")
        emit(step="start", seconds_to_ready=round(time.time() - t0, 1),
             daemon_device_line=device_line[-1].strip(),
             table_slots=slots, min_batch_width=min_width,
             max_batch_width=8192, compile_cache_dir=self.cache_dir,
             cache_entries_before=entries_before,
             cache_entries_after=cache_entries(self.cache_dir),
             daemon_memory=rss_mb(self.proc.pid),
             host_memory_mb=host_memory_mb())

    def check_memory(self, step: str) -> dict:
        """The daemon's resident set; fails the step while there is still
        room to say so (a host out of memory kills the run with no word)."""
        mem, host = rss_mb(self.proc.pid), host_memory_mb()
        if mem["rss_mb"] > 0.8 * host:
            raise StepFailed(step, f"daemon resident set {mem} MB is over "
                             f"80% of the host's {host} MB")
        return mem

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path) as f:
            return "".join(f.readlines()[-n:])

    def get(self, path: str) -> bytes:
        return urllib.request.urlopen(
            f"http://127.0.0.1:{self.http_port}{path}", timeout=60).read()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def stop(self) -> None:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.kill()
            raise StepFailed("stop", "daemon ignored SIGTERM for 120 s")
        if rc != 0:
            raise StepFailed("stop", f"daemon exited {rc} on SIGTERM:\n"
                             + self.log_tail())
        emit(step="stop", exit_code=rc)


# ---------------------------------------------------------------- requests


class Asker:
    """Sends batches over both public transports and holds every response
    to the oracle."""

    def __init__(self, daemon: Daemon):
        from gubernator_tpu.client import HttpClient, V1Client
        from gubernator_tpu.ops.oracle import BracketOracle

        self.clients = {
            "http": HttpClient(f"127.0.0.1:{daemon.http_port}"),
            "grpc": V1Client(f"127.0.0.1:{daemon.grpc_port}"),
        }
        self.oracle = BracketOracle()
        self.compared = 0
        self._lock = threading.Lock()

    def ask(self, step: str, via: str, reqs, oracle=None):
        """One batch; `oracle` defaults to the run's long-lived tables (a
        load batch of never-revisited keys brings a throwaway one)."""
        t0 = now_ms()
        resps = self.clients[via].get_rate_limits(reqs, timeout=120)
        t1 = now_ms()
        with self._lock:
            bad = (oracle or self.oracle).check(reqs, resps, t0, t1)
            self.compared += len(resps)
        if bad:
            raise StepFailed(step, f"{len(bad)} answers over {via} differ "
                             "from the oracle:\n" + "\n".join(bad[:10]))
        return resps


def load(asker: Asker, daemon: Daemon, rng: random.Random, n_keys: int,
         kept):
    """Fill the table: n_keys distinct keys in batches of BATCH, half over
    HTTP and half over gRPC, TOKEN_BUCKET and LEAKY_BUCKET alternating.
    The `kept` requests go in last, through the long-lived oracle tables,
    so later steps can come back to them."""
    from gubernator_tpu.ops.oracle import BracketOracle
    from gubernator_tpu.types import RateLimitReq

    batches = []
    for b in range(n_keys // BATCH - 1):
        limit = rng.choice((10, 100, 1000, 100_000))
        batches.append([
            RateLimitReq(name=f"load{b % 7}", unique_key=f"acct:{b}:{i}",
                         hits=1 + (i % 3), limit=limit, duration=HOUR_MS,
                         algorithm=(b + i) % 2)
            for i in range(BATCH)])
    failed = []
    t0 = time.time()

    def worker(via, mine):
        try:
            for reqs in mine:
                if failed:
                    return
                asker.ask("load", via, reqs, oracle=BracketOracle())
                daemon.check_memory("load")
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread
            failed.append(e)

    threads = [threading.Thread(
        target=worker, args=(("http", "grpc")[t % 2], batches[t::4]))
        for t in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    if failed:
        raise failed[0]
    asker.ask("load", "grpc", kept)
    emit(step="load", distinct_keys=len(batches) * BATCH + len(kept),
         batches=len(batches) + 1, batch_size=BATCH,
         transports=["http", "grpc"], algorithms=["TOKEN_BUCKET",
                                                  "LEAKY_BUCKET"],
         seconds=round(time.time() - t0, 1), answers_compared=asker.compared,
         daemon_memory=daemon.check_memory("load"),
         parent_memory=rss_mb(os.getpid()))


def answer(asker: Asker, rng: random.Random, kept, global_keys: bool):
    """The flows of the verify recipe, each held to the oracle."""
    from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq, Status

    def R(key, hits, limit, duration=HOUR_MS, algorithm=0, behavior=0,
          name="smoke"):
        return RateLimitReq(name=name, unique_key=key, hits=hits,
                            limit=limit, duration=duration,
                            algorithm=algorithm, behavior=behavior)

    tag = f"{rng.getrandbits(32):08x}"
    flows = []

    # drain one key to OVER_LIMIT, then peek
    via = "http"
    last = None
    for hits in (1, 1, 1, 1, 1, 1, 0):
        last = asker.ask("answer.drain", via, [R(f"drain:{tag}", hits, 5)])[0]
        via = "grpc" if via == "http" else "http"
    if last.status != Status.OVER_LIMIT or last.remaining != 0:
        raise StepFailed("answer.drain", f"not drained: {last}")
    flows.append("drain_to_over_limit")

    # leaky: burst to empty, rejected, one token leaks back after
    # duration/limit = 2000 ms
    lk = dict(limit=10, duration=20_000, algorithm=int(Algorithm.LEAKY_BUCKET))
    asker.ask("answer.leaky", "grpc", [R(f"leaky:{tag}", 10, **lk)])
    r = asker.ask("answer.leaky", "http", [R(f"leaky:{tag}", 1, **lk)])[0]
    if r.status != Status.OVER_LIMIT:
        raise StepFailed("answer.leaky", f"empty bucket admitted: {r}")
    time.sleep(3.0)
    r = asker.ask("answer.leaky", "grpc", [R(f"leaky:{tag}", 1, **lk)])[0]
    if r.status != Status.UNDER_LIMIT or r.remaining != 0:
        raise StepFailed("answer.leaky", f"no leak-back after 3 s: {r}")
    flows.append("leaky_leak_back")

    # duplicate keys inside one batch: decided in request order
    for via in ("http", "grpc"):
        asker.ask("answer.duplicates", via,
                  [R(f"dup:{tag}:{via}", 1, 4) for _ in range(6)])
    flows.append("duplicates_in_one_batch")

    # RESET_REMAINING on a drained token bucket and on a leaky bucket
    for algo in (0, 1):
        key = f"reset:{tag}:{algo}"
        asker.ask("answer.reset", "http", [R(key, 3, 3, algorithm=algo)])
        asker.ask("answer.reset", "grpc", [R(key, 1, 3, algorithm=algo)])
        asker.ask("answer.reset", "http", [R(
            key, 0, 3, algorithm=algo,
            behavior=int(Behavior.RESET_REMAINING))])
        asker.ask("answer.reset", "grpc", [R(key, 1, 3, algorithm=algo)])
    flows.append("reset_remaining")

    # DURATION_IS_GREGORIAN: duration is an interval code (1 = hours)
    for via in ("http", "grpc"):
        for algo in (0, 1):
            asker.ask("answer.gregorian", via, [R(
                f"greg:{tag}:{algo}", 2, 100, duration=1, algorithm=algo,
                behavior=int(Behavior.DURATION_IS_GREGORIAN))])
    flows.append("duration_is_gregorian")

    # validation errors ride beside valid requests
    for via in ("http", "grpc"):
        resps = asker.ask("answer.errors", via, [
            R(f"ok:{tag}", 1, 5), R("", 1, 5), R(f"x:{tag}", 1, 5, name="")])
        if not (resps[1].error and resps[2].error and not resps[0].error):
            raise StepFailed("answer.errors", f"{resps}")
    flows.append("empty_name_and_unique_key_errors")

    if global_keys:
        # Behavior=GLOBAL on a lone daemon: this node owns every key and
        # applies the request as a plain one (the broadcast has no peers)
        for via in ("http", "grpc"):
            for hits in (1, 1, 2, 1):
                asker.ask("answer.global", via, [R(
                    f"global:{tag}:{i}", hits, 4, algorithm=i % 2,
                    behavior=int(Behavior.GLOBAL)) for i in range(8)])
        flows.append("behavior_global_owner")

    # one 1000-key batch over the loaded keys: same configuration, hits
    # drawn from the seed, a tenth of the keys twice
    for via in ("http", "grpc"):
        picks = [rng.choice(kept) for _ in range(BATCH)]
        asker.ask("answer.batch", via, [
            RateLimitReq(name=k.name, unique_key=k.unique_key,
                         hits=rng.randrange(0, 5), limit=k.limit,
                         duration=k.duration, algorithm=k.algorithm)
            for k in picks])
    flows.append("1000_key_batch")
    emit(step="answer", flows=flows, answers_compared=asker.compared,
         mismatches=0)


def inspect(daemon: Daemon, chips: int, rehearse: bool) -> dict:
    """/metrics and /v1/debug/vars of the serving process."""
    # jitted decide launches by kernel and width (the single-table engine
    # counts them; the mesh engine reports its device rounds in its stats)
    launches = 0.0
    for line in daemon.get("/metrics").decode().splitlines():
        if line.startswith("engine_kernel_dispatch_total"):
            launches += float(line.rsplit(" ", 1)[1])
    dv = json.loads(daemon.get("/v1/debug/vars"))
    eng = dv["engine"]
    dispatches = int(eng["stats"]["rounds"])
    dev = eng["device"]
    table_bytes = daemon.slots // chips * 64
    checks = {
        "device dispatches > 0": dispatches > 0
        and (launches > 0 or chips > 1),
        "platform": dev["platform"] == ("cpu" if rehearse else "tpu"),
        "table on every chip": dev["device_count"] == chips
        and len(set(dev["devices"])) == chips,
        "a 1/chips share of the table on each": dev[
            "table_bytes_per_device"] == [table_bytes] * chips,
        "donation on": dev["donation"] is True,
        "native key directory": dev["key_directory"] == "native",
        "no circuit_open": "circuit_open" not in dv["anomaly"]["active"],
        "zero engine errors": int(eng["stats"].get("errors", 0)) == 0,
        "keys resident": int(eng.get("key_table_size", 0)) >= 1_000_000
        or rehearse,
    }
    emit(step="inspect", device_rounds=dispatches,
         daemon_memory=daemon.check_memory("inspect"),
         engine_kernel_dispatch_total=launches, device=dev,
         key_table_size=eng.get("key_table_size"),
         engine_type=eng["type"], engine_errors=eng["stats"].get("errors"),
         anomalies=dv["anomaly"]["active"], checks=checks)
    wrong = [k for k, ok in checks.items() if not ok]
    if wrong:
        raise StepFailed("inspect", f"{wrong}")
    return dev


def mesh_global_sync(chips: int, rehearse: bool) -> None:
    """The GLOBAL psum tier (ShardedEngine.global_sync), which the lone
    daemon never reaches: run the library's multi-chip dry run, which holds
    every answer before and after the sync to the oracle. Started only
    after the daemon has exited — one process at a time owns the chips."""
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, __graft_entry__ as g; "
         f"print(json.dumps(g.dryrun_multichip({chips})))"],
        env=child_env(chips, rehearse), cwd=REPO, capture_output=True,
        text=True, timeout=900)
    if r.returncode != 0:
        raise StepFailed("mesh_global_sync", r.stderr[-3000:])
    emit(step="mesh_global_sync", seconds=round(time.time() - t0, 1),
         **json.loads(r.stdout.strip().splitlines()[-1]))


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--min-width", type=int, default=None,
                    help="GUBER_MIN_BATCH_WIDTH for the daemon (the bottom "
                         "of the width ladder it compiles at boot)")
    ap.add_argument("--ready-timeout", type=float, default=1000.0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny table on virtual CPU devices; never ends in "
                         "the contract's ok line")
    args = ap.parse_args()
    rng = random.Random(args.seed)
    min_width = args.min_width or (32 if args.rehearse else MIN_WIDTH[args.chips])
    n_keys = 8_000 if args.rehearse else 1_000_000

    from gubernator_tpu import native
    from gubernator_tpu.types import RateLimitReq

    for component in native.COMPONENTS:
        emit(step="build_native", component=component,
             source_hash=native.source_key(component),
             library=os.path.basename(native.build_component(component)))

    # the keys later steps come back to: one batch, loaded last
    kept = [RateLimitReq(name="kept", unique_key=f"user:{rng.getrandbits(40):x}",
                         hits=1, limit=rng.choice((5, 20, 1000)),
                         duration=HOUR_MS, algorithm=i % 2)
            for i in range(BATCH)]

    daemon = Daemon(args.chips, args.rehearse, min_width, args.ready_timeout)
    try:
        asker = Asker(daemon)
        load(asker, daemon, rng, n_keys, kept)
        answer(asker, rng, kept, global_keys=args.chips > 1)
        dev = inspect(daemon, args.chips, args.rehearse)
        daemon.stop()
    finally:
        daemon.kill()
    if args.chips > 1:
        mesh_global_sync(args.chips, args.rehearse)

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise StepFailed("parent", "the parent initialised a JAX backend")
    verdict = {"ok": not args.rehearse,
               "device": {"platform": dev["platform"],
                          "kind": dev["device_kind"],
                          "count": dev["visible_device_count"]}}
    if args.rehearse:
        verdict["rehearsal"] = True
    print(json.dumps(verdict), flush=True)
    return 0


# GUBER_MIN_BATCH_WIDTH per --chips: the bottom of the width ladder the
# daemon compiles at boot. Measured on the v5e host, cold, 10M rows (PR 22,
# CHANGES.md): the shipped 64 takes 845 s to Ready (208 s of it the three
# 256-wide programs); 512 takes over 1000 s (every scan program is
# compiled at the bottom width, ~31 s each there against ~5 s at 64); a
# one-width ladder at 8192 takes 442 s. Only the last leaves room inside
# the 1200 s this script may take, so every window here is 8192 wide. The
# top width and the table are the shipped ones.
MIN_WIDTH = {1: 8192, 4: 8192}


if __name__ == "__main__":
    try:
        sys.exit(main())
    except StepFailed as e:
        print(e, file=sys.stderr, flush=True)
        sys.exit(1)
