"""Lifecycle of the system under test: the shipped daemon as the one process
that owns the chip, seen only through its standard output, its log and its
HTTP endpoints. (Lifecycle copied from chip_smoke.py's `Daemon`, proven on
the chip in PR 22.)
"""

from __future__ import annotations

import datetime
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.request


class RunFailed(Exception):
    """The run cannot produce a result line; the process exits non-zero."""


def free_port(offset: int = 0) -> int:
    """A free port p; with `offset`, p+offset is free too (the native front
    listens on the gRPC port, the peerlink on that port + 1000)."""
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
    raise RunFailed("no free port pair")


def host_memory_mb() -> int:
    with open("/proc/meminfo") as f:
        total = int(f.readline().split()[1]) // 1024
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            total = min(total, int(f.read()) >> 20)
    except (OSError, ValueError):
        pass
    return total


class Daemon:
    def __init__(self, repo: str, run_dir: str, settings: dict, chips: int,
                 rehearse: bool, snapshot_path: str):
        self.chips, self.rehearse = chips, rehearse
        self.grpc_port = free_port(1000)
        self.http_port = free_port()
        self.slots = int(settings["GUBER_CACHE_SIZE"])
        # whatever platform pin or virtual-device flag this process
        # inherited is dropped: on the chip JAX_PLATFORMS=tpu, so JAX
        # itself refuses to come up without one
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "XLA_FLAGS")}
        env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
        if rehearse:
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.update({k: str(v) for k, v in settings.items()})
        env.update(
            GUBER_GRPC_ADDRESS=f"127.0.0.1:{self.grpc_port}",
            GUBER_HTTP_ADDRESS=f"127.0.0.1:{self.http_port}",
            GUBER_SNAPSHOT_PATH=snapshot_path,
            # the profiler capture lands in the daemon's temp directory
            TMPDIR=os.path.join(run_dir, "tmp"),
        )
        # fixed path inside the checkout unless the environment names one
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(repo, ".jax_cache"))
        self.cache_dir = env["JAX_COMPILATION_CACHE_DIR"]
        os.makedirs(env["TMPDIR"], exist_ok=True)
        self.log_path = os.path.join(run_dir, "daemon.log")
        self.t0 = time.time()
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "gubernator_tpu.cmd.daemon"],
            env=env, cwd=repo, stdout=subprocess.PIPE, stderr=self._log,
            text=True)
        self._ready = threading.Event()
        self._peak_rss_mb = 0.0
        threading.Thread(target=self._watch, daemon=True).start()
        threading.Thread(target=self._sample_memory, daemon=True).start()

    def _watch(self):
        for line in self.proc.stdout:
            if line.strip() == "Ready":
                self._ready.set()

    def _sample_memory(self):
        """The resident set every half second: a sandboxed kernel may not
        keep VmHWM, so the peak is the largest reading seen."""
        while self.proc.poll() is None:
            try:
                self.rss_mb()
            except OSError:
                return
            time.sleep(0.5)

    def wait_ready(self, timeout_s: float) -> float:
        """Seconds from start to `Ready`."""
        deadline = self.t0 + timeout_s
        while not self._ready.is_set():
            if self.proc.poll() is not None:
                raise RunFailed(
                    f"daemon exited {self.proc.returncode} before Ready "
                    f"(JAX_PLATFORMS={'cpu' if self.rehearse else 'tpu'}):\n"
                    + self.log_tail())
            if self.rss_mb()["rss_mb"] > 0.8 * host_memory_mb():
                self.kill()
                raise RunFailed("daemon resident set over 80% of the host")
            if time.time() > deadline:
                self.kill()
                raise RunFailed(f"no Ready within {timeout_s:.0f} s:\n"
                                + self.log_tail())
            self._ready.wait(0.1)
        return time.time() - self.t0

    def restore_s(self):
        """Seconds the Engine's constructor took, which is the snapshot
        restore (and, on a cold cache, the inject program's compile): from
        the log's `compile cache:` line to its `backend:` line."""
        stamps = {}
        with open(self.log_path) as f:
            for line in f:
                m = re.match(r"(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3}) \S+ "
                             r"INFO (compile cache:|backend:)", line)
                if m:
                    stamps[m.group(3)] = datetime.datetime.strptime(
                        m.group(1), "%Y-%m-%d %H:%M:%S").timestamp() \
                        + int(m.group(2)) / 1000
        if len(stamps) < 2:
            return None
        return stamps["backend:"] - stamps["compile cache:"]

    def rss_mb(self) -> dict:
        out = {}
        with open(f"/proc/{self.proc.pid}/status") as f:
            for line in f:
                if line.startswith(("VmRSS", "VmHWM")):
                    out[line[:5]] = int(line.split()[1]) / 1024
        self._peak_rss_mb = max(self._peak_rss_mb, out.get("VmRSS", 0.0),
                                out.get("VmHWM", 0.0))
        return {"rss_mb": out.get("VmRSS", 0.0),
                "peak_rss_mb": self._peak_rss_mb}

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path) as f:
            return "".join(f.readlines()[-n:])

    def get(self, path: str, timeout: float = 60) -> bytes:
        return urllib.request.urlopen(
            f"http://127.0.0.1:{self.http_port}{path}", timeout=timeout).read()

    def scrape(self) -> dict:
        """The three endpoints the layer metrics read, at one instant."""
        return {"at": time.time(),
                "metrics": parse_metrics(self.get("/metrics").decode()),
                "vars": json.loads(self.get("/v1/debug/vars")),
                "profile": json.loads(self.get("/v1/debug/profile"))}

    def capture(self, seconds: float) -> dict:
        """One jax.profiler capture through the daemon's own endpoint;
        blocks for `seconds`."""
        body = json.loads(self.get(
            f"/v1/debug/profile?capture=1&seconds={seconds}",
            timeout=seconds + 120))
        return body["capture"].get("triggered") or {}

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._log.close()


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {family: sum over its label sets}; histogram
    buckets are left out."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#" or "_bucket{" in line:
            continue
        name, _, value = line.rpartition(" ")
        family = name.split("{", 1)[0]
        try:
            out[family] = out.get(family, 0.0) + float(value)
        except ValueError:
            continue
    return out


def inspect(daemon: Daemon, scrape: dict, residents: int) -> dict:
    """chip_smoke.py's inspect step: the device the daemon serves from and
    the faults it counts. Returns {check: passed}."""
    dv = scrape["vars"]
    eng = dv["engine"]
    dev = eng["device"]
    chips = daemon.chips
    return {
        "platform": dev["platform"] == ("cpu" if daemon.rehearse else "tpu"),
        "table on every chip": dev["device_count"] == chips
        and len(set(dev["devices"])) == chips,
        "a 1/chips share of the table on each": dev[
            "table_bytes_per_device"] == [daemon.slots // chips * 64] * chips,
        "donation on": dev["donation"] is True,
        "native key directory": dev["key_directory"] == "native",
        "no circuit_open": "circuit_open" not in dv["anomaly"]["active"],
        "zero engine errors": int(eng["stats"].get("errors", 0)) == 0,
        "keys resident": int(eng.get("key_table_size", 0)) >= residents,
        "device dispatches > 0": int(eng["stats"]["rounds"]) > 0,
    }
