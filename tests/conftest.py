"""Test env: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's test strategy of N real in-process servers on
loopback (reference: cluster/cluster.go, functional_test.go:35-49) — here the
"cluster" is 8 virtual XLA CPU devices, so mesh sharding and collectives run
for real without TPU hardware.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

if not os.environ.get("GUBER_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Plugins (jaxtyping) may import jax before this conftest runs, freezing
    # the env-derived default; override the live config too.
    import jax

    jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: without it the first suite run pays every
# XLA compile cold, which can push loopback RPCs past their deadlines and
# poison HealthCheck via the 5-minute peer-error TTL.
import jax as _jax  # noqa: E402

# Same rule as the daemon's utils/platform.compile_cache_dir (not imported:
# nothing of the package may load before the witness env below is set):
# JAX_COMPILATION_CACHE_DIR wins, otherwise a fixed directory.
_cache_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
_cache_dir = _cache_env or os.path.join(os.path.dirname(__file__),
                                        ".jax_cache")

# Self-healing for a poisoned cache: a run killed mid-write (OOM kill,
# watchdog SIGKILL, ctrl-C at the wrong instant) can leave a truncated
# entry that segfaults the NEXT run at deserialization time. Each session
# drops a pid-stamped sentinel in the cache dir and removes it on clean
# finish (pytest_sessionfinish below); finding a sentinel whose pid is no
# longer alive means the previous run died uncleanly with the cache dir
# open for writing — wipe it and recompile warm entries rather than risk
# the segfault. A sentinel whose pid IS alive is a concurrent run sharing
# the cache; leave it alone.
_sentinel = os.path.join(_cache_dir, ".session.pid")


def _stale_sentinel() -> bool:
    try:
        with open(_sentinel) as f:
            pid = int(f.read().strip() or 0)
    except (OSError, ValueError):
        return False
    if pid <= 0 or pid == os.getpid():
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True  # recorded owner is dead: unclean shutdown
    except PermissionError:
        pass  # alive but not ours
    return False


if _stale_sentinel():
    import shutil

    shutil.rmtree(_cache_dir, ignore_errors=True)

os.makedirs(_cache_dir, exist_ok=True)
with open(_sentinel, "w") as _f:
    _f.write(str(os.getpid()))

if not _cache_env:
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

# ---------------------------------------------------------------------------
# Runtime lock-order witness (obs/witness.py): tier-1 runs the WHOLE suite
# with every canonical lock order-checked against the committed
# lockmap.json graph, so an acquisition inverting the committed order
# fails the offending test with both stacks instead of deadlocking a CI
# run. Must be set before any gubernator_tpu module constructs a lock —
# i.e. here, before collection imports anything. setdefault so
# GUBER_LOCK_WITNESS=0 still lets a developer bisect with the witness
# out of the picture. The dump dir makes subprocess daemons (which
# inherit this env) write their observations at exit, feeding the same
# session-end gate as the in-process witness (pytest_sessionfinish).

os.environ.setdefault("GUBER_LOCK_WITNESS", "1")

_witness_dump = os.path.join(os.path.dirname(__file__), ".witness")
if not os.environ.get("GUBER_LOCK_WITNESS_DUMP"):
    import shutil as _shutil

    _shutil.rmtree(_witness_dump, ignore_errors=True)
    os.environ["GUBER_LOCK_WITNESS_DUMP"] = _witness_dump

# tests/lint_corpus/ holds miniature FAKE repos for the guberlint golden
# tests (test_lint_corpus.py) — some deliberately mirror real test-file
# names (test_debug_schema.py), so pytest must never collect in there
collect_ignore = ["lint_corpus"]


# ---------------------------------------------------------------------------
# Exit watchdog: the suite's RESULT is what matters; interpreter teardown is
# not under test. Observed (rarely) on this rig: after the summary line is
# printed, interpreter exit wedges indefinitely in native-thread teardown
# (grpc/XLA atexit), turning a fully green run into an apparent timeout. The
# watchdog arms only after the session result exists, gives natural exit a
# 60 s grace, then forces the already-decided exit code out.

_session_exit = {}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow') — heavyweight "
        "allocations or long soaks")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection / peer-failure drills "
        "(service/faults.py). Fast and pinned-seed by default, so they run "
        "in tier-1; `make chaos` re-runs them with a randomized "
        "GUBER_CHAOS_SEED (printed for reproduction)")


def _witness_violations():
    """Session-end lock-witness gate: collect inversions and uncommitted
    edges from the in-process witness AND every subprocess daemon's exit
    dump. This is the runtime half of the lockmap two-direction pin: an
    ordering the committed graph doesn't carry must be reviewed and
    added to lockmap.json runtime_edges (with a `why`), not silently
    blessed."""
    from gubernator_tpu.obs import witness as _w

    if not _w.witness_enabled():
        return []
    snaps = []
    if _w._WITNESS is not None:  # don't instantiate just to read nothing
        snaps.append(("pytest", _w._WITNESS.snapshot()))
    dump_dir = os.environ.get("GUBER_LOCK_WITNESS_DUMP", "")
    if dump_dir and os.path.isdir(dump_dir):
        import glob
        import json

        for path in sorted(glob.glob(
                os.path.join(dump_dir, "witness-*.json"))):
            if path.endswith(f"witness-{os.getpid()}.json"):
                continue  # own atexit dump (not written yet anyway)
            try:
                with open(path, encoding="utf-8") as f:
                    snaps.append((os.path.basename(path), json.load(f)))
            except (OSError, ValueError):
                pass  # a torn dump from a killed daemon is not a verdict
    problems = []
    for origin, snap in snaps:
        for inv in snap.get("inversions", []):
            problems.append(
                f"[{origin}] lock-order INVERSION {inv['src']} -> "
                f"{inv['dst']} (the committed lockmap orders the "
                "reverse)\n"
                f"  stack holding `{inv['src']}`:\n{inv['held_stack']}"
                f"  stack acquiring `{inv['dst']}`:\n"
                f"{inv['acquire_stack']}")
        for unk in snap.get("unknown", []):
            problems.append(
                f"[{origin}] uncommitted acquisition edge {unk['src']} "
                f"-> {unk['dst']} — review the ordering, then add it to "
                "lockmap.json runtime_edges with a `why` (docs/"
                "static-analysis.md 'Reading a lockmap')\n"
                f"  stack holding `{unk['src']}`:\n{unk['held_stack']}"
                f"  stack acquiring `{unk['dst']}`:\n"
                f"{unk['acquire_stack']}")
    return problems


def pytest_sessionfinish(session, exitstatus):
    problems = _witness_violations()
    if problems:
        print("\n" + "=" * 70)
        print("lock-witness session gate: ORDER VIOLATIONS "
              f"({len(problems)})")
        print("=" * 70)
        for p in problems:
            print(p)
        if int(exitstatus) == 0:
            # green tests + a dirty witness is still a failed session
            # (wrap_session reads session.exitstatus after this hook)
            session.exitstatus = exitstatus = 1
    _session_exit["code"] = int(exitstatus)
    # clean finish: retire the cache sentinel ONLY if this session still
    # owns it (a concurrent run may have replaced it after wiping)
    try:
        with open(_sentinel) as f:
            if int(f.read().strip() or 0) == os.getpid():
                os.unlink(_sentinel)
    except (OSError, ValueError):
        pass


def pytest_unconfigure(config):
    import signal
    import sys
    import threading
    import time

    code = _session_exit.get("code")
    if code is None:
        return

    # Tier 1: a daemon thread that preserves the real exit code. Fires
    # for pre-finalization wedges (e.g. threading._shutdown joining a
    # stuck non-daemon thread — the observed case), where the GIL still
    # schedules normally.
    def _watchdog():
        time.sleep(60.0)
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)  # teardown wedged; the verdict above is final

    threading.Thread(target=_watchdog, daemon=True,
                     name="exit-watchdog").start()

    # Tier 2: a forked killer for wedges INSIDE interpreter finalization,
    # where a Python thread can never run again (it would die trying to
    # reacquire the GIL). The child is GIL-free: if the parent is still
    # alive after 150 s, SIGKILL it — a killed-by-9 after the printed
    # summary beats an infinite hang. The child reparents to init and
    # exits on its own either way.
    parent = os.getpid()
    try:
        import warnings

        with warnings.catch_warnings():
            # CPython's DeprecationWarning and JAX's at-fork
            # RuntimeWarning would print AFTER the suite summary and
            # become the run's last line; the child only sleeps and
            # kills, which fork-safety allows
            warnings.simplefilter("ignore")
            pid = os.fork()
    except OSError:
        return
    if pid == 0:
        try:
            # release every inherited fd NOW — holding the stdout pipe
            # open would delay EOF (and any wrapping pipeline) by the
            # whole grace period on perfectly healthy runs
            devnull = os.open(os.devnull, os.O_RDWR)
            for fd in (0, 1, 2):
                os.dup2(devnull, fd)
            os.closerange(3, 4096)
            time.sleep(150.0)
            os.kill(parent, signal.SIGKILL)
        except OSError:
            pass
        finally:
            os._exit(0)


def free_port() -> int:
    """Reserve an ephemeral TCP port (shared test helper)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Failure bundles: when a test backed by real daemon subprocesses fails,
# the daemons' in-memory state (flight recorder, anomaly sweep, circuit
# view, traces) is the post-mortem — and it dies with the fixture teardown
# an instant later. spawn_daemon registers each daemon's HTTP port; on a
# failed test the makereport hook snapshots /v1/debug/bundle from every
# live registered daemon into GUBER_TEST_ARTIFACTS (default
# tests/artifacts/) before teardown runs. Best-effort by design: a daemon
# too sick to serve its bundle must not turn one failure into two.

_debug_daemon_ports = set()


def _collect_failure_bundles(test_name):
    import json
    import re
    import urllib.request

    if not _debug_daemon_ports:
        return
    art_dir = os.environ.get(
        "GUBER_TEST_ARTIFACTS",
        os.path.join(os.path.dirname(__file__), "artifacts"))
    slug = re.sub(r"[^A-Za-z0-9_.-]+", "-", test_name)[:120]
    for port in sorted(_debug_daemon_ports):
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/debug/bundle", timeout=5).read()
            json.loads(body)  # only keep well-formed bundles
            os.makedirs(art_dir, exist_ok=True)
            path = os.path.join(art_dir, f"{slug}-{port}.json")
            with open(path, "wb") as f:
                f.write(body)
            print(f"\n[failure-bundle] {path}")
        except Exception:  # noqa: BLE001 — diagnostics never add failures
            pass


def pytest_runtest_makereport(item, call):
    if call.when == "call" and call.excinfo is not None:
        _collect_failure_bundles(item.nodeid.split("::", 1)[-1])


def spawn_daemon(env_overrides, ready_timeout=240.0, stderr_path=None):
    """Spawn the real daemon subprocess and wait for its Ready sentinel.

    The sentinel is read on a side thread so a silently wedged daemon
    (alive, printing nothing) fails at the deadline instead of hanging the
    suite on a blocking readline. Returns the Popen; callers own teardown
    (terminate + wait, kill on TimeoutExpired). `stderr_path` tees the
    daemon's log stream to a file for post-mortem assertions.
    """
    import os
    import subprocess
    import sys
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(repo, "tests", ".jax_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    env.update(env_overrides)
    stderr = (open(stderr_path, "w") if stderr_path
              else subprocess.DEVNULL)
    proc = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cmd.daemon"],
        env=env, cwd=repo, stdout=subprocess.PIPE, stderr=stderr,
        text=True,
    )
    if stderr_path:
        stderr.close()  # the child holds its own descriptor
    ready = threading.Event()

    def wait_ready():
        while True:
            line = proc.stdout.readline()
            if not line:
                return
            if "Ready" in line:
                ready.set()
                return

    # failure-bundle registration: remember where this daemon's debug
    # plane lives so a failing test can snapshot it (hook above)
    http_addr = env_overrides.get("GUBER_HTTP_ADDRESS", "")
    port = http_addr.rpartition(":")[2]
    if port.isdigit():
        proc._guber_http_port = int(port)
        _debug_daemon_ports.add(proc._guber_http_port)

    t = threading.Thread(target=wait_ready, daemon=True)
    t.start()
    deadline = time.time() + ready_timeout
    while time.time() < deadline:
        if ready.is_set():
            return proc
        if proc.poll() is not None:
            raise RuntimeError(
                f"daemon died at startup (rc={proc.returncode})")
        time.sleep(0.1)
    proc.kill()
    proc.wait()
    raise RuntimeError(f"daemon never printed Ready in {ready_timeout:.0f}s")


def stop_daemon(proc):
    import subprocess

    _debug_daemon_ports.discard(getattr(proc, "_guber_http_port", None))
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def http_metric(http_port, name):
    """One Prometheus sample from a daemon's /metrics (shared by the
    multi-daemon e2e suites)."""
    import urllib.request

    text = urllib.request.urlopen(
        f"http://127.0.0.1:{http_port}/metrics", timeout=10).read().decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return 0.0


def await_cond(cond, timeout, every=0.5):
    """Poll `cond()` until truthy or `timeout` seconds elapse (shared by
    the multi-node e2e suites)."""
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(every)
    return False


def wait_http_metric(http_port, name, want, deadline_s,
                     cmp=lambda v, w: v >= w):
    import time

    end = time.time() + deadline_s
    v = http_metric(http_port, name)
    while time.time() < end:
        if cmp(v, want):
            return v
        time.sleep(0.2)
        v = http_metric(http_port, name)
    return v
