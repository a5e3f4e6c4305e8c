"""ThreadSanitizer pass over the native tier (reference parity:
`go test ./... -race`, Makefile:7-8).

Both C++ components are rebuilt with -fsanitize=thread and hammered under
their REAL concurrency disciplines in a subprocess running with libtsan
preloaded:

- peerlink (native/peerlink.cpp) is genuinely multithreaded: one epoll IO
  thread, N puller threads blocking in pls_next_batch, responder threads
  writing directly to sockets, concurrent client connects/closes. The
  stress speaks raw frames over sockets so the subprocess needs no
  package imports (TSan's ~10x slowdown stays off the jax import path).
- keydir (native/keydir.cpp): batch callers (lookup/drop) keep the
  engine-lock discipline, while the r3 native lone-request path —
  decide_one / mirror_seed / mirror_flush — runs from separate threads
  WITHOUT that lock, exactly as the peerlink IO thread does in
  production; the internal KeyDir mutex is the only synchronization, and
  that race (mirror math vs batch lookups on the same keys) is the main
  thing this stress exists to check. Do NOT wrap native_decider in the
  Python lock: that would silently destroy the coverage. The tickers'
  reverse lookup (keys_for_slots) runs the same way, lock-free outside
  and chunk by chunk inside.

A data race makes TSan print "WARNING: ThreadSanitizer" and exit 66
(TSAN_OPTIONS exitcode); the test asserts a clean run.
"""

import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NATIVE = os.path.join(HERE, "..", "gubernator_tpu", "native")


def _tsan_lib(component: str) -> str:
    """Build the TSan variant of a native component (hash-keyed cache,
    the runtime's own builder)."""
    from gubernator_tpu import native

    return native.build_component(component, "tsan")


def _find_libtsan():
    for root in ("/usr/lib/gcc/x86_64-linux-gnu",):
        if os.path.isdir(root):
            for ver in sorted(os.listdir(root), reverse=True):
                p = os.path.join(root, ver, "libtsan.so")
                if os.path.exists(p):
                    return p
    return None


LIBTSAN = _find_libtsan()


def _libtsan_gcc_major() -> int:
    """gcc major version of the discovered libtsan (its parent directory
    on the /usr/lib/gcc/<triple>/<ver>/ layout), 0 when unknown."""
    if LIBTSAN is None:
        return 0
    try:
        return int(os.path.basename(os.path.dirname(LIBTSAN)).split(".")[0])
    except ValueError:
        return 0


# gcc-10's libtsan runtime misreports the peerlink stop path: its race
# report shows BOTH stacks (the pls_stop flag write and the CV-wait
# predicate read in pls_next_batch) already holding the same mutex M
# ("(mutexes: write M122)" on each side), plus a bogus "double lock of a
# mutex" on the same run — i.e. the runtime's lock tracking, not the
# code, is wrong. gcc-11+ libtsan analyzes the identical binary clean.
# Rather than skipping the whole peerlink stress on such rigs, the
# targeted suppressions in native/tsan.supp silence exactly the
# corrupted-ownership reports (one stack always inside a ctypes-called
# pls_* entry) and the test runs everywhere; modern runtimes get no
# suppressions at all.
TSAN_SUPP = os.path.abspath(os.path.join(NATIVE, "tsan.supp"))


def _tsan_options() -> str:
    opts = "exitcode=66 halt_on_error=0"
    if 0 < _libtsan_gcc_major() < 11:
        opts += f" suppressions={TSAN_SUPP}"
    return opts

_PEERLINK_STRESS = textwrap.dedent("""
    import ctypes, socket, struct, sys, threading, time
    lib = ctypes.CDLL(sys.argv[1])
    c = ctypes
    lib.pls_start.restype = c.c_void_p
    lib.pls_start.argtypes = [c.c_int, c.POINTER(c.c_int)]
    lib.pls_stop.argtypes = [c.c_void_p]
    lib.pls_free.argtypes = [c.c_void_p]
    lib.pls_next_batch.restype = c.c_int
    lib.pls_next_batch.argtypes = [c.c_void_p, c.c_longlong, c.c_char_p,
        c.c_int] + [c.c_void_p] * 11 + [c.c_int]
    lib.pls_send_responses.argtypes = [c.c_void_p, c.c_int] + \\
        [c.c_void_p] * 8 + [c.c_char_p]

    port = c.c_int(0)
    h = lib.pls_start(0, c.byref(port))
    assert h

    N = 256
    stop = False

    def puller():
        keys = c.create_string_buffer(1 << 20)
        arrs = [(c.c_int32 * (N + 1))(), (c.c_int32 * N)(),
                (c.c_int64 * N)(), (c.c_int64 * N)(), (c.c_int64 * N)(),
                (c.c_int32 * N)(), (c.c_int32 * N)(), (c.c_int32 * N)(),
                (c.c_int32 * N)(), (c.c_uint64 * N)(), (c.c_uint64 * N)()]
        ptrs = [c.cast(a, c.c_void_p) for a in arrs]
        status = (c.c_int32 * N)(); lim = (c.c_int64 * N)()
        rem = (c.c_int64 * N)(); rst = (c.c_int64 * N)()
        eoff = (c.c_int32 * (N + 1))()
        moff = (c.c_int32 * (N + 1))()
        while not stop:
            got = lib.pls_next_batch(h, 50_000, keys, 1 << 20, *ptrs, N)
            if got <= 0:
                if got < 0:
                    return
                continue
            for i in range(got):
                status[i] = 0; lim[i] = 10; rem[i] = 9
                rst[i] = 12345; eoff[i + 1] = 0
            lib.pls_send_responses(h, got, ptrs[9], ptrs[10], ptrs[8],
                c.cast(status, c.c_void_p), c.cast(lim, c.c_void_p),
                c.cast(rem, c.c_void_p), c.cast(rst, c.c_void_p),
                c.cast(eoff, c.c_void_p), b"", c.cast(moff, c.c_void_p),
                b"")

    def frame(rid, n=1):
        name, ukey = b"t", b"key%d" % rid
        body = struct.pack("<QBH", rid, 1, 1)
        body += struct.pack("<H", len(name)) + struct.pack("<H", len(ukey))
        body += name + ukey
        body += struct.pack("<q", 1) + struct.pack("<q", 10)
        body += struct.pack("<q", 60000)
        body += struct.pack("<I", 0) + struct.pack("<I", 0)
        return struct.pack("<I", len(body)) + body

    def client(tid, calls):
        s = socket.create_connection(("127.0.0.1", port.value), timeout=10)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        buf = b""
        for i in range(calls):
            s.sendall(frame(tid * 100000 + i))
            # pipelined: read whenever data is there
            while len(buf) >= 4:
                (ln,) = struct.unpack_from("<I", buf, 0)
                if len(buf) - 4 < ln:
                    break
                buf = buf[4 + ln:]
            s.setblocking(True)
            buf += s.recv(4096)
        s.close()

    def churner(n):
        # rapid connect/half-frame/close: exercises close_conn vs responders
        for i in range(n):
            s = socket.create_connection(("127.0.0.1", port.value), timeout=10)
            s.sendall(struct.pack("<I", 40))  # length, then vanish
            s.close()

    pullers = [threading.Thread(target=puller) for _ in range(3)]
    [t.start() for t in pullers]
    clients = [threading.Thread(target=client, args=(t, 120))
               for t in range(6)] + [threading.Thread(target=churner,
                                                      args=(60,))]
    [t.start() for t in clients]
    [t.join(timeout=120) for t in clients]
    stop = True
    lib.pls_stop(h)
    [t.join(timeout=10) for t in pullers]
    lib.pls_free(h)
    print("PEERLINK_STRESS_OK")
""")

_KEYDIR_STRESS = textwrap.dedent("""
    import ctypes, sys, threading
    lib = ctypes.CDLL(sys.argv[1])
    c = ctypes
    lib.keydir_new.restype = c.c_void_p
    lib.keydir_new.argtypes = [c.c_int64]
    lib.keydir_free.argtypes = [c.c_void_p]
    lib.keydir_lookup_batch.restype = c.c_int64
    lib.keydir_lookup_batch.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p,
                                        c.c_int32, c.c_void_p, c.c_void_p,
                                        c.c_void_p, c.c_void_p]
    # offsets are int64_t[n+1] bounds into the packed key bytes
    lib.keydir_drop.argtypes = [c.c_void_p, c.c_char_p, c.c_int32]
    lib.keydir_dump.restype = c.c_int64
    lib.keydir_dump.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                c.c_void_p, c.c_void_p, c.c_int64]
    lib.keydir_mirror_seed.argtypes = [c.c_void_p, c.c_char_p, c.c_int32,
                                       c.c_void_p]
    lib.keydir_decide_one.restype = c.c_int32
    lib.keydir_decide_one.argtypes = [c.c_void_p, c.c_char_p, c.c_int32,
                                      c.c_int64, c.c_int64, c.c_int64,
                                      c.c_int32, c.c_int32, c.c_int64,
                                      c.c_void_p]
    lib.keydir_mirror_flush.restype = c.c_int32
    lib.keydir_mirror_flush.argtypes = [c.c_void_p, c.c_void_p, c.c_int32]
    lib.keydir_keys_for_slots.restype = c.c_int64
    lib.keydir_keys_for_slots.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                          c.c_void_p, c.c_int64, c.c_void_p]
    lib.keydir_slots_live.restype = None
    lib.keydir_slots_live.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                      c.c_void_p]
    lib.keydir_peek_batch.restype = c.c_int64
    lib.keydir_peek_batch.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p,
                                      c.c_int64, c.c_void_p]

    kd = lib.keydir_new(512)
    lock = threading.Lock()  # batch callers keep the engine-lock discipline

    def hammer(tid):
        W = 16
        slots = (c.c_int32 * W)()
        fresh = (c.c_uint8 * W)()
        inject = (c.c_int64 * (W * 8))()
        n_inj = (c.c_int32 * 1)()
        for i in range(400):
            parts = [b"k%d_%d" % (tid, (i + j) % 64) for j in range(W)]
            keys = b"".join(parts)
            offs = (c.c_int64 * (W + 1))()
            pos = 0
            for j, part in enumerate(parts):
                pos += len(part)
                offs[j + 1] = pos
            with lock:
                lib.keydir_lookup_batch(kd, keys, offs, W,
                                        c.cast(slots, c.c_void_p),
                                        c.cast(fresh, c.c_void_p),
                                        c.cast(inject, c.c_void_p),
                                        c.cast(n_inj, c.c_void_p))
            if i % 50 == 0:
                k = b"k%d_%d" % (tid, i % 64)
                with lock:
                    lib.keydir_drop(kd, k, len(k))

    def native_decider(tid):
        # the r3 lone-request path: decide_one + mirror seeds run WITHOUT
        # the engine lock (the IO-thread contract) — the KeyDir mutex is
        # the only synchronization, which is exactly what TSan must check
        row = (c.c_int64 * 7)(0, 100, 50, 60000, 1, 10**15, 0)
        out = (c.c_int64 * 4)()
        inject = (c.c_int64 * (64 * 8))()
        for i in range(600):
            k = b"k%d_%d" % (i % 6, i % 64)  # collide with batch keys
            lib.keydir_mirror_seed(kd, k, len(k), c.cast(row, c.c_void_p))
            lib.keydir_decide_one(kd, k, len(k), 1, 100, 60000, 0, 0,
                                  10**12 + i, c.cast(out, c.c_void_p))
            if i % 97 == 0:
                lib.keydir_mirror_flush(kd, c.cast(inject, c.c_void_p), 64)

    def resolver(tid):
        # the tickers' reverse lookup: no engine lock, the KeyDir mutex
        # taken chunk by chunk (three chunks a call here) with the batch
        # callers and the deciders let in between
        N = 20000
        slots = (c.c_int32 * N)(*[(i * 7) % 600 - 40 for i in range(N)])
        buf = (c.c_char * (N * 8))()
        offs = (c.c_int64 * (N + 1))()
        live = (c.c_uint8 * N)()
        parts = [b"k%d_%d" % (tid, j) for j in range(64)]
        names = b"".join(parts)
        name_offs = (c.c_int64 * 65)()
        for j, part in enumerate(parts):
            name_offs[j + 1] = name_offs[j] + len(part)
        where = (c.c_int32 * 64)()
        for i in range(40):
            got = lib.keydir_keys_for_slots(kd, c.cast(slots, c.c_void_p),
                                            N, c.cast(buf, c.c_void_p),
                                            N * 8, c.cast(offs, c.c_void_p))
            assert 0 <= got == offs[N], got
            # the ledger audit's two questions, the same way: which slots
            # hold a key (the chunked walk, nothing copied) and where
            # keys live (a peek a key)
            lib.keydir_slots_live(kd, c.cast(slots, c.c_void_p), N,
                                  c.cast(live, c.c_void_p))
            assert set(live) <= {0, 1}
            assert not any(live[j] for j in range(N)
                           if not 0 <= slots[j] < 512)
            lib.keydir_peek_batch(kd, names, c.cast(name_offs, c.c_void_p),
                                  64, c.cast(where, c.c_void_p))
            assert all(-1 <= s < 512 for s in where)

    ts = [threading.Thread(target=hammer, args=(t,)) for t in range(6)]
    ts += [threading.Thread(target=native_decider, args=(t,))
           for t in range(3)]
    ts += [threading.Thread(target=resolver, args=(t,)) for t in range(2)]
    [t.start() for t in ts]
    [t.join(timeout=120) for t in ts]
    lib.keydir_free(kd)
    print("KEYDIR_STRESS_OK")
""")


_GRPC_FRONT_FUZZ = textwrap.dedent("""
    import ctypes, random, socket, struct, sys, threading
    lib = ctypes.CDLL(sys.argv[1])
    c = ctypes
    lib.pls_start.restype = c.c_void_p
    lib.pls_start.argtypes = [c.c_int, c.POINTER(c.c_int)]
    lib.pls_stop.argtypes = [c.c_void_p]
    lib.pls_free.argtypes = [c.c_void_p]
    lib.pls_start_grpc.restype = c.c_int
    lib.pls_start_grpc.argtypes = [c.c_void_p, c.c_int, c.c_char_p]

    port = c.c_int(0)
    h = lib.pls_start(0, c.byref(port))
    assert h
    gp = lib.pls_start_grpc(h, 0, b"")
    assert gp > 0

    PRE = b"PRI * HTTP/2.0\\r\\n\\r\\nSM\\r\\n\\r\\n"
    def fr(t, flags, sid, payload=b""):
        return (struct.pack(">I", len(payload))[1:] + bytes([t, flags])
                + struct.pack(">I", sid) + payload)
    def lit(n, v):
        return bytes([0, len(n)]) + n + bytes([len(v)]) + v
    HDRS = (lit(b":method", b"POST") + lit(b":scheme", b"http")
            + lit(b":path", b"/pb.gubernator.V1/HealthCheck")
            + lit(b":authority", b"t")
            + lit(b"content-type", b"application/grpc"))
    VALID = (PRE + fr(4, 0, 0) + fr(1, 0x4, 1, HDRS)
             + fr(0, 0x1, 1, b"\\x00" + struct.pack(">I", 0)))
    BOMBS = [b"\\x80", b"\\x3f" + b"\\xff" * 12,
             b"\\x00\\x85garb\\xff\\x85" + b"\\xff" * 5,
             b"\\xff\\xff\\xff\\xff\\xff\\x7f"]

    def fuzzer(seed):
        # malformed H2/HPACK under TSan: IO thread parses while other
        # connections churn — the race surface the single-threaded fuzz
        # campaign (test_grpc_front) cannot see
        rng = random.Random(seed)
        for i in range(250):
            try:
                s = socket.create_connection(("127.0.0.1", gp), timeout=5)
                kind = i % 3
                if kind == 0:
                    s.sendall(PRE + rng.randbytes(rng.randrange(1, 200)))
                elif kind == 1:
                    m = bytearray(VALID)
                    for _ in range(rng.randrange(1, 5)):
                        m[rng.randrange(len(PRE), len(m))] = rng.randrange(256)
                    s.sendall(bytes(m))
                else:
                    s.sendall(PRE + fr(4, 0, 0)
                              + fr(1, 0x4, 1, BOMBS[i % len(BOMBS)]))
                if i % 7 == 0:
                    s.settimeout(0.05)
                    try:
                        s.recv(4096)
                    except OSError:
                        pass
                s.close()
            except OSError:
                pass

    def health(n):
        # concurrent VALID HealthChecks (C-cached) race the fuzzers'
        # connection churn through the same epoll loop
        for i in range(n):
            try:
                s = socket.create_connection(("127.0.0.1", gp), timeout=5)
                s.sendall(VALID)
                s.settimeout(0.5)
                try:
                    s.recv(8192)
                except OSError:
                    pass
                s.close()
            except OSError:
                pass

    ts = [threading.Thread(target=fuzzer, args=(t,)) for t in range(4)]
    ts += [threading.Thread(target=health, args=(150,))]
    [t.start() for t in ts]
    [t.join(timeout=240) for t in ts]
    lib.pls_stop(h)
    lib.pls_free(h)
    print("GRPC_FRONT_FUZZ_OK")
""")


@pytest.mark.skipif(LIBTSAN is None, reason="libtsan not installed")
@pytest.mark.parametrize("name,component,script,sentinel", [
    ("peerlink", "peerlink", _PEERLINK_STRESS, "PEERLINK_STRESS_OK"),
    ("keydir", "keydir", _KEYDIR_STRESS, "KEYDIR_STRESS_OK"),
    ("grpc_front", "peerlink", _GRPC_FRONT_FUZZ, "GRPC_FRONT_FUZZ_OK"),
])
def test_tsan_clean(tmp_path, name, component, script, sentinel):
    lib = _tsan_lib(component)
    worker = tmp_path / f"stress_{name}.py"
    worker.write_text(script)
    env = dict(os.environ)
    env["LD_PRELOAD"] = LIBTSAN
    env["TSAN_OPTIONS"] = _tsan_options()
    proc = subprocess.run(
        [sys.executable, str(worker), lib],
        env=env, capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    assert "WARNING: ThreadSanitizer" not in out, out[-4000:]
    assert proc.returncode == 0, out[-4000:]
    assert sentinel in proc.stdout, out[-2000:]
