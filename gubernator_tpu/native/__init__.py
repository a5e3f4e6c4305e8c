"""Native (C++) host-path components, loaded via ctypes.

Builds `keydir.cpp` / `peerlink.cpp` into cached shared libraries on first
use (g++ -O2, ~2 s each). The cache name is a hash of the source bytes plus
the compiler flags, so a binary is only ever loaded for the source it was
built from, whatever a copy of the tree did to mtimes. Everything here has a
pure-Python fallback — `NativeKeyDirectory` mirrors
models/keyspace.KeyDirectory exactly and the engines accept either.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import subprocess
import sysconfig
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from gubernator_tpu.obs import witness

log = logging.getLogger("gubernator_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_LOCK = witness.make_lock("native.loader")
_LIB: Optional[ctypes.CDLL] = None

# component -> (source file, component flags). Python.h is for keydir's
# prep_pack fast path; its symbols resolve from the host interpreter at
# load time (no -lpython needed on Linux)
COMPONENTS: Dict[str, Tuple[str, List[str]]] = {
    "keydir": ("keydir.cpp", [f"-I{sysconfig.get_paths()['include']}"]),
    "peerlink": ("peerlink.cpp", ["-pthread"]),
}
# flavor -> flags. "" is what the daemon loads; the sanitizer flavors are
# built by scripts/build_native.py and tests/test_tsan.py. TSan and ASan
# are mutually exclusive instrumentation, hence separate flavors;
# -fno-omit-frame-pointer keeps ASan stacks honest at -O1.
FLAVORS: Dict[str, List[str]] = {
    "": ["-O2"],
    "tsan": ["-O1", "-g", "-fsanitize=thread", "-pthread"],
    "asan": ["-O1", "-g", "-fsanitize=address", "-fno-omit-frame-pointer",
             "-pthread"],
    "ubsan": ["-O1", "-g", "-fsanitize=undefined", "-pthread"],
}


class NativeBuildError(RuntimeError):
    """g++ refused a native source; the message carries its stderr."""


# cache path -> compiler stderr. Only a compiler verdict is remembered (it
# is a function of the cache key); a transient OSError is retried.
_BUILD_ERRS: Dict[str, str] = {}


def cache_prefix(component: str, flavor: str = "") -> str:
    return f"_{flavor}_{component}_" if flavor else f"_{component}_"


def compile_command(component: str, flavor: str) -> Tuple[str, List[str]]:
    src_name, extra = COMPONENTS[component]
    return (os.path.join(_HERE, src_name),
            ["g++", *FLAVORS[flavor], *extra, "-shared", "-fPIC",
             "-std=c++17"])


def source_key(component: str, flavor: str = "") -> str:
    """Hash of the source bytes and the full compile command."""
    src, cmd = compile_command(component, flavor)
    h = hashlib.sha256("\0".join(cmd).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    return h.hexdigest()[:16]


def cache_path(component: str, flavor: str = "") -> str:
    return os.path.join(
        _HERE,
        f"{cache_prefix(component, flavor)}{source_key(component, flavor)}.so")


def build_component(component: str, flavor: str = "") -> str:
    """Compile a native component into its hash-keyed cache and return the
    path. One builder at a time (flock beside the sources): the rest wait
    and then find the result. Other-hash siblings are pruned under the
    same lock."""
    path = cache_path(component, flavor)
    if os.path.exists(path):
        return path
    if path in _BUILD_ERRS:
        raise NativeBuildError(_BUILD_ERRS[path])
    src, cmd = compile_command(component, flavor)
    # a file lock across processes, not a threading lock: the with-scope
    # below is named so the lock-order analysis does not take it for one
    serialise = os.path.join(_HERE, ".build.lock")
    with open(serialise, "w") as builders:
        fcntl.flock(builders, fcntl.LOCK_EX)
        if os.path.exists(path):
            return path
        tmp = f"{path}.{os.getpid()}.tmp"
        r = subprocess.run([*cmd, "-o", tmp, src],
                           capture_output=True, text=True)
        if r.returncode != 0:
            if os.path.exists(tmp):
                os.unlink(tmp)
            _BUILD_ERRS[path] = (
                f"{' '.join(cmd)} {src} exited {r.returncode}:\n{r.stderr}")
            raise NativeBuildError(_BUILD_ERRS[path])
        os.replace(tmp, path)
        prefix = cache_prefix(component, flavor)
        for name in os.listdir(_HERE):
            if name.startswith(prefix) and name.endswith(".so") and \
                    os.path.join(_HERE, name) != path:
                os.unlink(os.path.join(_HERE, name))
    return path


def load_library() -> ctypes.CDLL:
    """Build (if needed) and load the native library; raises on failure."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        lib = ctypes.CDLL(build_component("keydir"))
        c = ctypes
        lib.keydir_new.restype = c.c_void_p
        lib.keydir_new.argtypes = [c.c_int64]
        lib.keydir_free.argtypes = [c.c_void_p]
        lib.keydir_lookup_batch.restype = c.c_int64
        lib.keydir_lookup_batch.argtypes = [
            c.c_void_p, c.c_char_p, c.c_void_p, c.c_int32, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p,
        ]
        lib.keydir_mirror_seed.argtypes = [
            c.c_void_p, c.c_char_p, c.c_int32, c.c_void_p,
        ]
        lib.keydir_decide_one.restype = c.c_int32
        lib.keydir_decide_one.argtypes = [
            c.c_void_p, c.c_char_p, c.c_int32, c.c_int64, c.c_int64,
            c.c_int64, c.c_int32, c.c_int32, c.c_int64, c.c_void_p,
        ]
        lib.keydir_mirror_flush.restype = c.c_int32
        lib.keydir_mirror_flush.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int32,
        ]
        lib.keydir_drop.argtypes = [c.c_void_p, c.c_char_p, c.c_int32]
        lib.keydir_peek.restype = c.c_int32
        lib.keydir_peek.argtypes = [c.c_void_p, c.c_char_p, c.c_int32]
        lib.keydir_dump.restype = c.c_int64
        lib.keydir_dump.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p, c.c_void_p, c.c_int64,
        ]
        lib.keydir_keys_for_slots.restype = c.c_int64
        lib.keydir_keys_for_slots.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
            c.c_void_p,
        ]
        lib.keydir_slots_live.restype = None
        lib.keydir_slots_live.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p,
        ]
        lib.keydir_size.restype = c.c_int64
        lib.keydir_size.argtypes = [c.c_void_p]
        lib.keydir_evictions.restype = c.c_int64
        lib.keydir_evictions.argtypes = [c.c_void_p]
        lib.keydir_churn_stats.restype = None
        lib.keydir_churn_stats.argtypes = [c.c_void_p, c.c_void_p]
        lib.fnv1a_owner_batch.argtypes = [
            c.c_char_p, c.c_void_p, c.c_int32, c.c_int32, c.c_void_p,
        ]
        lib.fnv1a_fingerprint_batch.argtypes = [
            c.c_char_p, c.c_void_p, c.c_int32, c.c_void_p,
        ]
        # columnar prep is pure C (no CPython API): riding the CDLL handle
        # releases the GIL for the whole pass
        lib.keydir_prep_pack_columnar.restype = c.c_int32
        lib.keydir_prep_pack_columnar.argtypes = [
            c.c_void_p, c.c_int32, c.c_char_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_int64, c.c_void_p, c.c_int32, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p,
        ]
        lib.keydir_prep_route_columnar.restype = c.c_int32
        lib.keydir_prep_route_columnar.argtypes = [
            c.c_void_p, c.c_int32, c.c_int32, c.c_char_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p,
        ]
        lib.keydir_intern_max_cfg.restype = c.c_int64
        lib.keydir_intern_max_cfg.argtypes = []
        lib.keydir_intern_hash_slots.restype = c.c_int64
        lib.keydir_intern_hash_slots.argtypes = []
        lib.keydir_prep_pack_interned.restype = c.c_int32
        lib.keydir_prep_pack_interned.argtypes = [
            # kd, n, keys, key_off, name_len, hits, limit, duration,
            # algorithm, behavior, slow_mask, iw, width, cfg, n_cfg,
            # cfg_hash, lane_item, leftover, n_leftover_out, inject,
            # n_inject — 21 params; a count mismatch here reads stale
            # stack in C (wild pointers), so keep this list annotated
            c.c_void_p, c.c_int32, c.c_char_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_int64, c.c_void_p, c.c_int32, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p,
        ]
        lib.keydir_peek_batch.restype = c.c_int64
        lib.keydir_peek_batch.argtypes = [
            c.c_void_p, c.c_char_p, c.c_void_p, c.c_int64, c.c_void_p,
        ]
        lib.keydir_lean_max_cfg.restype = c.c_int64
        lib.keydir_lean_max_cfg.argtypes = []
        lib.keydir_lean_hash_slots.restype = c.c_int64
        lib.keydir_lean_hash_slots.argtypes = []
        # same 21-slot layout as keydir_prep_pack_interned (iw is i32[width],
        # cfg i64[128][4], cfg_hash i32[512]) — see that annotation
        lib.keydir_prep_pack_lean.restype = c.c_int32
        lib.keydir_prep_pack_lean.argtypes = \
            list(lib.keydir_prep_pack_interned.argtypes)
        _LIB = lib
        return lib


_PL_LIB: Optional[ctypes.CDLL] = None


def load_peerlink() -> ctypes.CDLL:
    """Build (if needed) and load the peerlink transport library.

    CDLL on purpose: pls_next_batch blocks in C waiting for frames, and the
    GIL must be released for the whole wait."""
    global _PL_LIB
    with _LIB_LOCK:
        if _PL_LIB is not None:
            return _PL_LIB
        lib = ctypes.CDLL(build_component("peerlink"))
        c = ctypes
        lib.pls_start.restype = c.c_void_p
        lib.pls_start.argtypes = [c.c_int, c.POINTER(c.c_int)]
        # v2-capable start: third arg caps the negotiable wire contract
        # (2 = greet clients / accept HELLO; 1 = byte-exact v1 server)
        lib.pls_start2.restype = c.c_void_p
        lib.pls_start2.argtypes = [c.c_int, c.POINTER(c.c_int), c.c_int]
        lib.pls_stop.argtypes = [c.c_void_p]
        lib.pls_free.argtypes = [c.c_void_p]
        lib.pls_port.restype = c.c_int
        lib.pls_port.argtypes = [c.c_void_p]
        lib.pls_next_batch.restype = c.c_int
        lib.pls_next_batch.argtypes = [
            c.c_void_p, c.c_longlong, c.c_char_p, c.c_int, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_int,
        ]
        lib.pls_send_responses.argtypes = [
            # h, n, conn_token, rid, idx, status, limit, remaining, reset,
            # err_off, err_buf, meta_off, meta_buf — 13 params (the meta
            # sidecar carries pre-encoded pb metadata for gRPC replies)
            c.c_void_p, c.c_int, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_char_p, c.c_void_p, c.c_char_p,
        ]
        lib.pls_send_partial.argtypes = [
            # h, conn_token, rid, base, n, status, limit, remaining,
            # reset, err_off, err_buf, meta_off, meta_buf — 13 params;
            # err_off/meta_off are SPAN-relative (n+1 entries each)
            c.c_void_p, c.c_ulonglong, c.c_ulonglong, c.c_int, c.c_int,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_char_p, c.c_void_p, c.c_char_p,
        ]
        lib.pls_pending_count.restype = c.c_longlong
        lib.pls_pending_count.argtypes = [c.c_void_p]
        lib.pls_partial_posts.restype = c.c_longlong
        lib.pls_partial_posts.argtypes = [c.c_void_p]
        lib.pls_v2_conns.restype = c.c_longlong
        lib.pls_v2_conns.argtypes = [c.c_void_p]
        lib.pls_profile.restype = c.c_int
        lib.pls_profile.argtypes = [c.c_void_p, c.POINTER(c.c_longlong),
                                    c.c_int]
        # ---- gRPC/HTTP/2 front ----
        lib.pls_start_grpc.restype = c.c_int
        lib.pls_start_grpc.argtypes = [c.c_void_p, c.c_int, c.c_char_p]
        lib.pls_grpc_port.restype = c.c_int
        lib.pls_grpc_port.argtypes = [c.c_void_p]
        lib.pls_set_health.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
        lib.pls_next_raw.restype = c.c_int
        lib.pls_next_raw.argtypes = [
            # h, timeout_us, path, path_cap, path_len, body, body_cap,
            # conn_token, stream_id — 9 params
            c.c_void_p, c.c_longlong, c.c_char_p, c.c_int, c.c_void_p,
            c.c_char_p, c.c_int, c.c_void_p, c.c_void_p,
        ]
        lib.pls_send_raw.argtypes = [
            c.c_void_p, c.c_ulonglong, c.c_uint, c.c_char_p, c.c_int,
            c.c_int, c.c_char_p,
        ]
        lib.pls_set_native.argtypes = [
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_longlong,
        ]
        lib.pls_native_hits.restype = c.c_longlong
        lib.pls_native_hits.argtypes = [c.c_void_p]
        lib.pls_set_native_public.argtypes = [c.c_void_p, c.c_int]
        _PL_LIB = lib
        return lib


_PYLIB: Optional[ctypes.PyDLL] = None


def load_pydll() -> ctypes.PyDLL:
    """The same library via PyDLL — calls hold the GIL, as the
    PyObject-consuming prep_pack fast path requires."""
    global _PYLIB
    with _LIB_LOCK:
        if _PYLIB is not None:
            return _PYLIB
    load_library()  # build + validate first (its own locking)
    with _LIB_LOCK:
        if _PYLIB is None:
            c = ctypes
            lib = ctypes.PyDLL(cache_path("keydir"))
            lib.keydir_prep_pack_fast.restype = c.c_int32
            lib.keydir_prep_pack_fast.argtypes = [
                c.c_void_p, c.py_object, c.c_void_p, c.c_int32, c.c_int64,
                c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            ]
            lib.keydir_prep_route_sharded.restype = c.c_int32
            lib.keydir_prep_route_sharded.argtypes = [
                c.c_void_p, c.c_int32, c.py_object, c.c_int64,
                c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            ]
            _PYLIB = lib
        return _PYLIB


# prep_pack_fast return codes (keydir.cpp)
PREP_FALLBACK = -1
PREP_OVERCOMMIT = -2


def prep_pack_fast(directory: "NativeKeyDirectory", requests,
                   packed: np.ndarray, greg_mask: int):
    """One-pass native window prep: validate + first-occurrence round split
    + directory lookup + pack in one C call. `packed` must be a zeroed
    C-contiguous i64[9, width].

    Returns (n0, lane_item, leftover, inject): n0 lanes packed (lane j
    answers requests[lane_item[j]]), with `leftover` the item indices the
    python pipeline must run AFTER this round (invalid / gregorian /
    duplicate occurrences) and `inject` the i64[m, 8] dirty-mirror rows
    (slot + 7 row values) the engine must scatter into the device table
    BEFORE this window decides (native lone-path reconciliation). n0 is
    PREP_FALLBACK or PREP_OVERCOMMIT on the non-sequence/oversize and
    over-commit paths."""
    lib = load_pydll()
    width = packed.shape[1]
    n = len(requests)
    lane_item = np.empty(width, np.int32)
    leftover = np.empty(n, np.int32)
    n_left = np.zeros(1, np.int32)
    inject = np.empty((n, 8), np.int64)
    n_inj = np.zeros(1, np.int32)
    n0 = lib.keydir_prep_pack_fast(
        directory._kd, requests, packed.ctypes.data, width, greg_mask,
        lane_item.ctypes.data, leftover.ctypes.data, n_left.ctypes.data,
        inject.ctypes.data, n_inj.ctypes.data,
    )
    if n0 < 0:
        # over-commit may abort MID-lookup with dirty-mirror rows already
        # collected (and their flags cleared): hand them back so the
        # engine can still apply them before raising
        return n0, None, None, inject[:int(n_inj[0])]
    return (n0, lane_item[:n0], leftover[:int(n_left[0])],
            inject[:int(n_inj[0])])


def prep_pack_columnar(directory: "NativeKeyDirectory", n: int,
                       keys, key_off, name_len, hits, limit, duration,
                       algorithm, behavior, slow_mask: int,
                       packed: np.ndarray):
    """Columnar one-pass window prep: the peerlink wire columns straight
    into the decide staging buffer — no RateLimitReq objects, no GIL.

    `keys` is the name+unique_key byte arena (ctypes buffer or bytes);
    key_off i32[>=n+1]; name_len/algorithm/behavior i32; hits/limit/
    duration i64; `packed` a zeroed C-contiguous i64[9, width].

    Returns (n0, lane_item, leftover, inject) like prep_pack_fast."""
    lib = load_library()
    width = packed.shape[1]
    lane_item = np.empty(width, np.int32)
    leftover = np.empty(n, np.int32)
    n_left = np.zeros(1, np.int32)
    inject = np.empty((n, 8), np.int64)
    n_inj = np.zeros(1, np.int32)
    n0 = lib.keydir_prep_pack_columnar(
        directory._kd, n, keys,
        key_off.ctypes.data, name_len.ctypes.data, hits.ctypes.data,
        limit.ctypes.data, duration.ctypes.data, algorithm.ctypes.data,
        behavior.ctypes.data, slow_mask, packed.ctypes.data, width,
        lane_item.ctypes.data, leftover.ctypes.data, n_left.ctypes.data,
        inject.ctypes.data, n_inj.ctypes.data,
    )
    if n0 < 0:
        return n0, None, None, inject[:int(n_inj[0])]
    return (n0, lane_item[:n0], leftover[:int(n_left[0])],
            inject[:int(n_inj[0])])


# keydir_prep_pack_interned: the window needs more distinct
# (limit, duration) pairs than the config table holds — re-prep wide
PREP_CFG_OVERFLOW = -3


class InternPrepState:
    """Caller-owned persistent state for the interned columnar prep: the
    i64[256, 2] (limit, duration) config table the device receives, its
    fill count, and the C-side find-or-insert map. One instance per
    serving loop / engine; ships cfg to the device whenever n_cfg grows."""

    def __init__(self):
        lib = load_library()  # buffer sizes come from the C side so the
        max_cfg = lib.keydir_intern_max_cfg()  # compile-time constants
        slots = lib.keydir_intern_hash_slots()  # can never drift past the
        self.cfg = np.zeros((max_cfg, 2), np.int64)  # allocations
        self._n_cfg = np.zeros(1, np.int32)
        self._hash = np.zeros((slots, 2), np.int64)

    @property
    def n_cfg(self) -> int:
        return int(self._n_cfg[0])


def _prep_pack_cfg(fn, width: int, directory: "NativeKeyDirectory", n: int,
                   keys, key_off, name_len, hits, limit, duration,
                   algorithm, behavior, slow_mask: int, iw: np.ndarray,
                   state):
    """Shared driver for the two config-interning preps (interned / lean):
    identical buffer setup, ctypes call shape, and (n0, lane_item,
    leftover, inject) return contract — only the C entry point, staging
    width, and state type differ."""
    lane_item = np.empty(width, np.int32)
    leftover = np.empty(n, np.int32)
    n_left = np.zeros(1, np.int32)
    inject = np.empty((n, 8), np.int64)
    n_inj = np.zeros(1, np.int32)
    n0 = fn(
        directory._kd, n, keys,
        key_off.ctypes.data, name_len.ctypes.data, hits.ctypes.data,
        limit.ctypes.data, duration.ctypes.data, algorithm.ctypes.data,
        behavior.ctypes.data, slow_mask, iw.ctypes.data, width,
        state.cfg.ctypes.data, state._n_cfg.ctypes.data,
        state._hash.ctypes.data,
        lane_item.ctypes.data, leftover.ctypes.data, n_left.ctypes.data,
        inject.ctypes.data, n_inj.ctypes.data,
    )
    if n0 < 0:
        return n0, None, None, inject[:int(n_inj[0])]
    return (n0, lane_item[:n0], leftover[:int(n_left[0])],
            inject[:int(n_inj[0])])


def prep_pack_interned(directory: "NativeKeyDirectory", n: int,
                       keys, key_off, name_len, hits, limit, duration,
                       algorithm, behavior, slow_mask: int,
                       iw: np.ndarray, state: InternPrepState):
    """Columnar one-pass prep emitting the INTERNED staging format
    (ops/decide.py decide_packed_interned): `iw` is i32[2, width] (no
    pre-zeroing needed — every lane is written), `state` persists the
    config table across windows. Lanes the interned format cannot carry
    demote to `leftover`; a window needing >256 distinct configs returns
    PREP_CFG_OVERFLOW with the directory and config state untouched
    (caller re-preps that window through prep_pack_columnar).

    Returns (n0, lane_item, leftover, inject) like prep_pack_columnar."""
    lib = load_library()
    return _prep_pack_cfg(
        lib.keydir_prep_pack_interned, iw.shape[1], directory, n, keys,
        key_off, name_len, hits, limit, duration, algorithm, behavior,
        slow_mask, iw, state)


# keydir_prep_pack_lean: the directory's capacity exceeds the 24-bit lane
# field — the caller's capacity gate (ops/decide.py lean_capacity_ok) was
# skipped. Checked at entry, BEFORE the lookup commits inserts/LRU/inject
# rows: the directory and config state are untouched on this return
PREP_SLOT_WIDE = -4


class LeanPrepState:
    """Caller-owned persistent state for the lean columnar prep: the
    i64[128, 4] (limit, duration, algorithm, behavior) config table the
    device receives, its fill count, and the C-side find-or-insert map
    (i32[512] of id+1). One instance per serving loop / engine; ships cfg
    to the device whenever n_cfg grows."""

    def __init__(self):
        lib = load_library()  # sizes come from the C compile-time constants
        max_cfg = lib.keydir_lean_max_cfg()
        slots = lib.keydir_lean_hash_slots()
        self.cfg = np.zeros((max_cfg, 4), np.int64)
        self._n_cfg = np.zeros(1, np.int32)
        self._hash = np.zeros(slots, np.int32)

    @property
    def n_cfg(self) -> int:
        return int(self._n_cfg[0])


def prep_pack_lean(directory: "NativeKeyDirectory", n: int,
                   keys, key_off, name_len, hits, limit, duration,
                   algorithm, behavior, slow_mask: int,
                   iw: np.ndarray, state: LeanPrepState):
    """Columnar one-pass prep emitting the LEAN staging format
    (ops/decide.py decide_packed_lean): `iw` is i32[width] — ONE word per
    lane, 4 bytes/decision on the wire (no pre-zeroing needed — every lane
    is written), `state` persists the config table across windows. Lanes
    the lean format cannot carry (hits != 1, out-of-range values,
    slow-mask behaviors) demote to `leftover`; >128 distinct configs
    returns PREP_CFG_OVERFLOW with directory and config state untouched.
    The caller must hold the capacity gate: directory capacity <= 0xFFFFFF
    (lean_capacity_ok) — PREP_SLOT_WIDE flags a breach, detected at entry
    with the directory untouched.

    Returns (n0, lane_item, leftover, inject) like prep_pack_columnar."""
    lib = load_library()
    return _prep_pack_cfg(
        lib.keydir_prep_pack_lean, iw.shape[0], directory, n, keys,
        key_off, name_len, hits, limit, duration, algorithm, behavior,
        slow_mask, iw, state)


def prep_route_columnar(directories, n: int, keys, key_off, name_len,
                        hits, limit, duration, algorithm, behavior,
                        slow_mask: int):
    """Columnar sharded prep: the peerlink wire columns routed to owner
    shards in one GIL-free C pass (see prep_route_sharded for the output
    contract). Returns (n0, cols, lane_item, owner_count, leftover)."""
    lib = load_library()
    n_owners = len(directories)
    handles = (ctypes.c_void_p * n_owners)(*[d._kd for d in directories])
    cols = np.zeros((9, n), np.int64)
    lane_item = np.empty(n, np.int32)
    owner_count = np.empty(n_owners, np.int32)
    leftover = np.empty(n, np.int32)
    n_left = np.zeros(1, np.int32)
    n0 = lib.keydir_prep_route_columnar(
        handles, n_owners, n, keys,
        key_off.ctypes.data, name_len.ctypes.data, hits.ctypes.data,
        limit.ctypes.data, duration.ctypes.data, algorithm.ctypes.data,
        behavior.ctypes.data, slow_mask,
        cols.ctypes.data, lane_item.ctypes.data, owner_count.ctypes.data,
        leftover.ctypes.data, n_left.ctypes.data,
    )
    if n0 < 0:
        return n0, None, None, None, None
    return (n0, cols, lane_item[:n0], owner_count,
            leftover[:int(n_left[0])])


def prep_route_sharded(directories, requests, greg_mask: int):
    """Sharded one-pass native window prep: validate + first-occurrence
    split + owner routing (fnv1a % n_owners) + per-owner directory lookup.

    Returns (n0, cols, lane_item, owner_count, leftover): `cols` is
    i64[9, len(requests)] with the first n0 lanes owner-major in the decide
    staging row order (rows 6/7 zero); lane j answers
    requests[lane_item[j]]; owner o owns the owner_count[o]-lane run at
    offset sum(owner_count[:o]). n0 is PREP_FALLBACK / PREP_OVERCOMMIT on
    the corresponding paths (cols et al. are None then)."""
    lib = load_pydll()
    n = len(requests)
    n_owners = len(directories)
    handles = (ctypes.c_void_p * n_owners)(*[d._kd for d in directories])
    cols = np.zeros((9, n), np.int64)
    lane_item = np.empty(n, np.int32)
    owner_count = np.empty(n_owners, np.int32)
    leftover = np.empty(n, np.int32)
    n_left = np.zeros(1, np.int32)
    n0 = lib.keydir_prep_route_sharded(
        handles, n_owners, requests, greg_mask,
        cols.ctypes.data, lane_item.ctypes.data, owner_count.ctypes.data,
        leftover.ctypes.data, n_left.ctypes.data,
    )
    if n0 < 0:
        return n0, None, None, None, None
    return (n0, cols, lane_item[:n0], owner_count,
            leftover[:int(n_left[0])])


def available() -> bool:
    """Whether the native library builds and loads here (no compiler, or
    a source it refuses: False)."""
    try:
        load_library()
        return True
    except (NativeBuildError, OSError):
        return False


def pack_keys(keys: Sequence[str]) -> Tuple[bytes, np.ndarray]:
    """Concatenate utf-8 keys; offsets[n+1] int64.

    Fast path: one join + one encode; when the result is pure ASCII,
    character counts equal byte counts so no per-key encode is needed."""
    n = len(keys)
    joined = "".join(keys)
    data = joined.encode("utf-8")
    offsets = np.zeros(n + 1, np.int64)
    if len(data) == len(joined):
        lens = np.fromiter(map(len, keys), np.int64, count=n)
    else:
        blobs = [k.encode("utf-8") for k in keys]
        data = b"".join(blobs)
        lens = np.fromiter(map(len, blobs), np.int64, count=n)
    np.cumsum(lens, out=offsets[1:])
    return data, offsets


def unpack_keys(data: bytes, offsets: np.ndarray, which=None) -> List[str]:
    """pack_keys read back: the keys of a packed arena, or only those at
    the indices `which`."""
    lo, hi = offsets[:-1], offsets[1:]
    if which is not None:
        lo, hi = lo[which], hi[which]
    bounds = zip(lo.tolist(), hi.tolist())
    if data.isascii():  # one decode, then slices: bytes are characters
        text = data.decode("ascii")
        return [text[a:b] for a, b in bounds]
    return [data[a:b].decode("utf-8") for a, b in bounds]


def fingerprint_batch(keys: Sequence[str]) -> np.ndarray:
    """63-bit nonzero key fingerprints for the device directory
    (ops/devdir.py key_fingerprint, C fast path)."""
    lib = load_library()
    data, offsets = pack_keys(keys)
    out = np.empty(len(keys), np.int64)
    lib.fnv1a_fingerprint_batch(
        data, offsets.ctypes.data, len(keys), out.ctypes.data)
    return out


def owner_batch(keys: Sequence[str], n_owners: int) -> np.ndarray:
    """fnv1a64(key) % n_owners for a key batch (native fast path of
    parallel/mesh.py shard_of_key)."""
    lib = load_library()
    data, offsets = pack_keys(keys)
    out = np.empty(len(keys), np.int32)
    lib.fnv1a_owner_batch(
        data, offsets.ctypes.data, len(keys), n_owners, out.ctypes.data
    )
    return out


def _as_slots32(slots) -> np.ndarray:
    """Any integers in, int32 out: what int32 cannot hold is no slot."""
    return np.clip(np.asarray(slots, np.int64), -1,
                   np.iinfo(np.int32).max).astype(np.int32)


class NativeKeyDirectory:
    """Drop-in replacement for models/keyspace.KeyDirectory backed by the
    C++ open-addressing LRU table."""

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._lib = load_library()
        self._kd = self._lib.keydir_new(capacity)
        if not self._kd:
            raise MemoryError("keydir_new failed")

    def __del__(self):
        kd = getattr(self, "_kd", None)
        if kd:
            self._lib.keydir_free(kd)
            self._kd = None

    def __len__(self) -> int:
        return int(self._lib.keydir_size(self._kd))

    def __contains__(self, key: str) -> bool:
        return self.peek_slot(key) >= 0

    @property
    def evictions(self) -> int:
        return int(self._lib.keydir_evictions(self._kd))

    def churn_stats(self) -> dict:
        """What the directory counts of keys coming and going, beside
        `evictions()`: `inserts`, the keys it has given a slot (a restore's
        and the serving path's fresh lanes alike), and the tombstone
        rebuilds of the bucket array: how many, the nanoseconds they took
        in all, the longest one (each walks every live entry under the
        directory's mutex, so each is a stall)."""
        out = (ctypes.c_int64 * 4)()
        self._lib.keydir_churn_stats(self._kd, out)
        return {"inserts": int(out[0]), "rebuilds": int(out[1]),
                "rebuild_ns": int(out[2]), "rebuild_max_ns": int(out[3])}

    def lookup(self, keys: Sequence[str]) -> Tuple[List[int], List[bool]]:
        slots, fresh, inject = self.lookup_inject(keys)
        # a caller that discards the inject rows (snapshot load overwrites
        # them anyway) still invalidated the mirrors, which is the contract
        return slots, fresh

    def lookup_inject(self, keys: Sequence[str]):
        """lookup() + the dirty-mirror rows (i64[m, 8]: slot + 7 row
        values) that must be scattered into the device table BEFORE the
        window these slots feed (native lone-path reconciliation)."""
        data, offsets = pack_keys(keys)
        n = len(keys)
        slots = np.empty(n, np.int32)
        fresh = np.empty(n, np.uint8)
        inject = np.empty((n, 8), np.int64)
        n_inj = np.zeros(1, np.int32)
        done = self._lib.keydir_lookup_batch(
            self._kd, data, offsets.ctypes.data, n,
            slots.ctypes.data, fresh.ctypes.data,
            inject.ctypes.data, n_inj.ctypes.data,
        )
        if done != n:
            raise RuntimeError(
                f"key directory over-committed: >{self.capacity} distinct "
                "keys in one lookup"
            )
        return (slots.tolist(), fresh.astype(bool).tolist(),
                inject[:int(n_inj[0])])

    def mirror_seed(self, key: str, row7: Sequence[int]) -> None:
        """Install a device row copy as the key's mirror (see keydir.cpp
        Mirror); subsequent decide_one calls serve natively until a batch
        lookup invalidates it."""
        b = key.encode("utf-8")
        row = np.asarray(list(row7), np.int64)
        self._lib.keydir_mirror_seed(self._kd, b, len(b), row.ctypes.data)

    def mirror_flush(self, max_rows: int = 4096) -> np.ndarray:
        """Drain dirty mirrors for snapshot/shutdown coherence: returns
        i64[m, 8] reconciliation rows (callers loop until empty)."""
        inject = np.empty((max_rows, 8), np.int64)
        m = self._lib.keydir_mirror_flush(
            self._kd, inject.ctypes.data, max_rows)
        return inject[:m]

    def decide_one(self, key: str, hits: int, limit: int, duration: int,
                   algorithm: int, behavior: int, now_ms: int = 0):
        """Native lone decision against the mirror; None = miss (take the
        kernel path). now_ms=0 reads the wall clock in C."""
        b = key.encode("utf-8")
        out = np.empty(4, np.int64)
        hit = self._lib.keydir_decide_one(
            self._kd, b, len(b), hits, limit, duration, algorithm,
            behavior, now_ms, out.ctypes.data)
        return tuple(out.tolist()) if hit else None

    def drop(self, key: str) -> None:
        b = key.encode("utf-8")
        self._lib.keydir_drop(self._kd, b, len(b))

    def peek_slot(self, key: str) -> int:
        b = key.encode("utf-8")
        return int(self._lib.keydir_peek(self._kd, b, len(b)))

    _DUMP_HEADROOM = 16384  # two 8192-wide windows of fresh keys

    def items_raw(self) -> Tuple[bytes, np.ndarray, np.ndarray]:
        """(key_blob, offsets i64[n+1], slots i32[n]) without per-key
        decode — the streamed binary snapshot's directory walk (10M
        python tuples/str decodes would dominate the save otherwise)."""
        if len(self) == 0:
            return b"", np.zeros(1, np.int64), np.empty(0, np.int32)
        buf_cap = 1 << 16
        while True:
            # the directory keeps serving while it is dumped: size both
            # buffers from what it holds NOW, with headroom for the keys a
            # window may insert before the dump takes the mutex. (A count
            # read once, outside the loop, made every retry fail the same
            # way while the key buffer doubled without bound — the 40 GiB
            # host of PR 22's first chip runs.)
            n = len(self) + self._DUMP_HEADROOM
            key_buf = ctypes.create_string_buffer(buf_cap)
            offsets = np.empty(n + 1, np.int64)
            slots = np.empty(n, np.int32)
            count = self._lib.keydir_dump(
                self._kd, key_buf, buf_cap, offsets.ctypes.data,
                slots.ctypes.data, n,
            )
            if count >= 0:
                break
            # -count is the key bytes held at that instant, whichever
            # bound was short
            buf_cap = max(buf_cap, -count + (-count >> 3) + (1 << 16))
        count = int(count)
        return (key_buf.raw[:int(offsets[count])], offsets[:count + 1],
                slots[:count])

    def keys_for_slots(self, slots) -> Tuple[bytes, np.ndarray]:
        """(key_blob, offsets i64[n+1]) for an array of slots: key i is
        `key_blob[offsets[i]:offsets[i + 1]]`, empty for a free, negative
        or out-of-range slot. Reverse lookup by index (keydir.cpp
        keys_for_slots): the cost is the slots asked, not the directory,
        and the directory's mutex is held for at most 8,192 slots at a
        time, so tickers resolve slots through this and never through
        items_raw(). The directory keeps serving meanwhile: each slot is
        answered with the key that holds it at that instant, so a slot
        recycled since the caller saw it names its new key (the dump had
        the same contract)."""
        slots = _as_slots32(slots)
        n = len(slots)
        offsets = np.empty(n + 1, np.int64)
        buf_cap = 48 * n + (1 << 16)
        while True:
            key_buf = np.empty(buf_cap, np.uint8)
            nbytes = self._lib.keydir_keys_for_slots(
                self._kd, slots.ctypes.data, n, key_buf.ctypes.data,
                buf_cap, offsets.ctypes.data)
            if nbytes >= 0:
                return key_buf[:nbytes].tobytes(), offsets
            # -nbytes is what these slots' keys took at that instant
            buf_cap = -nbytes + (-nbytes >> 3) + (1 << 16)

    def slots_live(self, slots) -> np.ndarray:
        """bool[n]: which of `slots` hold a key right now (keydir.cpp
        slots_live: keys_for_slots' walk and instant-by-chunk contract,
        with no key copied)."""
        slots = _as_slots32(slots)
        live = np.empty(len(slots), np.uint8)
        self._lib.keydir_slots_live(
            self._kd, slots.ctypes.data, len(slots), live.ctypes.data)
        return live.view(np.bool_)

    def peek_slots_raw(self, key_blob: bytes, offsets: np.ndarray
                       ) -> np.ndarray:
        """Batch peek over a packed key arena -> i32 slots (-1 = absent);
        LRU order untouched. One GIL-free C pass per snapshot slab."""
        n = len(offsets) - 1
        out = np.empty(n, np.int32)
        if n:
            off = np.ascontiguousarray(offsets, np.int64)
            self._lib.keydir_peek_batch(
                self._kd, key_blob, off.ctypes.data, n, out.ctypes.data)
        return out

    def lookup_raw(self, key_blob: bytes, offsets: np.ndarray):
        """lookup_inject over a packed arena (the binary restore path:
        no per-key str round trip). Returns (slots i32[n], fresh bool[n],
        inject rows)."""
        n = len(offsets) - 1
        slots = np.empty(n, np.int32)
        fresh = np.empty(n, np.uint8)
        inject = np.empty((max(n, 1), 8), np.int64)
        n_inj = np.zeros(1, np.int32)
        off = np.ascontiguousarray(offsets, np.int64)
        done = self._lib.keydir_lookup_batch(
            self._kd, key_blob, off.ctypes.data, n,
            slots.ctypes.data, fresh.ctypes.data,
            inject.ctypes.data, n_inj.ctypes.data,
        )
        if done != n:
            raise RuntimeError(
                f"key directory over-committed: >{self.capacity} distinct "
                "keys in one lookup"
            )
        return slots, fresh.astype(bool), inject[:int(n_inj[0])]

    def items(self) -> List[Tuple[str, int]]:
        raw, offsets, slots = self.items_raw()
        return [
            (raw[offsets[i]:offsets[i + 1]].decode("utf-8"), int(slots[i]))
            for i in range(len(slots))
        ]

    def keys(self) -> List[str]:
        return [k for k, _ in self.items()]


def make_key_directory(capacity: int, prefer_native: bool = True):
    """Factory: native directory when buildable, python fallback otherwise."""
    # guberlint: disable=knob-drift -- dev/bench escape: forces the python fallback without a config cycle; not an operator surface
    if prefer_native and not os.environ.get("GUBER_NO_NATIVE"):
        try:
            return NativeKeyDirectory(capacity)
        except (NativeBuildError, OSError) as e:
            log.warning(
                "native key directory unavailable, serving from the Python "
                "directory (set GUBER_NO_NATIVE=1 to choose it on purpose): "
                "%s", e)
    from gubernator_tpu.models.keyspace import KeyDirectory

    return KeyDirectory(capacity)
