"""What the process can say about the device it serves from, and the two
backend settings decided once at boot: buffer donation and where the
persistent compile cache lives.
"""

from __future__ import annotations

import collections
import ctypes
import functools
import os
import time

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@functools.lru_cache(maxsize=None)
def donation_supported() -> bool:
    """Dispatch one donated update and require that the backend took the
    buffer. Donation lets the decision kernel update the key table in
    place; every backend this repo runs on (TPU, and XLA:CPU under test)
    takes it, so a refusal is a boot failure — never a quiet second
    table-sized copy per window."""
    x = jnp.zeros((8,), jnp.int64)
    jax.jit(lambda v: v + 1, donate_argnums=0)(x).block_until_ready()
    if not x.is_deleted():
        raise RuntimeError(
            f"the {jax.default_backend()} backend did not take a donated "
            "buffer: the key table cannot be updated in place (pass "
            "donate=False to the engine to run without donation on purpose)")
    return True


def compile_cache_dir() -> str:
    """Place JAX's persistent compile cache; call before the first compile.

    JAX_COMPILATION_CACHE_DIR wins: JAX reads it itself and no other
    directory is set in code. Without it the cache goes to a FIXED
    directory, `<repo>/.jax_cache` — the path is part of every entry's
    key, so a temporary name never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_facts(backend, directory: str) -> dict:
    """Where the backend's table lives, read off the table array itself
    (not off jax.devices(): a table that was meant to be sharded and sits
    whole on the first device shows here). `directory` names the key
    directory in use: native | python | device."""
    state = backend.state
    shards = state.addressable_shards
    first = shards[0].device
    return {
        "platform": first.platform,
        "device_kind": first.device_kind,
        "device_count": len({s.device.id for s in shards}),
        "visible_device_count": jax.local_device_count(),
        "devices": [str(s.device) for s in shards],
        "table_bytes_per_device": [int(s.data.nbytes) for s in shards],
        # as stored: "u32[C,16]" — a row's 64-bit fields as word pairs,
        # because the chip has no 64-bit integers (ops/decide.py)
        "table_layout": "%s%d[C,%d]" % (
            state.dtype.kind, 8 * state.dtype.itemsize, state.shape[-1]),
        "donation": bool(backend.donate),
        "key_directory": directory,
    }


_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def device_memory(backend) -> list:
    """The allocator's own account of each device the table lives on
    (`device.memory_stats()`): bytes in use, their peak since the process
    started, and the limit. A key the backend does not report is None; the
    CPU backend reports none of them. The devices are found by the names
    `device_facts` recorded at boot, not through `backend.state`: while a
    window is being dispatched that is the donated, deleted table."""
    names = set(backend.device["devices"])
    out = []
    for device in jax.local_devices():
        if str(device) in names:
            stats = device.memory_stats() or {}
            out.append({"device": str(device),
                        **{k: stats.get(k) for k in _MEMORY_KEYS}})
    return out


class CompileWatch:
    """Counts the XLA backend compiles from its creation on (the daemon
    creates it at `Ready`): a shape that serving has not warmed compiles
    inside a request and reads as one slow call, so each one also lands in
    the flight recorder as `profile.compile` with the program's name. (A
    program loaded from the persistent cache counts too: it was not
    compiled before `Ready` either.)

    JAX calls the listener on the compiling thread, under whatever lock
    that thread holds (the engine's, when a window compiles), so it only
    appends to a deque; `facts()` — every read of /v1/debug/vars, every
    bundle — counts them and hands the new ones to the recorder, each with
    the seconds since it happened."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, recorder=None):
        self._recorder = recorder
        self._seen = collections.deque()  # (monotonic, seconds, program)
        self._published = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self._seen.append((time.monotonic(), duration,
                               str(kwargs.get("fun_name", ""))))

    def facts(self) -> dict:
        seen = list(self._seen)
        # two readers at once can publish an event twice; they cannot
        # miscount (the count is the deque's length)
        if self._recorder is not None:
            new, self._published = seen[self._published:], len(seen)
            for at, seconds, program in new:
                self._recorder.emit(
                    "profile.compile", program=program,
                    seconds=round(seconds, 3),
                    ago_s=round(time.monotonic() - at, 3))
        return {"count": len(seen),
                "seconds": round(sum(s for _, s, _ in seen), 3)}

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def release_compile_memory() -> None:
    """Hand the allocator's freed pages back to the OS (glibc
    `malloc_trim`; nothing to do on a libc without it).

    The TPU compiler frees hundreds of MB of scratch per program, and
    glibc keeps them in its arenas: over the ~50 table-sized programs of a
    cold warmup at 10M rows the daemon's resident set reached the 40 GiB
    of a one-chip host and the kernel killed it (PR 22, first chip run).
    The warmups call this after every program they compile."""
    trim = getattr(ctypes.CDLL(None), "malloc_trim", None)
    if trim is not None:
        trim(0)
