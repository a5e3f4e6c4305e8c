"""One scan group on the chip: the table as the scan's carry against the
rows (ops/decide.py decide_scan_packed* / decide_scan_carried*), the program
alone at the README's table size. PERF.md PR 39 has its readings.

    chiprun --chips 1 --timeout 1500 -- python scripts/scan_group_microbench.py

Each line: the program, the first call (trace + compile, compile cache off),
a call as the engine makes it (host arrays in, the answers fetched) and the
program alone (device-resident arguments, launches chained), in ms. Every
pair must read `same: true` (answers and the touched rows bit-identical).

Then (alone with `--staged-only`) the 32 x 2048 compact group as
Engine._apply_windows_scanned retires it, the host's parts and the chip's
apart: the stack zeroed (`alloc_ms`), lean_window's refusal and
compact_window (`stage_ms`), the jitted call and the copy back
(`call_fetch_ms`), the answers widened (`widen_ms`) — once walking the
launched width (`whole`: PERF.md PR 39's 2.27 ms row plus its host work),
once the group's 58 live lanes (`prefix`). Both forms must hand the
program the same bytes (`staged_same: true`).
Exits non-zero off the chip: a CPU time is not a device time."""

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gubernator_tpu.ops.decide  # noqa: F401  (the package re-exports the function)

D = sys.modules["gubernator_tpu.ops.decide"]
C = 10_000_000
NOW = 1_700_000_000_000
REPS = 30


def group(depth, width, seed, hits_one=False):
    """A nested group as a repeated key's tail: 58 keys in round 0, one of
    them in every round, each at one lane."""
    rng = np.random.default_rng(seed)
    n0 = min(58, width)
    slots = rng.choice(C, size=n0, replace=False)
    stay = np.sort(np.minimum(rng.zipf(1.6, n0), depth))[::-1].copy()
    stay[0] = depth
    limit = rng.choice([10, 100, 1000, 100000], n0)
    stack = np.zeros((depth, 9, width), np.int64)
    stack[:, 0, :] = -1
    for k in range(depth):
        n = int((stay > k).sum())
        stack[k, 0, :n] = slots[:n]
        stack[k, 1, :n] = 1 if hits_one else rng.integers(1, 4, n)
        stack[k, 2, :n] = limit[:n]
        stack[k, 3, :n] = 3_600_000
        stack[k, 4, :n] = slots[:n] % 2
    return stack, slots


def run(name, fn, args):
    t = time.perf_counter()
    step = jax.jit(fn, donate_argnums=(0,))
    state = D.make_table(C) + jnp.uint32(0)
    state, out = step(state, *args, NOW)
    first = np.asarray(out)
    first_call_s = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(REPS):
        state, out = step(state, *args, NOW)
        np.asarray(out)
    as_the_engine_ms = (time.perf_counter() - t) / REPS * 1e3
    on_device = [jax.device_put(a) for a in args]
    jax.block_until_ready(state)
    t = time.perf_counter()
    for _ in range(REPS):
        state, out = step(state, *on_device, NOW)
    jax.block_until_ready(out)
    alone_ms = (time.perf_counter() - t) / REPS * 1e3
    print(json.dumps({"program": name, "first_call_s": round(first_call_s, 2),
                      "as_the_engine_ms": round(as_the_engine_ms, 3),
                      "alone_ms": round(alone_ms, 3)}), flush=True)
    return first, state


def staged_group(depth, width):
    """The carried compact group as the engine stages, launches, fetches
    and widens it, by the launched width and by the live prefix."""
    stack, _slots = group(depth, width, 7)
    live = int((stack[0, 0] >= 0).sum())  # round 0 holds every key
    step = jax.jit(D.decide_scan_carried_compact, donate_argnums=(0,))
    state = D.make_table(C) + jnp.uint32(0)
    staged = {}
    for form, lanes, launched in (("whole", width, None),
                                  ("prefix", live, width)):
        parts = dict.fromkeys(
            ("alloc_ms", "stage_ms", "call_fetch_ms", "widen_ms"), 0.0)
        for rep in range(REPS + 1):  # the first call compiles: not counted
            t0 = time.perf_counter()
            src = np.zeros((depth, 9, lanes), np.int64)
            src[:, 0, :] = -1
            t1 = time.perf_counter()
            src[...] = stack[..., :lanes]  # pack_window's part: not timed
            t2 = time.perf_counter()
            # hits 1..3: the lean wire refuses, as in hot10m.repeats1000
            assert D.lean_window(src, C, launched) is None
            compact = D.compact_window(src, launched)
            t3 = time.perf_counter()
            state, out = step(state, compact, NOW)
            np.asarray(out)  # the wait and the copy; the array keeps it
            t4 = time.perf_counter()
            wide = D.widen_compact_out(out, NOW,
                                       None if launched is None else live)
            t5 = time.perf_counter()
            assert wide.shape == (depth, 4, lanes)
            if rep:
                for key, dt in (("alloc_ms", t1 - t0), ("stage_ms", t3 - t2),
                                ("call_fetch_ms", t4 - t3),
                                ("widen_ms", t5 - t4)):
                    parts[key] += dt / REPS * 1e3
        staged[form] = compact
        parts = {k: round(v, 3) for k, v in parts.items()}
        print(json.dumps({
            "group": f"rows  compact K{depth} W{width} live{live}",
            "form": form, **parts,
            "total_ms": round(sum(parts.values()), 3)}), flush=True)
    same = bool((staged["whole"] == staged["prefix"]).all())
    print(json.dumps({"staged_same": same}), flush=True)
    return same


def main():
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform,
                      "kind": device.device_kind}), flush=True)
    if device.platform != "tpu":
        return 2
    jax.config.update("jax_enable_compilation_cache", False)
    if "--staged-only" in sys.argv[1:]:
        return 0 if staged_group(32, 2048) else 1
    stagings = {
        "compact": (D.decide_scan_packed_compact,
                    D.decide_scan_carried_compact,
                    lambda stack: (D.compact_window(stack),), False),
        "lean": (D.decide_scan_packed_lean, D.decide_scan_carried_lean,
                 lambda stack: D.lean_window(stack, C), True),
        "wide": (D.decide_scan_packed, D.decide_scan_carried,
                 lambda stack: (stack,), False),
    }
    ok = True
    for depth, width, names in [(32, 2048, ("compact", "lean", "wide")),
                                (16, 2048, ("compact",)),
                                (32, 8192, ("compact",)),
                                (32, 64, ("compact",))]:
        for name in names:
            tabled, carried, stage, hits_one = stagings[name]
            stack, slots = group(depth, width, 7, hits_one)
            args = stage(stack)
            shape = f"{name} K{depth} W{width}"
            out_t, st_t = run("table " + shape, tabled, args)
            out_c, st_c = run("rows  " + shape, carried, args)
            same = bool((out_t == out_c).all()) and bool(
                (D.fetch_rows(st_t, slots) == D.fetch_rows(st_c, slots)).all())
            print(json.dumps({"same": same}), flush=True)
            ok = ok and same
            del st_t, st_c
    ok = staged_group(32, 2048) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
