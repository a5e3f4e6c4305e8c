"""Random row-access ceiling microbenchmark (VERDICT r3 item 3).

Isolates the decide kernel's memory access pattern — gather B random
i64[8] rows from a C-row table, scatter B rows back — WITHOUT the decide
math, to measure how far the kernel sits from the chip's random-access
ceiling. Variants:

  gather+scatter   the kernel's exact access pattern (touch both ways)
  gather_only      read side alone
  scatter_only     write side alone
  sorted           slots sorted ON DEVICE before the gather/scatter
                   (locality probe: does HBM row locality buy anything?)
  decide           the real kernel (ops/decide.py) for comparison

All completion-forced (data-dependent fetch), scan-coalesced K-deep,
donated state. Prints one JSON line per variant.
"""

from __future__ import annotations

import json
import time

import numpy as np

TABLE_CAPACITY = 10_000_000
BATCH = 8_192
SCAN_K = 128
N_VARIANTS = 4
TARGET_S = 3.0


def main() -> None:
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.ops.decide import I64, decide_scan_packed, make_table
    from gubernator_tpu.utils.platform import donation_supported

    donate = donation_supported()
    dargs = dict(donate_argnums=(0,)) if donate else {}

    def force(x) -> int:
        return int(np.asarray(x[(0,) * x.ndim]))

    rng = np.random.RandomState(3)
    slot_sets = [
        jnp.asarray(np.stack([
            rng.choice(TABLE_CAPACITY, BATCH, replace=False)
            for _ in range(SCAN_K)]).astype(np.int32))
        for _ in range(N_VARIANTS)
    ]

    # ---- raw gather+scatter: the kernel's access pattern, no math ------
    def gs_scan(state, slots_k, bump):
        def body(st, slots):
            rows = st[slots]                      # [B, 8] random gather
            st2 = st.at[slots].set(rows + bump)   # [B, 8] random scatter
            return st2, rows[:, 0]
        return jax.lax.scan(body, state, slots_k)

    def g_scan(state, slots_k, bump):
        def body(st, slots):
            rows = st[slots]
            return st, rows[:, 0] + bump
        return jax.lax.scan(body, state, slots_k)

    def s_scan(state, slots_k, bump):
        def body(st, slots):
            st2 = st.at[slots].set(
                jnp.full((slots.shape[0], 8), bump, I64))
            return st2, slots[:1].astype(I64)
        return jax.lax.scan(body, state, slots_k)

    def sorted_scan(state, slots_k, bump):
        def body(st, slots):
            order = jnp.argsort(slots)
            s_sorted = slots[order]
            rows = st[s_sorted]
            st2 = st.at[s_sorted].set(rows + bump)
            # un-sort the per-lane result (the serving contract)
            out = jnp.zeros_like(rows[:, 0]).at[order].set(rows[:, 0])
            return st2, out
        return jax.lax.scan(body, state, slots_k)

    variants = {
        "gather_scatter": gs_scan,
        "gather_only": g_scan,
        "scatter_only": s_scan,
        "sorted_gather_scatter": sorted_scan,
    }
    results = {}
    for name, fn in variants.items():
        step = jax.jit(fn, **dargs)
        state = make_table(TABLE_CAPACITY)
        state, out = step(state, slot_sets[0], 1)
        force(out)
        t0 = time.perf_counter()
        state, out = step(state, slot_sets[1], 2)
        force(out)
        per_call = max(time.perf_counter() - t0, 1e-6)
        iters = max(4, min(200, int(TARGET_S / per_call)))
        t0 = time.perf_counter()
        for i in range(iters):
            state, out = step(state, slot_sets[i % N_VARIANTS], 3 + i)
        force(out)
        el = time.perf_counter() - t0
        rate = iters * SCAN_K * BATCH / el
        results[name] = round(rate, 1)
        print(json.dumps({"variant": name, "rows_per_s": round(rate, 1),
                          "iters": iters}), flush=True)
        del state

    # ---- the real kernel for comparison --------------------------------
    def make_windows(seed: int) -> np.ndarray:
        r = np.random.RandomState(seed)
        p = np.zeros((SCAN_K, 9, BATCH), np.int64)
        for i in range(SCAN_K):
            p[i, 0] = r.choice(TABLE_CAPACITY, BATCH, replace=False)
            p[i, 1] = 1
            p[i, 2] = 1000
            p[i, 3] = 60_000
        return p
    scans = [jnp.asarray(make_windows(s)) for s in range(N_VARIANTS)]
    step = jax.jit(decide_scan_packed, **dargs)
    state = make_table(TABLE_CAPACITY)
    state, out = step(state, scans[0], 1)
    force(out)
    t0 = time.perf_counter()
    state, out = step(state, scans[1], 2)
    force(out)
    per_call = max(time.perf_counter() - t0, 1e-6)
    iters = max(4, min(200, int(TARGET_S / per_call)))
    t0 = time.perf_counter()
    for i in range(iters):
        state, out = step(state, scans[i % N_VARIANTS], 3 + i)
    force(out)
    rate = iters * SCAN_K * BATCH / (time.perf_counter() - t0)
    results["decide_kernel"] = round(rate, 1)
    print(json.dumps({"variant": "decide_kernel",
                      "rows_per_s": round(rate, 1), "iters": iters}),
          flush=True)
    print(json.dumps({"summary": results,
                      "device": str(jax.devices()[0]),
                      "capacity": TABLE_CAPACITY,
                      "batch": BATCH, "scan_k": SCAN_K}), flush=True)


if __name__ == "__main__":
    main()
