"""The batched rate-limit decision kernel.

This is the TPU-native replacement for the reference's per-key bucket state
machines (reference: algorithms.go:24-336). Where the reference walks one
request at a time through branchy Go code under a global cache mutex
(reference: gubernator.go:327-347), here the whole batch window is a single
branchless masked tensor program:

    gather state rows -> compute token & leaky paths as mask lattices
                      -> select -> scatter rows back

State is ONE row-major u32[C, 16] array in HBM — 64 bytes per key slot,
~640 MB at 10M keys — resident on one chip, shardable across a mesh (parallel/).
Row-major matters enormously on TPU: XLA executes random-index gather/scatter
roughly element-at-a-time, so a struct-of-arrays layout (seven separate
columns) costs 14 serialized random HBM touches per decision and capped the
chip at ~1M decisions/s; one 64-byte row gather + one row scatter per
decision runs the same workload ~5.6x faster (measured on v5e — see
DESIGN.md "Row-major state").

The row is eight 64-bit fields STORED AS THE CHIP HOLDS THEM: the TPU has no
64-bit integers, and for an s64[C, 8] parameter its compiler splits the whole
table into two u32 halves, scatters into those and recombines them — three
table-sized passes and a table-sized temp per launch, whatever the launch
decides (86% of device time at 10M rows, PERF.md PR 29). So words 2f and 2f+1
of a row are the low and high half of field f (little-endian: the host's
i64[n, 8].view("<u4") IS the device row), 64-bit values exist only on the
gathered [B, 8] lanes, and a launch touches its own rows. The layout has one
owner — make_table / load_rows / store_rows / load_column / host_rows below —
and nothing else indexes the array.

Semantics are bit-exact with the reference's integer math (the reference's
leaky bucket is already integer: ``rate = duration/limit`` and
``leak = elapsed/rate`` are int64 divisions, algorithms.go:214,235), with a
small set of deliberate bug-fix deviations documented in PARITY.md and
mirrored by the oracle (ops/oracle.py) used to test this kernel.

Batch-internal duplicate keys: the reference serializes all requests under a
mutex, so two hits to one key in a window observe each other. A scatter with
duplicate indices cannot express the OVER_LIMIT-doesn't-deduct rule
(algorithms.go:125-129), so the engine (models/engine.py) splits a window
into collision-free *rounds* — occurrence k of every key goes to round k.
Almost all real windows are round-1-only.
"""

from __future__ import annotations

import threading
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.obs import witness
from gubernator_tpu.types import Algorithm, Behavior, Status


class KernelTelemetry:
    """Process-wide kernel dispatch accounting + cost introspection.

    The engines report every device launch here — which kernel (wide /
    compact / lean, per-window / scan), at which width, at which scan
    depth — so an operator can see the compiled-program mix actually
    serving traffic (each distinct shape is one XLA program; an unexpected
    width churn here means warmup() and live traffic disagree). Totals are
    process-wide: in-process cluster harnesses share one registry, exactly
    like the shared jit caches they mirror. Exported in /v1/debug/vars
    ("kernel") and as engine_kernel_dispatch_total{kernel,width}.

    The profiling plane (obs/profile.py) extends each (kernel, width)
    with a live dispatch-time histogram (`dur_ns` on note) and a lazily
    computed XLA cost record — flops, bytes accessed, HLO fingerprint —
    from the abstract shapes of the first real dispatch (`offer_probe`;
    the costs compile OFF the serving path, on first /v1/debug/kernels
    access)."""

    def __init__(self):
        self._lock = witness.make_lock("kernel.telemetry")
        self._counts: Dict[Tuple[str, int], int] = {}
        self._hists: Dict[Tuple[str, int], "object"] = {}
        self._probes: Dict[Tuple[str, int], tuple] = {}
        self._costs: Dict[Tuple[str, int], dict] = {}

    def note(self, kernel: str, width: int, depth: int = 1,
             dur_ns: int = 0) -> None:
        """One dispatch of `kernel` at staging width `width` retiring
        `depth` windows (scan kernels); `dur_ns`, when nonzero, is the
        dispatch-call wall time (the profiler's `launch` sub-phase is fed
        from the same pair of clock reads)."""
        key = (kernel, width)
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + depth
            hist = self._hists.get(key) if dur_ns else None
            if dur_ns and hist is None:
                from gubernator_tpu.obs.profile import PhaseHist

                hist = self._hists.setdefault(key, PhaseHist())
        if dur_ns and hist is not None:
            hist.observe(dur_ns)

    def needs_probe(self, kernel: str, width: int) -> bool:
        """True until a cost probe is parked for (kernel, width) — a
        single dict test, cheap enough for the dispatch hot path."""
        return (kernel, width) not in self._probes

    def offer_probe(self, kernel: str, width: int, fn, args) -> None:
        """Park the abstract call shape of (kernel, width)'s first real
        dispatch: `fn` is the jitted callable, `args` its concrete
        arguments (captured BEFORE the call — donation invalidates them
        after). Cost analysis lowers/compiles from these avals later,
        off the serving path."""
        avals = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") and hasattr(a, "dtype") else a
            for a in args)
        with self._lock:
            self._probes.setdefault((kernel, width), (fn, avals))

    def _compute_cost(self, fn, avals) -> dict:
        """Lower + compile one probe and extract the cost record. Any
        failure (backend without cost analysis, shape drift) degrades to
        an error record — introspection must not break the endpoint."""
        from gubernator_tpu.obs.profile import hlo_fingerprint

        out: dict = {}
        try:
            lowered = fn.lower(*avals)
            out["fingerprint"] = hlo_fingerprint(lowered.as_text())
        except Exception as e:  # noqa: BLE001 — degrade, don't break
            return {"error": f"lower: {e}"}
        try:
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                out["flops"] = float(ca.get("flops", 0.0))
                out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        except Exception as e:  # noqa: BLE001 — degrade, don't break
            out["cost_error"] = str(e)
        return out

    def kernel_costs(self) -> Dict[Tuple[str, int], dict]:
        """Cost records for every probed (kernel, width), computing and
        caching any not yet analyzed (first call after new shapes pays
        the compiles; callers are debug endpoints, never serving)."""
        with self._lock:
            pending = {k: v for k, v in self._probes.items()
                       if k not in self._costs}
        for key, (fn, avals) in pending.items():
            cost = self._compute_cost(fn, avals)
            with self._lock:
                self._costs[key] = cost
        with self._lock:
            return dict(self._costs)

    def kernels_body(self) -> dict:
        """The schema-pinned /v1/debug/kernels body
        (tests/test_debug_schema.py)."""
        from gubernator_tpu.obs.profile import KERNELS_SCHEMA_VERSION

        costs = self.kernel_costs()
        with self._lock:
            counts = dict(self._counts)
            hists = dict(self._hists)
        kernels = {}
        for (k, w), n in sorted(counts.items()):
            hist = hists.get((k, w))
            kernels[f"{k}@{w}"] = {
                "windows": n,
                "dispatch_ns": hist.snapshot() if hist is not None else None,
                "cost": costs.get((k, w)),
            }
        return {
            "schema_version": KERNELS_SCHEMA_VERSION,
            "kernels": kernels,
        }

    def fingerprints(self) -> Dict[str, str]:
        """{kernel@width: HLO fingerprint} for every analyzed probe."""
        return {f"{k}@{w}": c["fingerprint"]
                for (k, w), c in self.kernel_costs().items()
                if "fingerprint" in c}

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "windows": {f"{k}@{w}": n
                            for (k, w), n in sorted(self._counts.items())},
            }

    def counts(self) -> Dict[Tuple[str, int], int]:
        with self._lock:
            return dict(self._counts)

    def dispatch_totals(self) -> Dict[Tuple[str, int], Tuple[int, int]]:
        """{(kernel, width): (dispatches, total_ns)} — the cheap scrape
        read behind engine_kernel_dispatch_seconds (no quantile math)."""
        with self._lock:
            hists = dict(self._hists)
        return {key: hist.totals() for key, hist in hists.items()}


kernel_telemetry = KernelTelemetry()

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32

# State-column algorithm codes: table slots hold -1 when vacant.
_VACANT = -1


# Field indices of one bucket row: TABLE_ROW_FIELDS 64-bit fields, held on
# the device as TABLE_ROW_WORDS u32 words (see load_rows / store_rows).
# `stamp` is the token bucket's CreatedAt and the leaky bucket's UpdatedAt
# (the reference keeps them in two different structs, store.go:11-24);
# `status` persists the token bucket's sticky OVER_LIMIT
# (algorithms.go:113-115); the 8th field pads the row to 64 bytes so one
# slot is one aligned DMA burst.
ROW_ALGO = 0  # -1 vacant, 0 token, 1 leaky
ROW_LIMIT = 1
ROW_REMAINING = 2
ROW_DURATION = 3  # ms
ROW_STAMP = 4  # unix ms
ROW_EXPIRE = 5  # unix ms (doubles as token ResetTime)
ROW_STATUS = 6
ROW_HITS = 7  # lifetime attempted hits (set in decide(); never in a snapshot)
TABLE_ROW_FIELDS = 8
TABLE_ROW_WORDS = 2 * TABLE_ROW_FIELDS

# The device table type: plain jax.Array u32[..., C, TABLE_ROW_WORDS].
TableState = jax.Array


class ReqBatch(NamedTuple):
    """One device-ready batch window of requests.

    `slot` is the table row the host key-directory assigned; -1 marks padding
    lanes (dropped on scatter). `fresh` is True when the directory newly
    assigned (or recycled) the slot, so whatever the row holds is garbage.
    `greg_expire`/`greg_interval` are host-precomputed calendar values, only
    read when the DURATION_IS_GREGORIAN bit is set.
    """

    slot: jax.Array  # i32[B]
    hits: jax.Array  # i64[B]
    limit: jax.Array  # i64[B]
    duration: jax.Array  # i64[B]
    algorithm: jax.Array  # i32[B]
    behavior: jax.Array  # i32[B]
    greg_expire: jax.Array  # i64[B]
    greg_interval: jax.Array  # i64[B]
    fresh: jax.Array  # bool[B]


class RespBatch(NamedTuple):
    status: jax.Array  # i32[B]
    limit: jax.Array  # i64[B]
    remaining: jax.Array  # i64[B]
    reset_time: jax.Array  # i64[B]


def vacant_rows(shape: Tuple[int, ...]) -> TableState:
    """u32[*shape, TABLE_ROW_WORDS] of vacant rows (algo = -1, the rest 0):
    ONE broadcast of a 64-byte row, so building a table never holds a second
    table-sized buffer (zeros().at[].set() would, and the allocator's peak
    since boot is what hbm_peak_mb reads)."""
    row = np.zeros(TABLE_ROW_FIELDS, "<i8")
    row[ROW_ALGO] = _VACANT
    return jnp.broadcast_to(jnp.asarray(row.view("<u4")),
                            tuple(shape) + (TABLE_ROW_WORDS,))


def make_table(capacity: int) -> TableState:
    """Fresh vacant table: u32[capacity, 16] rows with algo = -1."""
    return vacant_rows((capacity,))


def _combine(words: jax.Array) -> jax.Array:
    """u32[..., 2n] words -> i64[..., n]: word 2f is the low half."""
    lo = words[..., 0::2].astype(I64)
    hi = words[..., 1::2].astype(I64)
    return lo | (hi << 32)


def load_rows(state: TableState, idx: jax.Array) -> jax.Array:
    """Rows `idx` of the table as i64[..., 8]: ONE 64-byte row gather per
    lane, the 64-bit fields rebuilt on the lanes only."""
    return _combine(state[idx])


def store_rows(state: TableState, slot: jax.Array,
               rows: jax.Array) -> TableState:
    """Write i64[B, 8] `rows` at `slot` — ONE row scatter, split into words
    on the lanes. Negative (padding) slots are dropped (pad_to_drop)."""
    lo = (rows & 0xFFFFFFFF).astype(U32)
    hi = ((rows >> 32) & 0xFFFFFFFF).astype(U32)
    words = jnp.stack([lo, hi], axis=-1).reshape(
        rows.shape[:-1] + (TABLE_ROW_WORDS,))
    return state.at[pad_to_drop(slot, state.shape[-2])].set(
        words, mode="drop")


def load_column(state: TableState, field: int) -> jax.Array:
    """Field `field` of every row, i64[..., C] — for the few whole-table
    readers (the cartographer's hit column, the device directory's
    refresh); two strided reads, no row is rebuilt."""
    return _combine(state[..., 2 * field:2 * field + 2])[..., 0]


def host_rows(words) -> np.ndarray:
    """Fetched table words u32[..., 16] -> host i64[..., 8], no arithmetic:
    the little-endian word order is the contract."""
    return np.ascontiguousarray(np.asarray(words)).view("<i8")


def fetch_column(state: TableState, field: int) -> np.ndarray:
    """Field `field` of every row, to the host, i64[..., C]: its two word
    columns are fetched as one u32[..., C, 2] and viewed there — no 64-bit
    work on the chip, and no more host memory than the column itself."""
    return host_rows(state[..., 2 * field:2 * field + 2])[..., 0]


def host_words(rows) -> np.ndarray:
    """Host i64[..., 8] rows -> the u32[..., 16] words the device holds
    (the inverse view of host_rows)."""
    return np.ascontiguousarray(rows, "<i8").view("<u4")


def fetch_rows(state: TableState, slots) -> np.ndarray:
    """Point-read rows `slots` to the host as i64[n, 8] (debug and settle
    reads: an eager gather, the words viewed on the host)."""
    return host_rows(state[jnp.asarray(slots, I32)])


def _sel(default: jax.Array, *pairs) -> jax.Array:
    """Chained masked select; later pairs win over earlier ones."""
    out = default
    for mask, val in pairs:
        out = jnp.where(mask, val, out)
    return out


def pad_to_drop(slot: jax.Array, capacity: int) -> jax.Array:
    """Remap -1 padding lanes PAST capacity so scatter mode="drop" discards
    them: drop only drops out-of-range-high indices — negatives wrap
    NumPy-style, so a raw -1 lane would scatter into the LAST slot and
    clobber whatever bucket lives there once the table fills. Every scatter
    of host-routed slots must go through this."""
    return jnp.where(slot < 0, capacity, slot)


def decide(state: TableState, reqs: ReqBatch, now_ms: jax.Array) -> Tuple[TableState, RespBatch]:
    """Apply one collision-free batch of requests to the table.

    Pure function: returns the updated table and per-request responses.
    All requests in the batch must target distinct slots (engine guarantees
    via rounds); padding lanes carry slot == -1.
    """
    # ONE 64-byte row gather per lane (the layout that keeps TPU
    # gather/scatter off the serialized random-element path); padding
    # lanes gather row 0 and are dropped on the way back
    rows = load_rows(state, jnp.maximum(reqs.slot, 0))  # i64[B, 8]
    new_rows, resp = decide_rows(rows, reqs, now_ms)
    # ONE row scatter back (the -1 pad lanes are dropped)
    return store_rows(state, reqs.slot, new_rows), resp


def decide_rows(rows: jax.Array, reqs: ReqBatch,
                now_ms: jax.Array) -> Tuple[jax.Array, RespBatch]:
    """Everything of decide() between the gather and the scatter: the
    lanes' rows i64[B, 8] and their requests -> the rows as the requests
    leave them, and the responses. It reads no table, so a row may come
    from the table (decide) or from the lane's previous round (the carried
    scans below). A padding lane (slot == -1) gets its row back unchanged
    and an all-zero response.
    """
    now = jnp.asarray(now_ms, I64)
    active = reqs.slot >= 0
    st_algo = rows[:, ROW_ALGO]
    st_limit = rows[:, ROW_LIMIT]
    st_rem = rows[:, ROW_REMAINING]
    st_dur = rows[:, ROW_DURATION]
    st_stamp = rows[:, ROW_STAMP]
    st_exp = rows[:, ROW_EXPIRE]
    st_status = rows[:, ROW_STATUS]

    r_hits = reqs.hits
    r_limit = reqs.limit
    r_dur = reqs.duration
    is_tok = reqs.algorithm == Algorithm.TOKEN_BUCKET
    greg = (reqs.behavior & Behavior.DURATION_IS_GREGORIAN) != 0
    reset_rem = (reqs.behavior & Behavior.RESET_REMAINING) != 0
    peek = r_hits == 0

    OVER = jnp.asarray(Status.OVER_LIMIT, I32)
    UNDER = jnp.asarray(Status.UNDER_LIMIT, I32)

    # A slot is a hit only if occupied, unexpired (expiry-on-read,
    # cache.go:140-165) and running the same algorithm (an algorithm switch
    # recreates the bucket, algorithms.go:54-62,195-203).
    occupied = active & (~reqs.fresh) & (st_algo >= 0)
    alive = occupied & (now <= st_exp) & (st_algo == reqs.algorithm)

    # ---------------- token bucket, existing row (algorithms.go:35-134) ----
    tok_reset = alive & is_tok & reset_rem  # expire the bucket entirely
    lim_changed = st_limit != r_limit
    t_rem0 = jnp.where(lim_changed, jnp.minimum(st_rem, r_limit), st_rem)
    dur_changed = st_dur != r_dur
    t_new_exp = jnp.where(greg, reqs.greg_expire, st_stamp + r_dur)
    # a duration change that lands the bucket in the past recreates it
    # (algorithms.go:95-101)
    tok_recreate = alive & is_tok & ~reset_rem & dur_changed & (t_new_exp < now)
    tok_exists = alive & is_tok & ~reset_rem & ~tok_recreate
    te_exp = jnp.where(dur_changed, t_new_exp, st_exp)
    t_rem_zero = t_rem0 == 0
    t_over_req = r_hits > t_rem0  # reject without deducting (algorithms.go:125-129)
    t_deduct = (~peek) & (~t_rem_zero) & (~t_over_req)
    te_rem = jnp.where(t_deduct, t_rem0 - r_hits, t_rem0)
    te_status_resp = jnp.where((~peek) & (t_rem_zero | t_over_req), OVER, st_status)
    # only draining to zero persists OVER on the row (algorithms.go:112-115)
    te_status_store = jnp.where((~peek) & t_rem_zero, OVER, st_status)

    # ---------------- token bucket, vacant/recreate (algorithms.go:136-178) -
    tok_miss = active & is_tok & (~alive | tok_recreate)
    m_exp = jnp.where(greg, reqs.greg_expire, now + r_dur)
    m_over = r_hits > r_limit
    # first request over the limit: reject but store an *undrained* bucket
    # (algorithms.go:160-165)
    m_rem = jnp.where(m_over, r_limit, r_limit - r_hits)

    # ---------------- leaky bucket, existing row (algorithms.go:194-289) ----
    leak_exists = alive & ~is_tok
    l_rem0 = jnp.where(reset_rem, r_limit, st_rem)
    l_dur = jnp.where(greg, reqs.greg_expire - now, r_dur)
    l_rate = jnp.maximum(
        jnp.where(greg, reqs.greg_interval, r_dur) // jnp.maximum(r_limit, 1), 1
    )
    elapsed = jnp.maximum(now - st_stamp, 0)
    l_rem1 = jnp.minimum(r_limit, l_rem0 + elapsed // l_rate)
    l_rem_zero = l_rem1 == 0
    l_over_req = r_hits > l_rem1
    l_deduct = (~peek) & (~l_rem_zero) & (~l_over_req)
    le_rem = jnp.where(l_deduct, l_rem1 - r_hits, l_rem1)
    # an empty bucket rejects *without* consuming the leak residue
    # (UpdatedAt held back, algorithms.go:255-264)
    le_stamp = jnp.where((~l_rem_zero) & (~peek), now, st_stamp)
    le_status = jnp.where(l_rem_zero | ((~peek) & l_over_req), OVER, UNDER)
    le_exp = jnp.where(l_deduct, now + l_dur, st_exp)

    # ---------------- leaky bucket, vacant (algorithms.go:291-336) ----------
    leak_miss = active & (~is_tok) & ~alive
    lm_dur = jnp.where(greg, reqs.greg_expire - now, r_dur)
    lm_rate = jnp.maximum(lm_dur // jnp.maximum(r_limit, 1), 1)
    lm_over = r_hits > r_limit
    lm_rem = jnp.where(lm_over, jnp.zeros_like(r_limit), r_limit - r_hits)

    # ---------------- select new state ------------------------------------
    n_algo = _sel(
        st_algo,
        (tok_exists | tok_miss, jnp.asarray(Algorithm.TOKEN_BUCKET, I32)),
        (leak_exists | leak_miss, jnp.asarray(Algorithm.LEAKY_BUCKET, I32)),
        (tok_reset, jnp.asarray(_VACANT, I32)),
    )
    touched = tok_exists | tok_miss | leak_exists | leak_miss
    n_limit = jnp.where(touched, r_limit, st_limit)
    n_rem = _sel(
        st_rem,
        (tok_exists, te_rem),
        (tok_miss, m_rem),
        (leak_exists, le_rem),
        (leak_miss, lm_rem),
    )
    n_dur = _sel(
        st_dur,
        (tok_exists | tok_miss, r_dur),
        (leak_exists, l_dur),
        (leak_miss, lm_dur),
    )
    n_stamp = _sel(
        st_stamp,
        (tok_miss | leak_miss, now),
        (leak_exists, le_stamp),
    )
    n_exp = _sel(
        st_exp,
        (tok_exists, te_exp),
        (tok_miss, m_exp),
        (leak_exists, le_exp),
        (leak_miss, now + lm_dur),
    )
    n_status = _sel(
        st_status,
        (tok_exists, te_status_store),
        (tok_miss | leak_miss, UNDER),
    )

    new_rows = jnp.stack(
        [
            n_algo.astype(I64),
            n_limit,
            n_rem,
            n_dur,
            n_stamp,
            n_exp,
            n_status.astype(I64),
            # field 7: per-key lifetime attempt counter — every round adds
            # its requested hits (admitted or rejected), giving the lease
            # tier a device-resident hit count with zero extra dispatches
            # (service/leases.py). Responses and snapshots never read it,
            # so decision outputs are bit-identical with leases off.
            rows[:, ROW_HITS] + jnp.where(active, r_hits, 0),
        ],
        axis=1,
    )

    # ---------------- select response --------------------------------------
    z64 = jnp.zeros_like(r_limit)
    resp = RespBatch(
        status=_sel(
            jnp.zeros_like(st_status),
            (tok_exists, te_status_resp),
            (tok_miss, jnp.where(m_over, OVER, UNDER)),
            (leak_exists, le_status),
            (leak_miss, jnp.where(lm_over, OVER, UNDER)),
            (tok_reset, UNDER),
        ).astype(I32),
        limit=jnp.where(active, r_limit, z64),
        remaining=_sel(
            z64,
            (tok_exists, te_rem),
            (tok_miss, m_rem),
            (leak_exists, le_rem),
            (leak_miss, lm_rem),
            (tok_reset, r_limit),
        ),
        reset_time=_sel(
            z64,
            (tok_exists, te_exp),
            (tok_miss, m_exp),
            (leak_exists, now + l_rate),
            (leak_miss, now + lm_rate),
            (tok_reset, z64),
        ),
    )
    return new_rows, resp


def _wide_reqs(packed: jax.Array) -> ReqBatch:
    """The ReqBatch of one wide i64[9, B] staging window (pack_window's
    row order)."""
    return ReqBatch(
        slot=packed[0].astype(I32),
        hits=packed[1],
        limit=packed[2],
        duration=packed[3],
        algorithm=packed[4].astype(I32),
        behavior=packed[5].astype(I32),
        greg_expire=packed[6],
        greg_interval=packed[7],
        fresh=packed[8] != 0,
    )


def _wide_response(resp: RespBatch) -> jax.Array:
    return jnp.stack(
        [resp.status.astype(I64), resp.limit, resp.remaining, resp.reset_time]
    )


def decide_packed(
    state: TableState, packed: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide() over a single staging buffer.

    `packed` is i64[9, B] — one host→device transfer per window instead of
    nine column uploads; the response comes back as i64[4, B], one
    device→host readback instead of four. Off-chip round trips are the
    serving path's real cost (HBM-adjacent compute is ~µs; each transfer
    pays dispatch + interconnect latency), so the hot path stages through
    exactly one buffer each way. The host-side packer is pack_window below
    — the row-order contract lives only in this file.
    """
    new_state, resp = decide(state, _wide_reqs(packed), now_ms)
    return new_state, _wide_response(resp)


def decide_scan_packed(
    state: TableState, packed_k: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """Apply K packed windows sequentially in ONE device dispatch.

    `packed_k` is i64[K, 9, B]; the result is i64[K, 4, B]. Window k+1
    observes window k's table writes, exactly as K separate decide_packed
    calls would — `lax.scan` compiles the kernel body once and loops on
    device, so the per-window cost collapses from one full dispatch (launch
    overhead plus a host round trip) to the on-device loop carry. The
    table is the carry, so every window pays its own row gather and row
    scatter and may hold any keys at any lanes: the group launches of
    different callers' windows ride this one (Engine.launch_windows).
    """

    def body(st, pk):
        st2, out = decide_packed(st, pk, now_ms)
        return st2, out

    return jax.lax.scan(body, state, packed_k)


def _scan_carried(state: TableState, packed_k: jax.Array, reqs_of, out_of,
                  now_ms: jax.Array) -> Tuple[TableState, jax.Array]:
    """K staged rounds of ONE lane-aligned group with the lanes' rows, not
    the table, as the scan's carry: one row gather before the loop, K times
    decide_rows on the lanes, one row scatter after it.

    The contract the table-carried scans do not need: inside the stack a
    lane holds one slot — every round either that slot or -1 (the key sat
    the round out; decide_rows then hands its row back as it was). So the
    row a lane would gather in round k+1 is the row it scattered in round
    k, and the table is touched twice a group instead of twice a round.
    Same arithmetic in the same order with the same `now`: bit-identical
    to the table-carried scan of the same stack, table and responses
    (tests/test_scan_carried.py). The engine aligns a repeated key's rounds
    so (Engine._apply_windows_scanned); stacks it cannot align keep the
    table-carried program."""
    reqs_k = jax.vmap(reqs_of)(packed_k)  # ReqBatch of [K, B] columns
    slot = reqs_k.slot.max(axis=0)  # the lane's slot; -1: never live
    rows = load_rows(state, jnp.maximum(slot, 0))

    def body(rows, reqs):
        rows2, resp = decide_rows(rows, reqs, now_ms)
        return rows2, out_of(resp)

    rows, out = jax.lax.scan(body, rows, reqs_k)
    return store_rows(state, slot, rows), out


def decide_scan_carried(
    state: TableState, packed_k: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide_scan_packed's contract (i64[K, 9, B] -> i64[K, 4, B]) for a
    lane-aligned stack, the rows carried (see _scan_carried)."""
    return _scan_carried(state, packed_k, _wide_reqs, _wide_response, now_ms)


# ---------------------------------------------------------------- compact
# Ingest-bound links (any slow PCIe/NIC path) pay
# per-byte for every staging row, so the hot path offers a second wire
# format: i32[5, B] up (slot, hits, limit, duration, meta) and i32[4, B]
# back (status, limit, remaining, reset_delta) — 20+16 bytes/decision
# instead of the wide format's 72+32. Eligibility: values in [0, 2^31) and
# no DURATION_IS_GREGORIAN lanes (calendar spans exceed i32; the serving
# fast paths already route gregorian to the wide pipeline). The response's
# reset_time rides as a delta from `now` (always ≥ 0 for live buckets;
# an absolute 0 — RESET_REMAINING, padding — is the sentinel -1).

COMPACT_ROWS = 5
_META_BEHAVIOR_SHIFT = 1
_META_BEHAVIOR_MASK = 0x3F
_META_FRESH = 1 << 7
_I32_MAX = (1 << 31) - 1


def _compact_reqs(packed: jax.Array) -> ReqBatch:
    """The ReqBatch of one compact i32[5, B] staging window."""
    meta = packed[4]
    zero64 = jnp.zeros(packed.shape[-1], I64)
    return ReqBatch(
        slot=packed[0],
        hits=packed[1].astype(I64),
        limit=packed[2].astype(I64),
        duration=packed[3].astype(I64),
        algorithm=meta & 1,
        behavior=(meta >> _META_BEHAVIOR_SHIFT) & _META_BEHAVIOR_MASK,
        greg_expire=zero64,
        greg_interval=zero64,
        fresh=(meta & _META_FRESH) != 0,
    )


def decide_packed_compact(
    state: TableState, packed: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide() over one compact i32[5, B] staging buffer.

    Bit-identical to decide_packed on any window compact_window() accepts —
    held so by TestCompactStaging's differential. Returns i32[4, B]."""
    new_state, resp = decide(state, _compact_reqs(packed), now_ms)
    return new_state, _compact_response(resp, now_ms)


def _compact_response(resp, now_ms) -> jax.Array:
    """Pack a RespBatch into the compact i32[4, B] wire rows (status, limit,
    remaining, reset delta; absolute-zero reset encodes as -1). Shared by
    the compact and interned kernels so the response contract has one
    writer."""
    now = jnp.asarray(now_ms, I64)
    delta = jnp.where(resp.reset_time == 0, -1, resp.reset_time - now)
    return jnp.stack([
        resp.status,
        resp.limit.astype(I32),
        resp.remaining.astype(I32),
        delta.astype(I32),
    ])


def decide_scan_packed_compact(
    state: TableState, packed_k: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """K compact windows in one dispatch: i32[K, 5, B] -> i32[K, 4, B],
    window k+1 observing window k's writes (see decide_scan_packed)."""

    def body(st, pk):
        st2, out = decide_packed_compact(st, pk, now_ms)
        return st2, out

    return jax.lax.scan(body, state, packed_k)


def decide_scan_carried_compact(
    state: TableState, packed_k: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide_scan_packed_compact's contract (i32[K, 5, B] -> i32[K, 4, B])
    for a lane-aligned stack, the rows carried (see _scan_carried)."""
    return _scan_carried(
        state, packed_k, _compact_reqs,
        lambda resp: _compact_response(resp, now_ms), now_ms)


def compact_window(packed, width=None):
    """Wide i64[9, L] (or [K, 9, L]) staging -> compact i32[..., 5, width],
    or None when any lane is ineligible (gregorian, or a value outside
    [0, 2^31)).

    `width` (default L) is the launched width: `packed` is then the
    launch's live prefix and lanes [L, width) are padding (slot -1, zeros
    elsewhere), written by one fill — the array the whole-buffer form
    makes of the same window, for the price of its L live lanes."""
    vals = packed[..., 1:4, :]
    if (vals < 0).any() or (vals > _I32_MAX).any():
        return None
    if (packed[..., 5, :] & int(Behavior.DURATION_IS_GREGORIAN)).any():
        return None
    live = packed.shape[-1]
    if width is None or width == live:
        out = np.empty(packed.shape[:-2] + (COMPACT_ROWS, live), np.int32)
    else:
        # np.zeros is the allocator's zero pages; np.full would fault
        # every page in to write it
        out = np.zeros(packed.shape[:-2] + (COMPACT_ROWS, width), np.int32)
        out[..., 0, live:] = -1
    out[..., 0, :live] = packed[..., 0, :]
    out[..., 1:4, :live] = vals
    out[..., 4, :live] = (
        (packed[..., 4, :] & 1)
        | ((packed[..., 5, :] & _META_BEHAVIOR_MASK) << _META_BEHAVIOR_SHIFT)
        | ((packed[..., 8, :] != 0) << 7)
    )
    return out


def widen_compact_out(out, now_ms: int, live=None):
    """Compact i32[..., 4, B] responses -> the wide i64 rows decide_packed
    returns (reset_delta -1 decodes to absolute 0); with `live`, of lanes
    [0, live) alone (nothing reads a window beyond its live lanes)."""
    host = np.asarray(out)
    wide = (host if live is None else host[..., :live]).astype(np.int64)
    delta = wide[..., 3, :]
    wide[..., 3, :] = np.where(delta < 0, 0, now_ms + delta)
    return wide


def pad_window(packed, width: int):
    """A live-prefix wide stack i64[..., 9, L] at its launched width:
    lanes [L, width) padding (slot -1, zeros elsewhere). What a launch
    ships when no narrower wire format takes its window."""
    live = packed.shape[-1]
    if live == width:
        return packed
    out = np.zeros(packed.shape[:-1] + (width,), np.int64)
    out[..., 0, live:] = -1
    out[..., :live] = packed
    return out


# ---------------------------------------------------------------- interned
# Real fleets run a handful of limit CONFIGS (limit, duration pairs) over
# millions of keys — the reference's requests repeat the same RateLimit
# name/limit/duration per route (gubernator.proto RateLimitReq). The
# interned wire format exploits that: the host interns each window's
# (limit, duration) pairs into a tiny i64[N_CFG, 2] table shipped alongside
# (4 KB — noise), and each lane carries only slot + one packed meta word:
# i32[2, B] up = 8 bytes/decision instead of compact's 20 or wide's 72.
# The kernel gathers limit/duration back out of the config table — a [B]
# gather over a VMEM-resident 256-row table, free next to the HBM row
# gather. Responses reuse the compact i32[4, B] contract.
#
# meta word layout (bit 31 clear, always non-negative):
#   [14:0]  hits        (eligibility: 0 <= hits < 2^15)
#   [15]    algorithm
#   [21:16] behavior    (6 bits, same mask as compact)
#   [22]    fresh
#   [30:23] config id   (eligibility: <= 256 distinct pairs per stack)

INTERN_ROWS = 2
INTERN_MAX_CFG = 256
_INT_HITS_BITS = 15
_INT_HITS_MAX = (1 << _INT_HITS_BITS) - 1
_INT_ALGO_SHIFT = 15
_INT_BEHAVIOR_SHIFT = 16
_INT_FRESH_SHIFT = 22
_INT_CFG_SHIFT = 23


def decide_packed_interned(
    state: TableState, packed: jax.Array, cfg: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide() over one interned i32[2, B] staging buffer + i64[N, 2]
    config table. Bit-identical to decide_packed on any window
    intern_window() accepts (TestInternedStaging differential).
    Returns the compact i32[4, B] response rows."""
    meta = packed[1]
    cfgid = (meta >> _INT_CFG_SHIFT) & (INTERN_MAX_CFG - 1)
    zero64 = jnp.zeros(packed.shape[-1], I64)
    reqs = ReqBatch(
        slot=packed[0],
        hits=(meta & _INT_HITS_MAX).astype(I64),
        limit=cfg[cfgid, 0],
        duration=cfg[cfgid, 1],
        algorithm=(meta >> _INT_ALGO_SHIFT) & 1,
        behavior=(meta >> _INT_BEHAVIOR_SHIFT) & _META_BEHAVIOR_MASK,
        greg_expire=zero64,
        greg_interval=zero64,
        fresh=(meta & (1 << _INT_FRESH_SHIFT)) != 0,
    )
    new_state, resp = decide(state, reqs, now_ms)
    return new_state, _compact_response(resp, now_ms)


def decide_scan_packed_interned(
    state: TableState, packed_k: jax.Array, cfg: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """K interned windows in one dispatch: i32[K, 2, B] + one shared
    i64[N, 2] config table -> i32[K, 4, B], window k+1 observing window
    k's writes (see decide_scan_packed)."""

    def body(st, pk):
        st2, out = decide_packed_interned(st, pk, cfg, now_ms)
        return st2, out

    return jax.lax.scan(body, state, packed_k)


def _intern_pairs(packed):
    """Shared eligibility gate for the two Python interners: the
    (limit << 31) | duration pair per lane, or None when any lane cannot
    ride the interned format (gregorian, hits outside [0, 2^15),
    limit/duration outside [0, 2^31))."""
    hits = packed[..., 1, :]
    if (hits < 0).any() or (hits > _INT_HITS_MAX).any():
        return None
    vals = packed[..., 2:4, :]
    if (vals < 0).any() or (vals > _I32_MAX).any():
        return None
    if (packed[..., 5, :] & int(Behavior.DURATION_IS_GREGORIAN)).any():
        return None
    # both < 2^31: injective, fits i64
    return (packed[..., 2, :] << 31) | packed[..., 3, :]


def _emit_interned(packed, inv):
    """Shared meta-word emission: wide staging + per-lane config ids ->
    interned i32 rows. The bit layout has THREE writers (here, the two
    callers' id assignment aside: keydir.cpp keydir_prep_pack_interned)
    and one reader (decide_packed_interned) — keep them in sync."""
    out = np.empty(packed.shape[:-2] + (INTERN_ROWS, packed.shape[-1]),
                   np.int32)
    out[..., 0, :] = packed[..., 0, :]
    out[..., 1, :] = (
        packed[..., 1, :]
        | ((packed[..., 4, :] & 1) << _INT_ALGO_SHIFT)
        | ((packed[..., 5, :] & _META_BEHAVIOR_MASK) << _INT_BEHAVIOR_SHIFT)
        | ((packed[..., 8, :] != 0).astype(np.int64) << _INT_FRESH_SHIFT)
        | (inv.astype(np.int64) << _INT_CFG_SHIFT)
    )
    return out


def intern_window(packed):
    """Wide i64[9, W] (or [K, 9, W]) staging -> (interned i32 rows,
    i64[INTERN_MAX_CFG, 2] config table), or None when any lane is
    ineligible (see _intern_pairs) or the stack holds more than
    INTERN_MAX_CFG distinct (limit, duration) pairs. Padding lanes
    (slot == -1) intern like any other (their zero config occupies one
    table row)."""
    pair = _intern_pairs(packed)
    if pair is None:
        return None
    cfg_vals, inv = np.unique(pair, return_inverse=True)
    if cfg_vals.size > INTERN_MAX_CFG:
        return None
    cfg = np.zeros((INTERN_MAX_CFG, 2), np.int64)
    cfg[: cfg_vals.size, 0] = cfg_vals >> 31
    cfg[: cfg_vals.size, 1] = cfg_vals & _I32_MAX
    return _emit_interned(packed, inv.reshape(pair.shape)), cfg


class InternCache:
    """Stateful interner for a serving loop: the config table persists
    across windows, so the per-window cost is one searchsorted against the
    (tiny, sorted) known-pair array instead of np.unique's full sort of
    every lane. New pairs grow the table (stable ids — already-issued
    meta words stay valid); overflow past INTERN_MAX_CFG or any
    ineligible lane returns None for that window (caller falls back to
    wide/compact staging), leaving the cache intact."""

    def __init__(self):
        self._sorted_pairs = np.empty(0, np.int64)  # sorted for searchsorted
        self._sorted_ids = np.empty(0, np.int64)  # pair -> stable config id
        self.cfg = np.zeros((INTERN_MAX_CFG, 2), np.int64)
        self.n_cfg = 0

    def intern(self, packed):
        """Wide i64[..., 9, W] staging -> interned i32 rows (the shared
        self.cfg table ships alongside), or None when ineligible."""
        pair = _intern_pairs(packed)
        if pair is None:
            return None
        flat = pair.ravel()
        pos = np.searchsorted(self._sorted_pairs, flat)
        pos_c = np.minimum(pos, max(self._sorted_pairs.size - 1, 0))
        known = (self._sorted_pairs.size > 0) \
            and bool((self._sorted_pairs[pos_c] == flat).all())
        if not known:
            new = np.unique(flat) if self._sorted_pairs.size == 0 else \
                np.setdiff1d(np.unique(flat), self._sorted_pairs,
                             assume_unique=True)
            if self.n_cfg + new.size > INTERN_MAX_CFG:
                return None
            ids = np.arange(self.n_cfg, self.n_cfg + new.size)
            self.cfg[ids, 0] = new >> 31
            self.cfg[ids, 1] = new & _I32_MAX
            self.n_cfg += new.size
            self._sorted_pairs = np.concatenate([self._sorted_pairs, new])
            self._sorted_ids = np.concatenate([self._sorted_ids, ids])
            order = np.argsort(self._sorted_pairs, kind="stable")
            self._sorted_pairs = self._sorted_pairs[order]
            self._sorted_ids = self._sorted_ids[order]
            pos = np.searchsorted(self._sorted_pairs, flat)
        inv = self._sorted_ids[pos].reshape(pair.shape)
        return _emit_interned(packed, inv)


# ------------------------------------------------------------------ lean
# The dominant serving shape — hits == 1 (one decision per request), a
# handful of limit configs, no gregorian — needs even less than interned's
# 8 B/decision: ONE i32 word per lane. The config table absorbs algorithm
# and behavior alongside (limit, duration), hits = 1 is implied, and the
# slot rides in the low 24 bits (table <= 2^24 - 1 slots; the 10M-key
# north-star uses 10,000,001 < 16,777,215). 4 B up against interned's 8
# and compact's 20; the answers come back as the compact i32[4, B] rows,
# 16 B a lane, whichever narrow format carried the window.
#
# lane word layout (i32; bit 31 participates in the config id, so the
# word may be negative — every decode masks):
#   [23:0]  slot        (all-ones 0xFFFFFF = padding sentinel)
#   [24]    fresh
#   [31:25] config id   (<= 128 distinct (limit, duration, algo,
#                        behavior) tuples per deployment epoch)

LEAN_MAX_CFG = 128
_LEAN_SLOT_MASK = (1 << 24) - 1
_LEAN_PAD = _LEAN_SLOT_MASK  # slot sentinel: capacity must stay below it
_LEAN_FRESH_SHIFT = 24
_LEAN_CFG_SHIFT = 25


def staging_policy() -> str:
    """GUBER_STAGING resolution, shared by the single-chip and mesh
    engines (one parse, one error message): 'auto' ships each window on
    the leanest eligible wire format, 'wide' pins the i64[9] contract
    (e.g. to rule the switch out while debugging)."""
    import os

    # guberlint: disable=knob-drift -- kernel-debug pin read at engine build, before a DaemonConfig exists; not an operator surface
    s = os.environ.get("GUBER_STAGING", "auto")
    if s not in ("auto", "wide"):
        raise ValueError(
            f"GUBER_STAGING={s!r}: must be 'auto' or 'wide'"
            " (lean/compact cannot be pinned — ineligible windows need"
            " the wide format)")
    return s


def lean_capacity_ok(capacity: int) -> bool:
    """Slots must fit the 24-bit lane field with 0xFFFFFF reserved for
    padding — a deployment-time property, checked once per engine."""
    return capacity <= _LEAN_SLOT_MASK


def _config_rows(cfg: jax.Array, cfgid: jax.Array) -> jax.Array:
    """cfg[cfgid] as i64[..., 4], by a one-hot select over the table's few
    rows and not by a gather: the chip gathers element by element whatever
    the table's size (four column gathers were 4.2 ms of a 32 x 2048-lane
    lean scan, 4.89 ms against the compact one's 0.64; the select is 0.15,
    PERF.md PR 39), and exactly one row is hot, so the sum is that row."""
    hot = cfgid[..., None] == jnp.arange(cfg.shape[0], dtype=cfgid.dtype)
    return jnp.where(hot[..., None], cfg, 0).sum(axis=-2)


def _lean_reqs(lane: jax.Array, cfg: jax.Array) -> ReqBatch:
    """The ReqBatch of one lean i32[B] lane-word window and its config
    table; hits = 1 implied."""
    slot24 = lane & _LEAN_SLOT_MASK
    slot = jnp.where(slot24 == _LEAN_PAD, jnp.asarray(-1, I32), slot24)
    cfgid = (lane >> _LEAN_CFG_SHIFT) & (LEAN_MAX_CFG - 1)
    zero64 = jnp.zeros(lane.shape[-1], I64)
    config = _config_rows(cfg, cfgid)
    return ReqBatch(
        slot=slot,
        hits=jnp.ones(lane.shape[-1], I64),
        limit=config[..., 0],
        duration=config[..., 1],
        algorithm=config[..., 2].astype(I32),
        behavior=config[..., 3].astype(I32),
        greg_expire=zero64,
        greg_interval=zero64,
        fresh=((lane >> _LEAN_FRESH_SHIFT) & 1) != 0,
    )


def decide_packed_lean(
    state: TableState, packed: jax.Array, cfg: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide() over one lean i32[B] lane word per request + i64[128, 4]
    config table of (limit, duration, algorithm, behavior) rows. hits = 1
    implied. Bit-identical to decide_packed on any window lean_window()
    accepts (TestLeanStaging differential). Returns the compact i32[4, B]
    response rows."""
    new_state, resp = decide(state, _lean_reqs(packed, cfg), now_ms)
    return new_state, _compact_response(resp, now_ms)


def decide_scan_packed_lean(
    state: TableState, packed_k: jax.Array, cfg: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """K lean windows in one dispatch: i32[K, B] + one shared i64[128, 4]
    config table -> i32[K, 4, B], window k+1 observing window k's writes
    (see decide_scan_packed)."""

    def body(st, pk):
        st2, out = decide_packed_lean(st, pk, cfg, now_ms)
        return st2, out

    return jax.lax.scan(body, state, packed_k)


def decide_scan_carried_lean(
    state: TableState, packed_k: jax.Array, cfg: jax.Array, now_ms: jax.Array
) -> Tuple[TableState, jax.Array]:
    """decide_scan_packed_lean's contract (i32[K, B] + config table ->
    i32[K, 4, B]) for a lane-aligned stack, the rows carried (see
    _scan_carried)."""
    return _scan_carried(
        state, packed_k, lambda lane: _lean_reqs(lane, cfg),
        lambda resp: _compact_response(resp, now_ms), now_ms)


# Why a launch left the lean lane, in the order lean_stage looks: the names
# of EngineStats' `lean_refused_*` counters (docs/observability.md).
LEAN_REFUSALS = ("capacity", "hits", "gregorian", "range", "tuples")


def lean_window(packed, capacity: int, width=None):
    """Wide i64[9, W] (or [K, 9, W]) staging -> (lean i32[W] / [K, W] lane
    words, i64[LEAN_MAX_CFG, 4] config table), or None when any non-padding
    lane is ineligible: lean_stage without the reason."""
    return lean_stage(packed, capacity, width)[0]


def lean_stage(packed, capacity: int, width=None):
    """lean_window's conversion and, where it refuses, why: ((lanes, cfg),
    None, config rows used), or (None, reason, 0) with the first of
    LEAN_REFUSALS that holds of a non-padding lane: `capacity` a slot too
    wide for 24 bits (the table's, or a lane's), `hits` != 1, `gregorian`,
    `range` a limit or duration outside [0, 2^31), a behavior past 6 bits
    or an algorithm past 1, `tuples` more than LEAN_MAX_CFG distinct
    (limit, duration, algorithm, behavior) rows. Padding lanes emit the
    0xFFFFFF sentinel and occupy no config row. `width` as in
    compact_window: `packed` is the launch's live prefix, the lane words
    come back `width` wide.

    This is the served path's converter: the C prep (native/keydir.cpp
    keydir_prep_pack_columnar, keydir_prep_pack_fast) writes the wide
    buffer, and Engine._launch converts its live prefix here, in numpy,
    under the engine lock (masks + two 1-D uniques over the live lanes;
    the profiler's `stage` phase: onehit10m.batch1000 is the cell that
    measures it, 0.57 ms a launch of ~2,600 live lanes on the v5e's host
    where compact_window's launch pays 0.33; PERF.md section 6, PR 46).
    It drops the wire from
    72 to 4 B/lane; the answers come back as the compact i32[4, B] rows,
    16 B a lane. A launch with one `hits` != 1 lane pays one mask and
    leaves. The C emitter that writes lean directly
    (keydir_prep_pack_lean) has no caller on a served path: tests only."""
    if not lean_capacity_ok(capacity):
        return None, "capacity", 0
    slot = packed[..., 0, :]
    live = slot >= 0
    if (slot >= _LEAN_PAD).any():
        return None, "capacity", 0
    # the masks in the order of LEAN_REFUSALS: the commonest refusal (a
    # deployment whose requests carry hits other than 1) leaves first
    if ((packed[..., 1, :] != 1) & live).any():
        return None, "hits", 0
    limit = packed[..., 2, :]
    dur = packed[..., 3, :]
    algo = packed[..., 4, :]
    beh = packed[..., 5, :]
    if (((beh & int(Behavior.DURATION_IS_GREGORIAN)) != 0) & live).any():
        return None, "gregorian", 0
    bad = (
        (limit < 0) | (limit > _I32_MAX)
        | (dur < 0) | (dur > _I32_MAX)
        | ((algo & ~1) != 0)
        | ((beh & ~_META_BEHAVIOR_MASK) != 0)
    )
    if bool((bad & live).any()):
        return None, "range", 0
    # intern the (limit, duration, algorithm, behavior) tuples via TWO
    # 1-D uniques over injective packed keys — np.unique(axis=0) on the
    # stacked tuples costs ~1.9 µs/item (structured-view sort), two
    # plain i64 sorts cost ~20 ns/item
    pair = (limit[live] << 31) | dur[live]  # both < 2^31: injective
    meta7 = algo[live] | (beh[live] << 1)  # 7 bits
    u1, inv1 = np.unique(pair, return_inverse=True)
    u2, inv = np.unique(inv1.astype(np.int64) * 128 + meta7,
                        return_inverse=True)
    if u2.size > LEAN_MAX_CFG:
        return None, "tuples", 0
    cfg = np.zeros((LEAN_MAX_CFG, 4), np.int64)
    pairs = u1[u2 >> 7]
    cfg[: u2.size, 0] = pairs >> 31
    cfg[: u2.size, 1] = pairs & _I32_MAX
    cfg[: u2.size, 2] = u2 & 1
    cfg[: u2.size, 3] = (u2 & 127) >> 1
    lanes = np.full(slot.shape, _LEAN_PAD, np.int64)
    # astype before shifting: numpy 1.x value-based casting would promote
    # the bool to a small int dtype and overflow the 24-bit shift
    lanes[live] = (
        slot[live]
        | ((packed[..., 8, :][live] != 0).astype(np.int64)
           << _LEAN_FRESH_SHIFT)
        | (inv.reshape(-1).astype(np.int64) << _LEAN_CFG_SHIFT)
    )
    # bit 31 of the cfgid field lands in the i32 sign bit — wrap the bit
    # pattern through uint32 (every reader masks, so negatives are fine)
    lanes = lanes.astype(np.uint32).view(np.int32)
    n = lanes.shape[-1]
    if width is None or width == n:
        return (lanes, cfg), None, int(u2.size)
    out = np.empty(lanes.shape[:-1] + (width,), np.int32)
    out[..., n:] = _LEAN_PAD
    out[..., :n] = lanes
    return (out, cfg), None, int(u2.size)


def pack_window(items, slots, fresh, width: int, out=None):
    """Host-side packer for decide_packed: i64[9, width] from one window.

    `items` are prep WorkItems (resp_index, req, greg_expire, greg_interval);
    lanes beyond len(items) are padding (slot = -1). decide_packed is the
    only reader of the packed row order; it has TWO writers — this function
    and the native fast path (native/keydir.cpp keydir_prep_pack_fast) —
    which must stay in sync. `out`, when given, must be a zero-filled i64[9, width] view
    (e.g. one window's slice of a scan group's staging buffer) and is
    filled in place instead of allocating.
    """
    n = len(items)
    packed = np.zeros((9, width), np.int64) if out is None else out
    packed[0, :n] = slots
    packed[0, n:] = -1
    if n:
        packed[1:8, :n] = np.array(
            [
                (r.hits, r.limit, r.duration, int(r.algorithm),
                 int(r.behavior), ge, gi)
                for _i, r, ge, gi in items
            ],
            np.int64,
        ).T
    packed[8, :n] = fresh
    return packed


def make_decide_jit(donate: bool = None):
    """Compiled decide(). Donating the table keeps the 7 HBM columns in place
    across windows instead of allocating a fresh ~56B/key copy per call —
    but some backends reject donation, so probe unless told."""
    if donate is None:
        from gubernator_tpu.utils.platform import donation_supported

        donate = donation_supported()
    return jax.jit(decide, donate_argnums=(0,) if donate else ())


def pad_batch(reqs: ReqBatch, to_size: int) -> ReqBatch:
    """Pad a host-built batch to a bucketed size to bound recompilation."""
    b = reqs.slot.shape[0]
    if b == to_size:
        return reqs
    pad = to_size - b

    def _pad(x, fill):
        return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])

    return ReqBatch(
        slot=_pad(reqs.slot, -1),
        hits=_pad(reqs.hits, 0),
        limit=_pad(reqs.limit, 0),
        duration=_pad(reqs.duration, 0),
        algorithm=_pad(reqs.algorithm, 0),
        behavior=_pad(reqs.behavior, 0),
        greg_expire=_pad(reqs.greg_expire, 0),
        greg_interval=_pad(reqs.greg_interval, 0),
        fresh=_pad(reqs.fresh, False),
    )


def batch_from_columns(
    slot: Sequence[int],
    hits: Sequence[int],
    limit: Sequence[int],
    duration: Sequence[int],
    algorithm: Sequence[int],
    behavior: Sequence[int],
    greg_expire: Sequence[int],
    greg_interval: Sequence[int],
    fresh: Sequence[bool],
) -> ReqBatch:
    """Build a device batch from host lists (numpy staging happens in jnp)."""
    return ReqBatch(
        slot=jnp.asarray(slot, I32),
        hits=jnp.asarray(hits, I64),
        limit=jnp.asarray(limit, I64),
        duration=jnp.asarray(duration, I64),
        algorithm=jnp.asarray(algorithm, I32),
        behavior=jnp.asarray(behavior, I32),
        greg_expire=jnp.asarray(greg_expire, I64),
        greg_interval=jnp.asarray(greg_interval, I64),
        fresh=jnp.asarray(fresh, jnp.bool_),
    )
