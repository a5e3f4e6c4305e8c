"""Published peaks of one chip, keyed by `device_kind` as JAX reports it,
and the bytes the decision algorithm needs. A device that is not in the
table is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "hbm_bytes": 16e9},
}

ROW_BYTES = 64  # one bucket row in HBM
STAGING_IN_BYTES = 4  # the leanest wire a lane can ride in on (lean i32)
ANSWER_OUT_BYTES = 24  # status, remaining, reset_time as int64


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in benchmarks/peaks.py")
    return PEAKS[device_kind]


def decide_bytes(lanes: float) -> float:
    """HBM bytes the algorithm needs to decide `lanes` requests: each reads
    its row and writes it back, takes its staged request in and puts its
    answer out. The table's other rows are not needed."""
    return lanes * (2 * ROW_BYTES + STAGING_IN_BYTES + ANSWER_OUT_BYTES)
