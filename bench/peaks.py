"""Peak rates of the chips the benchmark runs on, and the work a kernel
is required to do, for roofline shares.

A roofline share is the least time the chip could take for the required
work, ``max(ops / peak ops/s, bytes / peak bytes/s)``, over the time the
kernel took."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"ops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    """The row for ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add a row with its source to bench/peaks.py"
                       ) from None


def fold_count_max_work(batch: int, capacity: int, width: int
                        ) -> tuple[int, int]:
    """(ops, bytes) a counting-set fold of ``batch`` entries into a table
    of ``capacity`` slots of ``width`` 32-bit key/check words requires,
    whatever implements it: each entry's slot id, amount and key row read
    once (one add and ``width`` maxes each), and the table's count and
    rows read and written once."""
    ops = batch * (1 + width)
    bytes_ = 4 * batch * (2 + width) + 2 * 4 * capacity * (1 + width)
    return ops, bytes_


def roofline_share(ops: float, bytes_: float, seconds: float,
                   device_kind: str) -> float:
    """Percent of the chip's roofline that ``ops`` and ``bytes_`` done in
    ``seconds`` reach."""
    p = peaks(device_kind)
    least = max(ops / p["ops_per_s"], bytes_ / p["bytes_per_s"])
    return 100.0 * least / seconds
