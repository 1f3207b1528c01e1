"""Peaks of the chips the benchmark knows, keyed by JAX's `device_kind`,
and the bytes and operations the served YCSB algorithm needs.

A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
# int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flop_per_s=197e12,
                        hbm_bytes=16e9),
}


def peak_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.py; "
                       "add its published peaks with their source")
    return PEAKS[kind]


def ycsb_epoch_bytes(committed_txns: float, req_per_query: int,
                     row_bytes: int) -> float:
    """Bytes of table traffic the algorithm NEEDS for one epoch: every
    committed access reads or writes one field of ``row_bytes`` (100 B in
    the reference's schema) exactly once.  Plans, sorts, verdicts and
    the feed are overhead, not needed traffic — they lower the share."""
    return committed_txns * req_per_query * row_bytes
