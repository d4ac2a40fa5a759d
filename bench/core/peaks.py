"""Published peaks per chip, keyed by JAX's ``device_kind``. A device
missing here is an error: no reading falls back to a guess.

TPU v5e ("TPU v5 lite" to JAX): Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/core/peaks.py with its source")
    return PEAKS[device_kind]


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations over
    peak bf16 rate and bytes over peak HBM bandwidth."""
    pk = peaks(device_kind)
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
