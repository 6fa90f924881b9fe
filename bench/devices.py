"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a share
of a peak is never computed against a guessed peak."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
