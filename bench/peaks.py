"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per
chip.  An fp32 matmul on the TPU runs as bf16 passes, so the bf16 peak
is the one a model FLOP count is held against.  A kind not listed here
is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a sourced row to "
                       f"bench/peaks.py") from None
