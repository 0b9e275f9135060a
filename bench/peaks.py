"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not here is an error, not a default.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bfloat16, 393 TOP/s int8,
16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
