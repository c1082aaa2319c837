"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, not a default.

TPU v5e (``"TPU v5 lite"``): Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
