"""Published peaks per device kind, as JAX reports ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
