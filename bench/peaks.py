"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": V5E,
}


def peaks(platform: str, device_kind: str) -> Dict:
    if platform != "tpu":
        raise ValueError(f"no peak table for platform {platform!r}: the "
                         "benchmark measures TPUs only")
    if device_kind not in PEAKS:
        raise ValueError(f"no peak table for device kind {device_kind!r}")
    return PEAKS[device_kind]
