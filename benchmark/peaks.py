"""Published peaks of the devices the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A device that is not here is an
error, never a default."""

from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
#: 16 GB HBM per chip
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/peaks.py with its source")
    return PEAKS[device_kind]
