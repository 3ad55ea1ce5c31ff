"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's ``device_kind``.  A device that is not here is an error."""
from __future__ import annotations

from typing import Dict

__all__ = ["PEAKS", "peaks"]

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add them to bench/peaks.py "
                       "with their source") from None
