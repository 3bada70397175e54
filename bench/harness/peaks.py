"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device missing from the table is an
error, never a default."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}
SOURCE = "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM"


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}") from None
