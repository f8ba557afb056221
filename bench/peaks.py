"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A device that is not listed is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s).
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    bf16_flops: float       # FLOP/s
    hbm_bytes_s: float      # bytes/s
    hbm_bytes: float        # bytes


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_s=819e9,
                        hbm_bytes=16e9),
}


def peak_of(device_kind: str) -> Peak:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
