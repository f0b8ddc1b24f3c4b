"""Peak rates of each accelerator the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device missing here is an error: a
roofline or utilisation share needs the chip's own peak.

TPU v5e (JAX reports "TPU v5 lite"): 197 TFLOP/s in bf16 and 819 GB/s
of HBM bandwidth per chip, from Google Cloud's documentation page
"TPU v5e".
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_bf16: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: int         # bytes of HBM per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16 * 10**9,
                        "Google Cloud documentation, 'TPU v5e'"),
    "TPU v5e": Peak(197e12, 819e9, 16 * 10**9,
                    "Google Cloud documentation, 'TPU v5e'"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
