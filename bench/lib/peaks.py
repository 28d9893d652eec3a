"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``. A kind that is not listed is an error, never
a default: a roofline share against a guessed peak means nothing.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_per_s: float      # dense bf16 matmul peak
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 2**30,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to bench/lib/peaks.py "
                       f"with its source") from None
