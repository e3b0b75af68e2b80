"""Published per-chip peaks, keyed by the device kind JAX reports.

TPU v5e (device kind ``TPU v5 lite``): Google Cloud documentation,
"TPU v5e" -- 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # bytes/s
    hbm_bytes: float        # bytes


PEAKS = {
    'TPU v5 lite': Peaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f'no peaks for device kind {device_kind!r}; the '
                       f'table has {sorted(PEAKS)}')
    return PEAKS[device_kind]
