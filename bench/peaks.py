"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.  A
device that is not listed is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    name: str
    bf16_flops: float        # FLOP/s, dense bf16 on the MXU
    hbm_bytes_per_s: float   # HBM bandwidth


PEAKS = {
    "TPU v5 lite": Peaks("v5e", bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
