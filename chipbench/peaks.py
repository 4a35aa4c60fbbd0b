"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind missing here is an error, never a
default."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float     # FLOP/s
    hbm_bw: float         # bytes/s
    hbm_bytes: float      # bytes of device memory
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None
