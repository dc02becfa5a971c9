"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip.  A kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

import dataclasses

__all__ = ["ChipPeaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops_per_s: float
    int8_ops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    ici_bytes_per_s: float


_V5E = ChipPeaks(
    bf16_flops_per_s=197e12,
    int8_ops_per_s=393e12,
    hbm_bytes_per_s=819e9,
    hbm_bytes=16 * 2**30,
    ici_bytes_per_s=1600e9 / 8,
)

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
