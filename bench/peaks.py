"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud TPU v5e documentation ("TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s per chip).  A device kind missing
from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses

SOURCE = "Google Cloud TPU v5e documentation"


@dataclasses.dataclass(frozen=True)
class Peaks:
    """One chip's peaks: bf16 FLOP/s, HBM bytes/s and HBM bytes."""

    bf16_flops: float
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9),
    "TPU v5e": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; ``KeyError`` if the table lacks it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
