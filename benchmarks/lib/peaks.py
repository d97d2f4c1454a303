"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` string JAX reports.  A kind that is not here is an error,
never a default: a roofline share against the wrong peak is a wrong
number under a right name.

Source, TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s chip-to-chip interconnect, per chip.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s, one chip
    hbm_bytes_per_s: float  # bytes/s, one chip
    hbm_bytes: float  # bytes, one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "819 GB/s, 16 GB",
    ),
}
# the same chip under the name newer runtimes report
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"to benchmarks/lib/peaks.py with its source (known: "
            f"{sorted(PEAKS)})"
        ) from None
