"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not in the table is an error:
a roofline share against a guessed peak is no measurement.

The roofline share of a piece of work is the least time the chip could
take for it, the larger of its operations over peak FLOP/s and its bytes
over peak HBM bandwidth, divided by the device time it took.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float      # bf16 FLOP/s of one chip
    hbm_bw: float     # HBM bytes/s of one chip
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB of HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str):
    """Percent of the chip's roofline that `ops` operations over `nbytes`
    bytes reached in `seconds` of device time; None without device time."""
    if seconds <= 0:
        return None
    p = peaks_for(device_kind)
    least = max(ops / p.flops, nbytes / p.hbm_bw)
    return 100.0 * least / seconds
