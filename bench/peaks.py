"""Published peaks of each chip, keyed by ``jax.Device.device_kind``.

Copied from ``repro.launch.hlo_analysis.CHIP_PEAKS`` so that the yardstick
stays with the benchmark. A kind that is not here is an error, never a
default: a roofline priced with another chip's peaks is wrong.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    hbm_bytes: int
    source: str


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 10**9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(CHIP_PEAKS)}"
        ) from None
