"""Published per-chip peaks, keyed by JAX's ``device_kind``.

One table for every place that turns a rate into a utilisation.  A device
that is not in it is an error where a utilisation is computed, never a
default: a share of the wrong peak is a wrong number with nothing to show
it.  (``HELIX_PEAK_FLOPS`` is the operator's override for a chip the table
does not know.)
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 10**9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


class UnknownDeviceKind(KeyError):
    """No published peaks for this ``device_kind``."""


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to helix_tpu/device/peaks.py with "
            "its source, or set HELIX_PEAK_FLOPS"
        ) from None


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip of this kind."""
    return chip_peaks(device_kind).bf16_flops
