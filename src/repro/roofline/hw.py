"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A kind that is not in the table raises: a roofline share computed against
another chip's peaks is wrong, not approximate.

Sources:
* "TPU v5 lite" (TPU v5e) — Google Cloud documentation, "TPU v5e": 197
  TFLOP/s bf16, HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
  interconnect over four ICI links.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s
    hbm_bw: float            # bytes/s
    ici_link_bw: float       # bytes/s per link


PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_link_bw=1600e9 / 8 / 4),
}

# the chip the production mesh and the dry-runs target
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind with
    no published entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
