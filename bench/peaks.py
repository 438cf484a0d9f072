"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of ``src/repro/roofline/hw.py``'s table, so that
no change to the program can move the yardstick.  A kind that is not in
the table is an error: a share of another chip's peak is wrong, not
approximate.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
394 TOP/s int8, 16 GB of HBM at 819 GB/s.  JAX reports the chip as
"TPU v5 lite".
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
