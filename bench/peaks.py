"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``launch/costs.py`` (PR 12) so the yardstick
stays put when the program changes.  Source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
1,600 Gbit/s chip-to-chip interconnect.  Add a kind only with its
published source.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; an unknown kind raises (a share of the wrong
    chip's peak is a wrong number, never a default)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak entry for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
