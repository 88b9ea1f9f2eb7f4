"""The chips' published peaks, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (as the
on-chip-measurement guide quotes it). A device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
