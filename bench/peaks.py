"""Published peaks per device kind.  A kind that is not listed is an
error: a roofline share against a guessed peak is no measurement."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,       # chip-to-chip interconnect
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source") from None
