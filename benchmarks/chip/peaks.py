"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

_V5E = {"flops_per_s": 197e12,        # bf16
        "bytes_per_s": 819e9,         # HBM
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud, TPU v5e"}

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
