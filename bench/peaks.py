"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def for_device(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to bench/peaks.py with their source")
    return PEAKS[kind]
