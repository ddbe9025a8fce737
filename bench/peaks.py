"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect over 4 links (50 GB/s each).  JAX reports a v5e as
"TPU v5 lite".
"""
from __future__ import annotations

_V5E = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 50e9, "hbm_bytes": 16e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
