"""Published peaks per accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.  A device that is not
in the table is an error, not a default.
"""
from __future__ import annotations

_V5E = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9, "bf16_flops_per_s": 197e12,
        "source": 'Google Cloud documentation, "TPU v5e"'}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
