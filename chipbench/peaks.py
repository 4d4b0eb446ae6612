"""Published peaks of each chip, keyed by JAX's ``device_kind``. A device
that is not here is an error, never a default.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def for_device(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
