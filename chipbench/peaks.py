"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; KeyError for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} ({SOURCE})") from None
