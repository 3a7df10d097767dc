"""Published peaks per accelerator, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error: a share of an unknown peak
is not a number this benchmark reports.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: 197 "
                  "TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
