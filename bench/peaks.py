"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip links.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
