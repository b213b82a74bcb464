"""Published peaks of the devices the benchmark runs on.

Keyed by ``jax.Device.device_kind``. A device that is not in the table is
an error: a share of a peak is never computed against a guessed peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_s": 1600e9,
        "source": ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                   "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of "
                   "chip-to-chip interconnect per chip"),
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add the device to bench/peaks.py with its source")
    return PEAKS[device_kind]
