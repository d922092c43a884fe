"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s per chip
        "int8_ops": 393e12,        # OP/s per chip
        "hbm_bytes_per_s": 819e9,  # B/s per chip
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1.6e12,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
