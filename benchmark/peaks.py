"""Published peaks of one chip, keyed by the `device_kind` JAX reports.

Source of every number: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s, 1,600 Gbit/s chip-to-chip interconnect. A device that is not in
the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s
        "int8_ops": 393e12,         # OP/s
        "hbm_bytes_per_s": 819e9,   # B/s
        "hbm_bytes": 16e9,          # B
        "ici_bits_per_s": 1600e9,   # bit/s
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            "with its source to benchmark/peaks.py")
    return PEAKS[device_kind]
