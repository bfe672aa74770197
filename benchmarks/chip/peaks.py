"""Published peaks of each accelerator the benchmark may run on, keyed by
the `device_kind` that JAX reports.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
A device kind missing from the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
