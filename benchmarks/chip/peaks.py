"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``. A device that is not here is an error: a share
of a peak that nobody published would be a guess.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s
        "hbm_bytes_per_s": 819e9,   # B/s
        "hbm_bytes": 16e9,          # B
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
