"""Published peaks of each accelerator the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind that is not here is an error, not a
default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI',
    },
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
