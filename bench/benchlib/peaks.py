"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
