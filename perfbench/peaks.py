"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table every utilisation divides by (the
original is ``benchmarks/peaks.py``; it is a copy so that no later PR can
move the yardstick). A kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e", per-chip specifications:
    # 197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s.
    "TPU v5 lite": {"flops": 197.0e12, "hbm_bytes_per_s": 819.0e9, "hbm_bytes": 16.0e9},
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"perfbench/peaks.py (known: {sorted(PEAKS)}); add the kind "
            "with its source before reporting a share of a peak on it"
        ) from None
