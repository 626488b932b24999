"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every utilisation in this repo divides by (``bench.py``,
``benchmarks/real_chip.py``, ``examples/llama/llama_fsdp.py``). A device
that is not in it is an error where a utilisation is computed, never a
default: a number over the wrong peak is worse than no number. Beside it,
:func:`bench_device`: the one gate that keeps a benchmark off anything
but the chip.
"""

from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass(frozen=True)
class ChipPeak:
    bf16_tflops: float  # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbps: float  # HBM bandwidth, GB/s per chip
    hbm_gb: float  # HBM capacity, GB per chip


PEAKS: dict[str, ChipPeak] = {
    # Google Cloud documentation, "TPU v5e" (system architecture, per-chip
    # specifications): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.
    "TPU v5 lite": ChipPeak(bf16_tflops=197.0, hbm_gbps=819.0, hbm_gb=16.0),
}


def peak_for(device) -> ChipPeak:
    """The peaks of ``device`` (a ``jax.Device``), by its ``device_kind``."""
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}) in benchmarks/peaks.py; a "
            "utilisation cannot be computed for it — add the kind with "
            f"its source (known: {sorted(PEAKS)})"
        ) from None


def bench_device(prog: str):
    """The device a benchmark may measure on: turns the compile cache on,
    then refuses (non-zero exit, reason on stderr) unless the platform JAX
    picked is ``tpu`` — a benchmark number comes only from the chip.
    ``BENCH_ALLOW_CPU=1`` lets the flow be rehearsed on the CPU."""
    import jax

    from tensorflowonspark_tpu.utils.util import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not os.environ.get("BENCH_ALLOW_CPU"):
        raise SystemExit(
            f"{prog}: no TPU — JAX picked the {dev.platform!r} backend "
            f"({dev.device_kind}). A benchmark number comes only from the "
            "chip; BENCH_ALLOW_CPU=1 rehearses the flow on the CPU "
            "(bench.py: with BENCH_SMOKE=1 for the tiny model)."
        )
    return dev
