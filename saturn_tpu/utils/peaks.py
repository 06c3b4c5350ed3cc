"""Published per-chip peaks, keyed by ``device_kind``.

One table for every utilization figure the package or its benchmarks report.
A device that is not listed is an error: a utilization against a guessed
peak is worse than none.

Sources: Google Cloud TPU documentation, the "System architecture" page of
each generation (v5e: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
"""

from __future__ import annotations

from typing import Any

#: bf16 peak FLOP/s of one chip, by the ``device_kind`` string JAX reports.
PEAK_BF16_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device: Any) -> float:
    """bf16 peak FLOP/s of ``device`` (anything with a ``device_kind``)."""
    kind = getattr(device, "device_kind", device)
    try:
        return PEAK_BF16_FLOPS[kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {kind!r}: add it to "
            f"saturn_tpu.utils.peaks.PEAK_BF16_FLOPS with its source"
        ) from None
