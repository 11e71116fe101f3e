"""TPU device metadata shared by the benchmarks.

One table so every bench computes MFU against the same peak; a number
corrected here propagates to bench_vit.py and any future MFU
report at once (they used to carry private copies that could drift).
"""

from __future__ import annotations

# bf16 peak TFLOP/s per chip, keyed by a lowercase substring of
# jax.Device.device_kind
PEAK_TFLOPS = {
    "tpu v5 lite": 197.0,
    "tpu v5e": 197.0,
    "tpu v4": 275.0,
    "tpu v6 lite": 918.0,
    "tpu v6e": 918.0,
}


def peak_tflops(device) -> float:
    """bf16 peak of ``device`` (a ``jax.Device``), by device_kind
    substring.  A device that is not in the table is an error: a
    utilization against an assumed peak is a made-up number."""
    kind = getattr(device, "device_kind", "").lower()
    for k, v in PEAK_TFLOPS.items():
        if k in kind:
            return v
    raise KeyError(
        f"no bf16 peak known for device_kind {kind!r}; add it to "
        f"chainermn_tpu.utils.tpu_info.PEAK_TFLOPS with its source "
        f"(known: {sorted(PEAK_TFLOPS)})")


__all__ = ["PEAK_TFLOPS", "peak_tflops"]
