"""Published peaks per accelerator, keyed by ``device_kind``.

The ONE table every utilization or roofline figure divides by
(``bench.py``, ``chip_smoke.py``). A device that is not in it is an
error, never a default: an invented peak turns every MFU printed under
it into noise that reads like a measurement. Add a device together with
the source of its numbers. No jax import here — callers pass the
``device_kind`` string jax reported.
"""

from __future__ import annotations

__all__ = ["DEVICE_PEAKS", "device_peaks"]

DEVICE_PEAKS: dict[str, dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s per chip
    "TPU v5 lite": {
        "flops": 197e12,
        "int8_ops": 393e12,
        "bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device_kind: str) -> dict[str, float]:
    """The table row of ``device_kind`` (``jax.devices()[0].device_kind``);
    raises ``KeyError`` naming the device when it has none."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to "
            "rl_tpu/utils/peaks.py with its source"
        ) from None
