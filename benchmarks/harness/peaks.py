"""Published peaks per accelerator, keyed by ``device_kind``.

The benchmark's own copy (the original is ``rl_tpu/utils/peaks.py``): the
yardstick may not move with the program. A device that is not here is an
error, never a default.
"""

from __future__ import annotations

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
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to benchmarks/harness/peaks.py with its source"
        ) from None
