"""Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.  A copy of
dynamo_tpu/runtime/device.py DEVICE_PEAKS, kept here so that the program
cannot move the yardstick.  A kind that is not in the table is an error,
never a default."""

from __future__ import annotations

from typing import Dict

DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def device_peaks(kind: str) -> Dict[str, float]:
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device kind {kind!r}; add it, with "
            "its source, to benchmark/lib/peaks.py") from None
