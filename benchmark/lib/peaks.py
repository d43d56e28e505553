"""Published peaks of the devices the benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in benchmark/lib/peaks.py") from None
