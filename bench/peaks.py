"""Published peaks of each accelerator, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s in
bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s). A device that is
not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
