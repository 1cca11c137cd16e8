"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

Copied from the program's `analysis/roofline.PEAKS` so that no change to
the program can move the yardstick. A device that is not in the table is
an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
