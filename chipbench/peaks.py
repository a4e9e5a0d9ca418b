"""Published per-chip peaks, keyed by ``jax.Device.device_kind`` (a copy of
the repo's ``launch/roofline.py:PEAKS``).  A kind not in the table is an
error, never a default.

TPU v5e (``"TPU v5 lite"``): Google Cloud documentation, "TPU v5e" —
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published {what!r} for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
