"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A device kind that is missing from :data:`PEAKS` is an error, never a
default: a share of a peak computed against the wrong chip is worse than
no number.
"""
from __future__ import annotations

#: device_kind -> peaks of ONE chip.  Source: Google Cloud documentation,
#: "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add the "
            f"chip to benchmarks/chip/yardstick/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None
