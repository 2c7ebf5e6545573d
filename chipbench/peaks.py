"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
per chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at
819 GB/s, and 1,600 Gbit/s of inter-chip interconnect.

A device kind missing from the table is an error, never a default: a share
of a peak computed against the wrong chip is worse than none.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # jax reports a v5e chip as "TPU v5 lite"
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in ``PEAKS``."""


def lookup(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises `UnknownDevice` if it has none."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
