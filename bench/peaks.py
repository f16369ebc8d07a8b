"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at
819 GB/s. A device kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add a "
            "row with its source to bench/peaks.py") from None
