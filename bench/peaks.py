"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

A device that is not in the table is an error, never a default: a share of
a peak against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises :class:`UnknownDevice` for a
    chip the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; the table "
            f"in bench/peaks.py lists {sorted(PEAKS)}") from None
