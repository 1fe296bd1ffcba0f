"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Only published numbers go here.  A device whose ``device_kind`` is not in
the table is an error, never a default: a roofline share against a guessed
peak would not mean anything.
"""
from __future__ import annotations

__all__ = ["PEAKS", "UnknownDevice", "peaks_for"]

# Google Cloud documentation, "TPU v5e" (system architecture, chip
# specifications): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "source": 'Google Cloud documentation, "TPU v5e"',
        "flops": {"bf16": 197e12, "int8": 393e12},
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(RuntimeError):
    """The device has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    """The peaks entry of ``device_kind``; raises :class:`UnknownDevice`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
