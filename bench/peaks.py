"""Published peak rates per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (four links of
50 GB/s). The row is a copy of the program's own table, kept here so that the
yardstick cannot move with the program. A device kind that
is not in the table is an error: a roofline share against a guessed peak would
mean nothing.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_link_bytes_per_s": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
