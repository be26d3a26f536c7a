"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip.  A device whose kind is not in the table
is an error: no metric is computed against a guessed peak.
"""
from __future__ import annotations

PEAKS = {
    # JAX reports a v5e chip as "TPU v5 lite".
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in ``PEAKS``."""


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
