"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
A device that is not in the table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
