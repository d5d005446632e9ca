"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A float32
matrix product or convolution at JAX's default precision runs as bf16
passes on the MXU, so the bf16 peak bounds it.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(platform: str, device_kind: str) -> dict:
    """The peaks of one chip. A platform other than the TPU, or a kind not
    in the table, is refused: no number is made up for it."""
    if platform != "tpu":
        raise UnknownDevice(f"platform {platform!r} is not a TPU; the "
                            f"benchmark measures only on the chip")
    if device_kind not in PEAKS:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; add them to peaks.py with "
                            f"their source")
    return PEAKS[device_kind]
