"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``benchmarks/peaks.py`` so that the yardstick stays with the
benchmark: a change to the program cannot move it.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
(Cloud TPU system architecture) — per chip 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of inter-chip
interconnect over 4 links (50 GB/s a link).

A device that is not in the table is an error, not a default: a roofline
share computed against another chip's peak is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_gbps": 819.0,
        "ici_link_gbps": 50.0,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add the chip to benchmarks/chip/peaks.py "
            "with its source"
        ) from None
