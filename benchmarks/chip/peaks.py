"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's table so that no change to the program can
move the peaks it is judged by. "TPU v5 lite" is TPU v5e. Source:
Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,          # FLOP/s
        "hbm_bytes": 16e9,             # B
        "hbm_bw": 819e9,               # B/s
        "ici_bw_per_link": 50e9,       # B/s
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to PEAKS with its "
                       f"source") from None
