"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Copied from the program's ``serving/hardware.py`` (the table and its
lookup, without the simulator's assumed efficiencies) so the yardstick
stays here. Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s
in bf16 (393 TOP/s in int8), 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    name: str
    flops_bf16: float       # FLOP/s per chip
    hbm_bw: float           # bytes/s per chip
    hbm_bytes: float        # bytes per chip


V5E = Peaks(name="tpu-v5e", flops_bf16=197e12, hbm_bw=819e9,
            hbm_bytes=16e9)

PEAKS = {"TPU v5 lite": V5E}


def peaks_for(device_kind: str) -> Peaks:
    """A chip's peaks; a kind not in the table is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
