"""Per-chip hardware peaks: the simulator's cost model and the roofline
analysis read them, and anything that reports an on-device rate looks
its chip up here by ``device_kind``."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops_bf16: float              # FLOP/s per chip
    hbm_bw: float                       # B/s per chip
    hbm_bytes: float                    # per chip
    ici_bw: float                       # B/s per link
    ici_latency: float = 1e-6           # per-hop collective latency (s)
    # cold-start modeling (static baselines; paper Table 2 cold restarts)
    weight_load_bw: float = 2e9         # B/s per chip from host/storage
    startup_fixed: float = 20.0         # process/compile/init seconds
    # simulator assumptions, not measurements: achievable fraction of
    # the peaks (to be fitted to chip runs)
    mfu_prefill: float = 0.5
    mfu_decode_bw: float = 0.7


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
# int8), 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
# (modeled as 4 links of 50 GB/s).
V5E = Hardware(name="tpu-v5e", peak_flops_bf16=197e12, hbm_bw=819e9,
               hbm_bytes=16e9, ici_bw=50e9)

# paper's evaluation hardware, for reproducing the published numbers
H200 = Hardware(name="h200", peak_flops_bf16=989e12, hbm_bw=4.8e12,
                hbm_bytes=141e9, ici_bw=450e9, ici_latency=2e-6,
                weight_load_bw=1.5e9, startup_fixed=30.0)

# jax ``Device.device_kind`` -> published peaks of that chip
PEAKS = {"TPU v5 lite": V5E}


def peaks_for(device_kind: str) -> Hardware:
    """The published peaks of a chip; a kind not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
