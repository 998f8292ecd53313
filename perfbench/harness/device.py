"""Device metrics of the traced window: utilization of the chip's peak,
kernels' roofline shares, the idle share.

Work comes from the launches the harness saw issued while the trace ran
(``system.Probe``: rows, contexts, chunks); time comes from the trace.
Totals are over all chips: FLOPs over (chip-seconds x peak).
"""
from __future__ import annotations

from typing import Dict, Optional

from . import trace as tr
from . import work


def phase_ns(rec, phases) -> int:
    """Device time of the executable runs of the given phases, summed
    over chips."""
    tot = 0
    for dev in rec.traced.trace.devices:
        kinds = tr.runs_by_phase(dev)
        times = tr.run_time_ns(dev)
        tot += sum(t for run, t in times.items() if kinds.get(run) in phases)
    return tot


def decode_flops(rec) -> float:
    m = rec.dims
    return sum(work.decode_row_flops(m, ctx)
               for l in rec.traced_launches() for _, ctx in l.decode)


def prefill_flops(rec) -> float:
    m = rec.dims
    return sum(work.prefill_chunk_flops(m, p, c, f)
               for l in rec.traced_launches() for _, p, c, f in l.prefill)


def mfu(rec, kind: str) -> Optional[float]:
    """Model FLOPs of the traced window's decode rows (or prompt chunks)
    over the device time of the steps that ran them, x the bf16 peak,
    in %."""
    if rec.traced is None:
        return None
    if kind == "decode":
        flops, ns = decode_flops(rec), phase_ns(rec, ("decode", "mixed"))
    else:
        flops, ns = prefill_flops(rec), phase_ns(rec, ("prefill", "mixed"))
    if ns <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (ns * 1e-9 * rec.peaks.flops_bf16)


def _groups(launch, rows_attr: str) -> Dict[int, list]:
    g: Dict[int, list] = {}
    for row in getattr(launch, rows_attr):
        g.setdefault(row[0], []).append(row)
    return g


def kernel_roofline(rec, kernel: str) -> Optional[float]:
    """Least time the chip could take for the kernel's calls in the
    traced window, over the time the trace shows them taking, in %. One
    call per layer per engine group covers the group's rows; a group of
    ``merge`` chips splits its heads, so each chip does 1/merge of it."""
    if rec.traced is None:
        return None
    spent = sum(tr.kernel_ns(d, kernel) for d in rec.traced.trace.devices)
    if spent <= 0:
        return None
    m, pk = rec.dims, rec.peaks
    least = 0.0
    for l in rec.traced_launches():
        merge = l.island[2]
        if kernel == "paged_decode":
            per = [[work.decode_kernel_work(m, ctx) for _, ctx in rows]
                   for rows in _groups(l, "decode").values()]
        else:
            per = [[work.prefill_kernel_work(m, p, c) for _, p, c, _ in rows]
                   for rows in _groups(l, "prefill").values()]
        for rows in per:
            f = sum(x[0] for x in rows) / merge
            b = sum(x[1] for x in rows) / merge
            least += merge * m.layers * work.roofline_s(
                f, b, pk.flops_bf16, pk.hbm_bw)
    if least <= 0:
        return None
    return 100.0 * least / (spent * 1e-9)


def busy_s(rec) -> Optional[float]:
    """Seconds in which an operation ran, averaged over chips."""
    if rec.traced is None or not rec.traced.trace.devices:
        return None
    devs = rec.traced.trace.devices
    return sum(d.busy_ns() for d in devs) / len(devs) / 1e9


def idle_share(rec) -> Optional[float]:
    b = busy_s(rec)
    if b is None or rec.traced.seconds <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - b / rec.traced.seconds)
