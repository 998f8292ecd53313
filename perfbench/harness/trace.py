"""Reduction of a JAX profiler trace to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. Each TPU device is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed
HLO operation (start and duration in ns, and stats such as ``hlo_op``,
``hlo_module``; the event's name is the operation's HLO text), and its
``XLA Modules`` line one event per executable run (with its ``run_id``).
The host's threads carry the harness's ``bench.*`` annotations.

From these it computes, per device: busy time (the union of operation
intervals), each executable run with the operations it holds, the
attention kernels' time (custom calls, told apart by the shapes in their
HLO text, see :func:`kernel_of`), the operations that took most time,
and the longest idle gaps, each named by what the host was doing in it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that contain others in the trace (a layer scan's loop): their
# time is their body's, so rankings leave them out
CONTAINERS = ("while", "conditional", "call")
HOST_PREFIX = "bench."


@dataclass
class Op:
    start: int              # ns
    dur: int                # ns
    name: str               # HLO instruction (its text, on a TPU)
    run: Optional[int]      # run_id of the executable run holding it
    kernel: Optional[str]   # paged_decode|flash_prefill|append|None
    category: str           # name without its numeric suffix


@dataclass
class DeviceTrace:
    name: str
    ops: List[Op] = field(default_factory=list)

    def busy_intervals(self) -> List[Tuple[int, int]]:
        iv = sorted((o.start, o.start + o.dur) for o in self.ops if o.dur > 0)
        out: List[Tuple[int, int]] = []
        for a, b in iv:
            if out and a <= out[-1][1]:
                if b > out[-1][1]:
                    out[-1] = (out[-1][0], b)
            else:
                out.append((a, b))
        return out

    def busy_ns(self) -> int:
        return sum(b - a for a, b in self.busy_intervals())


@dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Tuple[int, int, str]]       # (start, end, bench.* name)
    span: Tuple[int, int]                  # first and last timestamp (ns)


_SHAPE = re.compile(r"(bf16|f32|s32|u32|f16|pred|s8)\[([0-9,]*)\]")


def _result_shapes(text: str) -> List[Tuple[str, int]]:
    """(dtype, rank) of each result in an HLO instruction's text."""
    head = text.split("custom-call(", 1)[0]
    head = head.split("=", 1)[1] if "=" in head else head
    return [(m.group(1), len(m.group(2).split(",")) if m.group(2) else 0)
            for m in _SHAPE.finditer(head)]


def kernel_of(name: str, long_name: str = "") -> Optional[str]:
    """Which attention kernel an operation is, from its HLO text (the
    TPU trace names each operation by that text): a Mosaic custom call
    (``tpu_custom_call``) whose results are all rank-3 bf16 pools is an
    append; one whose first result has rank 3 is the paged decode kernel
    (``[B, rep, W]``); rank 4 is the paged flash-prefill kernel
    (``[B, H, q, hd]``)."""
    text = long_name or name
    if "tpu_custom_call" not in text:
        return None
    res = _result_shapes(text)
    if not res:
        return None
    if all(d == "bf16" and r == 3 for d, r in res):
        return "append"
    if res[0][1] == 3:
        return "paged_decode"
    if res[0][1] == 4:
        return "flash_prefill"
    return None


def _category(name: str) -> str:
    """An operation's name without its HLO text and numbered or
    rematerialized suffixes: ``%fusion.12 = f32[...] fusion(...)`` and
    ``%fusion.9.remat = ...`` -> ``fusion``."""
    name = name.split("=", 1)[0].strip().lstrip("%")
    return re.sub(r"([.\-_]?\d+|\.remat\d*)+$", "", name)


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:       # pragma: no cover - stats of odd types
        return {}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _assign_runs(ops: List[Op], modules: List[Tuple[int, int, int]]):
    """Give each operation the run_id of the executable run (an event on
    the device's ``XLA Modules`` line) whose interval holds its start."""
    import bisect
    modules.sort()
    starts = [m[0] for m in modules]
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < modules[i][1]:
            o.run = modules[i][2]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    lo, hi = None, None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dt = DeviceTrace(plane.name)
            modules = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        run = _stats(ev).get("run_id")
                        s = int(ev.start_ns)
                        modules.append((s, s + int(ev.duration_ns),
                                        int(run) if run is not None else -1))
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    long_name = str(st.get("long_name", ""))
                    dt.ops.append(Op(
                        start=int(ev.start_ns), dur=int(ev.duration_ns),
                        name=ev.name, run=None,
                        kernel=kernel_of(ev.name, long_name),
                        category=_category(ev.name)))
            _assign_runs(dt.ops, modules)
            devices.append(dt)
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
    for d in devices:
        for o in d.ops:
            lo = o.start if lo is None else min(lo, o.start)
            hi = o.start + o.dur if hi is None else max(hi, o.start + o.dur)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices=devices, host=host, span=(lo or 0, hi or 0))


def runs_by_phase(dev: DeviceTrace) -> Dict[int, str]:
    """run_id -> decode|prefill|mixed, by the attention kernels it ran
    (a run with neither is some other executable)."""
    kinds: Dict[int, set] = {}
    for o in dev.ops:
        if o.run is not None and o.kernel in ("paged_decode",
                                              "flash_prefill"):
            kinds.setdefault(o.run, set()).add(o.kernel)
    out = {}
    for run, k in kinds.items():
        out[run] = "mixed" if len(k) == 2 else (
            "decode" if "paged_decode" in k else "prefill")
    return out


def run_time_ns(dev: DeviceTrace) -> Dict[int, int]:
    """Device time of each executable run: the union of its operations'
    intervals (from the first operation's start to the last's end,
    less the gaps in between)."""
    per: Dict[int, List[Tuple[int, int]]] = {}
    for o in dev.ops:
        if o.run is not None:
            per.setdefault(o.run, []).append((o.start, o.start + o.dur))
    out = {}
    for run, iv in per.items():
        iv.sort()
        tot, end = 0, None
        for a, b in iv:
            if end is None or a > end:
                tot += b - a
                end = b
            elif b > end:
                tot += b - end
                end = b
        out[run] = tot
    return out


def kernel_ns(dev: DeviceTrace, kernel: str) -> int:
    return sum(o.dur for o in dev.ops if o.kernel == kernel)


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time, by category, summed
    over devices and averaged per device (seconds)."""
    tot: Dict[str, int] = {}
    for d in tr.devices:
        for o in d.ops:
            if o.kernel is None and o.category in CONTAINERS:
                continue
            key = o.kernel or o.category
            tot[key] = tot.get(key, 0) + o.dur
    nd = max(len(tr.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def idle_gaps(tr: Trace, window: Tuple[int, int],
              n: int = 10) -> List[List]:
    """The longest idle gaps on any device inside ``window`` (ns), each
    named by the innermost ``bench.*`` host span covering its middle."""
    gaps = []
    for d in tr.devices:
        prev = window[0]
        for a, b in d.busy_intervals():
            if b <= window[0]:
                continue
            a = max(a, window[0])
            if a > prev:
                gaps.append((a - prev, prev, a))
            prev = max(prev, min(b, window[1]))
            if prev >= window[1]:
                break
        if prev < window[1]:
            gaps.append((window[1] - prev, prev, window[1]))
    gaps.sort(reverse=True)
    out = []
    for g, a, b in gaps[:n]:
        mid = (a + b) // 2
        cover = [(e - s, nm) for s, e, nm in tr.host if s <= mid <= e]
        name = min(cover)[1] if cover else "host: no bench span"
        out.append([name, g / 1e9])
    return out
