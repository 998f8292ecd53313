"""The program's own host spans in a run's profiler trace.

The serving program marks its host work with ``fs.*`` spans
(``src/repro/core/tracing.py``): the scheduler tick, the policy, each
step's staging and launch, window waits, harvests, drains, the stream
pump and HTTP writes. They are on while the profiler records, so a
traced run's ``.xplane.pb`` holds them on the serve thread, on the
device planes' clock. ``trace.load`` keeps only the harness's own
``bench.*`` spans; this module reads the program's from the same file.

A program without these spans (an older commit) leaves the trace
without them, and every reader here then returns None.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "fs."


@dataclass
class Span:
    start: int                  # ns, on the trace's clock
    end: int
    name: str
    args: Dict
    thread: Tuple[str, str]     # (plane, line) the span ran on


def load(path: str) -> List[Span]:
    """Every ``fs.*`` span of the host planes, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append(Span(s, s + int(ev.duration_ns), ev.name,
                                    dict(ev.stats), (plane.name, line.name)))
    out.sort(key=lambda sp: (sp.start, -sp.end))
    return out


_loaded: Dict[str, List[Span]] = {}


def of_run(rec) -> List[Span]:
    """The spans of a traced run. ``run.py`` writes a cell's trace under
    ``.perfbench/<cell>/trace`` of the directory it runs from."""
    from .trace import find_xplane
    if rec.traced is None:
        return []
    try:
        path = find_xplane(str(Path.cwd() / ".perfbench" / rec.cell
                               / "trace"))
    except FileNotFoundError:
        return []
    if path not in _loaded:
        _loaded[path] = load(path)
    return _loaded[path]


def union(iv: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def self_ns(spans: Sequence[Span], outer: str,
            minus: Sequence[str]) -> List[int]:
    """For each ``outer`` span, its duration less the time of the
    ``minus`` spans it contains on its own thread."""
    out = []
    for o in spans:
        if o.name != outer:
            continue
        inner = [(s.start, s.end) for s in spans
                 if s.name in minus and s.thread == o.thread
                 and o.start <= s.start and s.end <= o.end]
        out.append(o.end - o.start - sum(b - a for a, b in union(inner)))
    return out


def median_self_ms(spans: Sequence[Span], outer: str,
                   minus: Sequence[str]) -> Optional[float]:
    vals = sorted(self_ns(spans, outer, minus))
    if not vals:
        return None
    n = len(vals)
    mid = vals[n // 2] if n % 2 else (vals[n // 2 - 1] + vals[n // 2]) / 2
    return mid / 1e6


def idle_outside_share(trace, spans: Sequence[Span], name: str,
                       seconds: float) -> Optional[float]:
    """Device time covered neither by an operation nor by a ``name``
    span, over the traced window (``seconds``), in %, from the chips'
    mean: ``device_idle_share`` with those spans counted as busy, so
    never above it. Spans are clipped to the device trace's span."""
    lo, hi = trace.span
    marks = [(max(s.start, lo), min(s.end, hi)) for s in spans
             if s.name == name]
    if not trace.devices or not marks or seconds <= 0:
        return None
    covered = [sum(b - a for a, b in union(d.busy_intervals() + marks))
               for d in trace.devices]
    mean = sum(covered) / len(covered) / 1e9
    return 100.0 * max(0.0, 1.0 - mean / seconds)


def idle_gaps(dev, window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The device's idle intervals inside ``window`` (ns)."""
    lo, hi = window
    out, prev = [], lo
    for a, b in dev.busy_intervals():
        a, b = max(a, lo), min(b, hi)
        if a >= b:
            continue
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    if prev < hi:
        out.append((prev, hi))
    return out


def idle_by_span(trace, spans: Sequence[Span], n: int = 10):
    """Every idle gap of every chip in the device trace's span, put down
    to the innermost ``fs.*`` span covering the gap's middle (``none``
    where no span does). Returns the ``n`` longest gaps as [ms, span]
    and the idle ms summed by span, longest first."""
    gaps = sorted(((b - a, (a + b) // 2) for d in trace.devices
                   for a, b in idle_gaps(d, trace.span)),
                  key=lambda g: g[1])
    order = sorted(spans, key=lambda sp: sp.start)
    named, active, i = [], [], 0
    for g, mid in gaps:
        while i < len(order) and order[i].start <= mid:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp.end >= mid]
        inner = min(active, key=lambda sp: sp.end - sp.start, default=None)
        named.append((g, inner.name if inner else "none"))
    by: Dict[str, float] = {}
    for g, name in named:
        by[name] = by.get(name, 0.0) + g / 1e6
    longest = sorted(named, key=lambda x: -x[0])[:n]
    return ([[g / 1e6, name] for g, name in longest],
            dict(sorted(by.items(), key=lambda kv: -kv[1])))


def modules(path: str) -> Dict[str, Dict[int, str]]:
    """Per device plane, run_id -> the name of the XLA module it ran
    (``jit_fs_decode_n1m1`` for a step program of the pool)."""
    from jax.profiler import ProfileData
    out: Dict[str, Dict[int, str]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                run = dict(ev.stats).get("run_id")
                if run is not None:
                    out.setdefault(plane.name, {})[int(run)] = \
                        ev.name.split("(")[0]
    return out


def _rows(v) -> List[List[int]]:
    if v is None or v == "":
        return []
    return [[int(x) for x in row.split("/")] for row in str(v).split(":")]


def launches(spans: Sequence[Span]) -> List:
    """The step launches the ``fs.step`` spans describe, as the harness's
    ``system.Launch`` (``t0``/``t1`` in seconds on the trace's clock)."""
    from .system import Launch
    out = []
    for s in spans:
        if s.name != "fs.step":
            continue
        a = s.args
        out.append(Launch(
            phase=str(a["phase"]), t0=s.start / 1e9, t1=s.end / 1e9,
            island=tuple(_rows(a["island"])[0]),
            decode=[(l, c) for l, c in _rows(a.get("dec"))],
            prefill=[(l, p, c, bool(f))
                     for l, p, c, f in _rows(a.get("pre"))]))
    return out
