#!/usr/bin/env python3
"""What the host was doing while the device waited, in one traced run:
the program's ``fs.*`` spans against the device's idle gaps.

    python3 perfbench/tools/span_gaps.py .perfbench/<cell>/trace [out.json]

Prints, as JSON: the device's idle ms and the part outside every
``fs.tick``; the longest idle gaps, each named by the innermost
``fs.*`` span over its middle, and the idle ms summed by that span;
per span name its count and median, longest and total ms; the tick's
and the step's own time (``sched_host_ms_per_tick``,
``stage_host_ms_per_step``); the runs per XLA module name, and how the
phase in a step program's name (``jit_fs_<phase>_...``) agrees with
``trace.runs_by_phase``. A trace from a program without ``fs.*`` spans
gives the device's numbers alone.
"""
from __future__ import annotations

import json
import re
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def analyse(log_dir: str) -> dict:
    from harness import spans as sp
    from harness import trace as tr
    path = tr.find_xplane(log_dir)
    t = tr.load(path)
    evs = sp.load(path)
    lo, hi = t.span
    nd = max(len(t.devices), 1)
    idle = sum(b - a for d in t.devices for a, b in sp.idle_gaps(d, t.span))
    longest, by_span = sp.idle_by_span(t, evs)
    out = {"window_ms": (hi - lo) / 1e6, "chips": len(t.devices),
           "idle_ms": idle / nd / 1e6, "longest_gaps_ms": longest,
           "idle_ms_by_span": {k: v / nd for k, v in by_span.items()}}
    share = sp.idle_outside_share(t, evs, "fs.tick", (hi - lo) / 1e9)
    if share is not None:
        out["idle_outside_tick_ms"] = share / 100 * (hi - lo) / 1e6
    out["tick_self_ms"] = sp.median_self_ms(evs, "fs.tick",
                                            ("fs.step", "fs.rebind"))
    out["step_self_ms"] = sp.median_self_ms(evs, "fs.step",
                                            ("fs.wait", "fs.harvest"))
    durs: dict = {}
    for s in evs:
        durs.setdefault(s.name, []).append((s.end - s.start) / 1e6)
    out["spans_ms"] = {k: {"n": len(v), "median": sorted(v)[len(v) // 2],
                           "max": max(v), "sum": sum(v)}
                       for k, v in sorted(durs.items())}
    names = sp.modules(path)
    out["runs_by_module"] = dict(Counter(
        n for runs in names.values() for n in runs.values()).most_common())
    agree: Counter = Counter()
    for d in t.devices:
        runs = names.get(d.name, {})
        for run, phase in tr.runs_by_phase(d).items():
            m = re.search(r"fs_(decode|prefill|mixed)_", runs.get(run, ""))
            agree[f"{phase}|{m.group(1) if m else runs.get(run, '?')}"] += 1
    out["phase_vs_module"] = dict(agree)
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    text = json.dumps(analyse(argv[0]), indent=1)
    if len(argv) > 1:
        Path(argv[1]).write_text(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
