#!/usr/bin/env python3
"""Print the structure of a profiler trace: its planes and lines, the
most frequent and the longest events of each line, and the stats of a
few of them (custom calls first). For looking at a trace by hand before
writing a reduction against it.

    python3 perfbench/tools/trace_dump.py <dir with .xplane.pb> [out.txt]
"""
from __future__ import annotations

import sys
from collections import Counter, defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def dump(log_dir: str, out=sys.stdout) -> None:
    from jax.profiler import ProfileData
    from harness.trace import find_xplane
    pd = ProfileData.from_file(find_xplane(log_dir))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events", file=out)
            if not evs:
                continue
            n = Counter(e.name for e in evs)
            t = defaultdict(float)
            for e in evs:
                t[e.name] += e.duration_ns
            for name, c in n.most_common(8):
                print(f"    freq {c:6d}  {t[name] / 1e6:10.3f} ms  {name}",
                      file=out)
            for name, v in sorted(t.items(), key=lambda kv: -kv[1])[:8]:
                print(f"    time {n[name]:6d}  {v / 1e6:10.3f} ms  {name}",
                      file=out)
            shown = 0
            for e in sorted(evs, key=lambda e: "custom" not in e.name):
                if shown >= 4:
                    break
                st = {k: (str(v)[:600]) for k, v in dict(e.stats).items()}
                print(f"    event {e.name!r} dur {e.duration_ns} stats "
                      f"{st}", file=out)
                shown += 1


if __name__ == "__main__":
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            dump(sys.argv[1], f)
    else:
        dump(sys.argv[1])
