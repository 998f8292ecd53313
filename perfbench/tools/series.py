#!/usr/bin/env python3
"""Run a cell several times, one process after another, and summarize.

    python3 perfbench/tools/series.py --workload stablelm-1.6b.chat \
        --seconds 51 --traced 901,902,903 --seeds 11,12,13,14,15,16 \
        --sets 2 --out chiprun_out/chat

Runs ``perfbench/run.py`` as a child for every traced seed (with
``--trace 1``), then ``--sets`` times over ``--seeds`` (``--trace 0``),
in that order (the traced seeds last with ``--traced-last``). This process never imports JAX, so each child has the
chip to itself. Each run's output goes to ``<out>/<n>.log``; the result
lines go to ``<out>/results.jsonl``; the end prints, per set and per
metric, the median and the spread (interquartile range over the
median, by ``statistics.quantiles(values, n=4)``), also without the
run farthest from the set's median, leaving out each set's first run
for ``setup_s`` where it compiled.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.stats import spread  # noqa: E402


def one(args, seed: int, trace: int, out: Path, n: int) -> dict:
    cmd = [sys.executable, "perfbench/run.py", "--workload", args.workload,
           "--seed", str(seed), "--seconds", str(args.seconds),
           "--trace", str(trace)]
    t0 = time.time()
    p = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=args.timeout)
    wall = time.time() - t0
    (out / f"{n:02d}.log").write_text(
        f"$ {' '.join(cmd)}\nrc {p.returncode} wall {wall:.1f}\n"
        f"--- stdout\n{p.stdout}\n--- stderr\n{p.stderr[-20000:]}\n")
    line = {}
    if p.returncode == 0 and p.stdout.strip():
        try:
            line = json.loads(p.stdout.strip().splitlines()[-1])
        except ValueError:
            line = {}
    rec = {"n": n, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": wall, "line": line}
    with open(out / "results.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    m = {k: v["value"] for k, v in line.get("metrics", {}).items()}
    print(f"run {n:02d} seed {seed} trace {trace} rc {p.returncode} wall "
          f"{wall:.1f}s correct {line.get('correct')} "
          f"attempted {line.get('attempted')} failed {line.get('failed')} "
          f"{json.dumps(m)} compared {json.dumps(line.get('compared'))}",
          flush=True)
    if p.returncode != 0:
        print(p.stderr[-3000:], flush=True)
    return rec


def trimmed(v) -> float:
    """The spread without the value farthest from the median, where
    that narrows it."""
    if len(v) < 3:
        return float("nan")
    med = statistics.median(v)
    far = max(range(len(v)), key=lambda i: abs(v[i] - med))
    return min(spread(v), spread(v[:far] + v[far + 1:]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--traced", default="")
    ap.add_argument("--traced-last", action="store_true")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    n = 0
    traced = [int(x) for x in args.traced.split(",") if x]
    for s in ([] if args.traced_last else traced):
        if one(args, s, 1, out, n)["rc"] != 0:
            return 1
        n += 1
    seeds = [int(x) for x in args.seeds.split(",") if x]
    sets = []
    for _ in range(args.sets):
        runs = []
        for s in seeds:
            runs.append(one(args, s, 0, out, n))
            n += 1
            if runs[-1]["rc"] != 0:
                return 1
        sets.append(runs)
    for s in (traced if args.traced_last else []):
        if one(args, s, 1, out, n)["rc"] != 0:
            return 1
        n += 1
    for i, runs in enumerate(sets):
        names = sorted({k for r in runs
                        for k in r["line"].get("metrics", {})})
        for k in names:
            v = [r["line"]["metrics"][k]["value"] for r in runs
                 if k in r["line"].get("metrics", {})]
            if k == "setup_s" and i == 0 and n and sets[0][0]["n"] == 0:
                v = v[1:]
            print(f"set {i} {k}: n {len(v)} median "
                  f"{statistics.median(v) if v else float('nan'):.6g} "
                  f"spread {spread(v) if len(v) > 1 else float('nan'):.6g}"
                  f" trimmed {trimmed(v):.6g} values {v}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
