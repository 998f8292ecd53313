#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates, one
window each, in one process on the chip.

    python3 perfbench/tools/sweep.py --workload stablelm-1.6b.chat \
        --seed 11 --rates 1,2,3,4 --seconds 25 [--stream 0] \
        [--trace-dump chiprun_out/trace.txt]

The rate of traffic stream ``--stream`` is set to each value in turn;
each window follows the last, its traffic starting with the file's lead-in.
Per rate it prints TTFT and TPOT percentiles with their sample counts,
queue wait, completed requests and output tokens per second. With
``--trace-dump`` the first window is traced: the per-layer metrics are
printed and the trace's structure is written to the file. Each
window's sample is checked against the reference at the end.
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--stream", type=int, default=0)
    ap.add_argument("--trace-dump", default="")
    ap.add_argument("--trace-window", type=int, default=0,
                    help="which window --trace-dump traces")
    ap.add_argument("--warm", choices=("replay", "none"), default="replay",
                    help="replay: serve a replay of the cell's traffic "
                    "first, as a run does; none: compile on first use")
    args = ap.parse_args()
    t_start = time.time()
    root = Path.cwd()
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(root / "src"))
    from harness import cell as cm
    from harness import correct, loadgen, system
    from harness import weights as wts
    from harness.spec import load_benchmark, load_reader, resolve_cell
    from harness.stats import percentile
    cell = resolve_cell(load_benchmark(root), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.serving.server import ServeHTTP
    devs = jax.devices()
    counter = system.CompileCounter()
    door, loop, probe = system.build(cell, args.seed)
    engine = door.sched.backend
    rates = [float(x) for x in args.rates.split(",")]
    out_dir = root / ".perfbench" / "sweep"
    windows = []

    async def run_all():
        srv = await ServeHTTP(loop).start(cm.HOST, 0)
        gen = loadgen.Generator(cm.HOST, srv.port)
        try:
            if args.warm == "replay":
                await cm.warm_up(gen, door, loop, counter,
                                 cell.traffic_data, args.seed, args.seconds)
            print(f"setup {time.time() - t_start:.1f} s "
                  f"({counter.compiles} compiles or cache loads, "
                  f"{counter.compile_s:.1f} s); kv pool "
                  f"{engine.geom.num_blocks} blocks", flush=True)
            for i, rate in enumerate(rates):
                tr = copy.deepcopy(cell.traffic_data)
                tr["streams"][args.stream]["rate"] = rate
                tdir = str(out_dir / "trace") if (
                    args.trace_dump and i == args.trace_window) else None
                res = await cm.measure(gen, door, probe, counter, tr,
                                       args.seed + i, args.seconds, tdir)
                res["trace_dir"] = tdir
                rec = cm.record_of(res, cell, door, probe, args.seconds,
                                   0.0, devs)
                windows.append((rate, rec, res))
                iw = rec.in_window
                ttft = [r.ttft for r in iw if r.ttft is not None]
                tpot = [r.tpot for r in iw if r.tpot is not None]
                late = [r.late for r in rec.records if r.late is not None]
                row = {
                    "rate": rate, "sent": len(iw),
                    "done": sum(r.state == "done" for r in iw),
                    "ttft_p50_ms": 1e3 * (percentile(ttft, 50) or 0),
                    "ttft_p90_ms": 1e3 * (percentile(ttft, 90) or 0),
                    "ttft_n": len(ttft),
                    "tpot_p50_ms": 1e3 * (percentile(tpot, 50) or 0),
                    "tpot_p90_ms": 1e3 * (percentile(tpot, 90) or 0),
                    "tpot_n": len(tpot),
                    "queue_p90_ms": 1e3 * (percentile(rec.queue_waits, 90)
                                           or 0),
                    "out_tok_s": load_reader("output_tok_s")(rec),
                    "late_p50_ms": 1e3 * (percentile(late, 50) or 0),
                    "late_max_ms": 1e3 * max(late, default=0),
                    "compiles": res["c1"][0] - res["c0"][0]}
                print("SWEEP " + json.dumps(row), flush=True)
                progs = sorted(probe.programs.items(), key=lambda kv: -kv[1])
                print(f"  programs {len(progs)}: " + "; ".join(
                    f"{k} x{v}" for k, v in progs), flush=True)
                probe.programs.clear()
                if tdir is not None:
                    for m in cell.per_layer:
                        print(f"  traced {m.name}: "
                              f"{load_reader(m.name)(rec)}", flush=True)
                    print("  breakdown " + json.dumps(rec.breakdown),
                          flush=True)
        finally:
            gen.close()
            await srv.stop()

    asyncio.run(run_all())
    if args.trace_dump:
        from tools.trace_dump import dump
        Path(args.trace_dump).parent.mkdir(parents=True, exist_ok=True)
        with open(args.trace_dump, "w") as f:
            dump(str(out_dir / "trace"), f)
    print("memory peak " + str(max((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0) for d in devs)), flush=True)
    system.free_pools(engine)
    w = wts.named(engine.params)
    for rate, rec, _res in windows:
        sample = correct.pick(rec.records, args.seed,
                              must=cm.must_cover(rec))
        t0 = time.time()
        cmp = correct.compare(w, cell.config_data, sample,
                              cell.config_data["vocab_size"])
        cmp["rate"] = rate
        cmp["ref_s"] = time.time() - t0
        print("CHECK " + json.dumps(cmp), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
