"""One run of one cell: set-up, the measured window over HTTP, the
check of what was served, and the metrics.

Timeline (seconds, window start = 0): the process starts at ``-setup``;
set-up builds the stack, starts the server and warms it by serving a
replay of the cell's own traffic (another schedule from the same seed)
for ``warm_s`` and on until no program has been compiled or loaded for
``warm_quiet_s`` (both from the traffic file), then
cancels what the replay left and waits for an idle server; the cell's
traffic starts at ``-lead_s`` from that idle server; the window is
``[0, seconds)``; afterwards the harness waits (at most ``drain_s``) for
the window's requests to finish, stops the server, reads the device's
memory peak, frees the KV pools and runs the reference on a sample.
Everything before the window, the lead-in included, is set-up. With
``trace`` the profiler records ``TRACE_S`` seconds in the middle of the
window.
"""
from __future__ import annotations

import asyncio
import shutil
import sys
import time
from typing import Dict, List, Optional

from . import loadgen, system, traffic
from .record import RunRecord, TraceWindow
from .stats import percentile

TRACE_S = 10.0
HOST = "127.0.0.1"
WARM_SALT = 1           # the warm-up replay's schedule: plan(..., salt=1)
WARM_CAP_S = 180.0     # and in any case after this long


def say(msg: str) -> None:
    print(msg, flush=True)


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _live(door) -> bool:
    from repro.core.task_pool import TERMINAL_STATES
    return bool(door.sched.running) or any(
        r.state not in TERMINAL_STATES for r in door.requests.values())


async def warm_up(gen, door, sloop, counter, tr: Dict, seed: int,
                  seconds: float) -> Dict:
    """Serve the first ``warm_s`` seconds of a replay of the traffic
    (the same mix under another schedule), and go on serving until no
    program has been compiled or loaded for ``warm_quiet_s``; then close
    the replay's streams, cancel what is still live, and wait until the
    server is idle. Returns what the set-up line prints."""
    planned = traffic.plan(tr, seconds, seed, salt=WARM_SALT)
    warm_s = float(tr.get("warm_s", 30.0))
    quiet_s = float(tr.get("warm_quiet_s", 8.0))
    t_first = planned[0].t if planned else 0.0
    reqs = [p for p in planned if p.t - t_first < warm_s]
    t0 = time.monotonic()
    c0 = counter.snap()[0]
    recs, job = gen.start(reqs, t0 - t_first)
    last, quiet_from = c0, t0
    while True:
        await asyncio.sleep(0.25)
        now = time.monotonic()
        c = counter.snap()[0]
        if c != last:
            last, quiet_from = c, now
        if (now - t0 >= warm_s and now - quiet_from >= quiet_s) \
                or now - t0 >= WARM_CAP_S:
            break
    t_serve = time.monotonic() - t0
    gen.cancel(job)
    t1 = time.monotonic()
    while (_live(door) and time.monotonic() - t1 < 60.0) \
            or time.monotonic() - t1 < 0.5:
        for rid in list(door.requests):
            sloop.abort(rid)
        await asyncio.sleep(0.05)
    return {"served_s": t_serve, "settle_s": time.monotonic() - t1,
            "sent": sum(r.late is not None for r in recs),
            "done": sum(r.state == "done" for r in recs),
            "compiles": counter.snap()[0] - c0}


async def measure(gen, door, probe, counter, tr: Dict, seed: int,
                  seconds: float, trace_dir: Optional[str] = None) -> Dict:
    """Offer ``tr``'s traffic through the generator ``gen`` and measure
    one window; returns the records and the counters at the window's
    ends."""
    from jax import profiler
    engine = door.sched.backend
    planned = traffic.plan(tr, seconds, seed)
    out: Dict = {"lead_c0": counter.snap()}
    anchor = time.monotonic() + float(tr.get("lead_s", 0.0))
    recs, job = gen.start(planned, anchor)
    out["records"] = recs

    async def until(t):
        d = anchor + t - time.monotonic()
        if d > 0:
            await asyncio.sleep(d)

    await until(0.0)
    out["win_wall"] = time.time()
    out["win_pc"] = time.perf_counter()
    out["c0"] = counter.snap()
    out["s0"] = system.sync_counts(engine)
    probe.launches.clear()
    # the rate's clock starts once the steps launched before the window
    # have run, and stops once every step the window launched has run
    out["gen_t0"] = probe.settle()
    probe.recording = True
    if trace_dir is not None:
        await until(seconds / 2 - TRACE_S / 2)
        profiler.start_trace(trace_dir)
        out["trace_t0"] = time.perf_counter()
        await until(seconds / 2 + TRACE_S / 2)
        out["trace_t1"] = time.perf_counter()
        profiler.stop_trace()
    await until(seconds)
    probe.recording = False
    out["gen_t1"] = probe.settle()
    out["c1"] = counter.snap()
    out["s1"] = system.sync_counts(engine)
    # wait for the window's requests (an answer that comes late is late)
    mine = [r for r in recs if 0.0 <= r.plan.t < seconds]
    deadline = anchor + seconds + float(tr.get("drain_s", 60.0))
    while not gen.done(mine) and time.monotonic() < deadline:
        await asyncio.sleep(0.05)
    gen.cancel(job)
    return out


def record_of(res: Dict, cell, door, probe, seconds: float,
              setup_s: float, devs) -> RunRecord:
    from .peaks import peaks_for
    from .work import Dims
    rec = RunRecord(cell=cell.name, window_s=seconds, setup_s=setup_s,
                    records=res["records"],
                    dims=Dims.from_config(cell.config_data),
                    peaks=peaks_for(devs[0].device_kind)
                    if devs[0].platform == "tpu" else None,
                    chips=len(devs), launches=list(probe.launches),
                    rebinds=[(t0 - res["win_pc"], dt)
                             for t0, dt in probe.rebinds
                             if 0.0 <= t0 - res["win_pc"] < seconds],
                    sync_delta={k: res["s1"][k] - res["s0"][k]
                                for k in res["s0"]},
                    gen_s=res["gen_t1"] - res["gen_t0"])
    reqs = door.requests
    for r in rec.in_window:
        q = reqs.get(r.server_id) if r.server_id else None
        if q is not None and q.sched_t is not None:
            rec.queue_waits.append(q.sched_t - q.arrival)
    if "trace_t0" in res:
        from .trace import find_xplane, idle_gaps, load, top_ops
        t = load(find_xplane(res["trace_dir"]))
        rec.traced = TraceWindow(t, res["trace_t0"], res["trace_t1"])
        rec.breakdown = {"device_ops": top_ops(t),
                         "idle_gaps": idle_gaps(t, t.span)}
    return rec


def report_window(rec: RunRecord, res: Dict) -> None:
    win_compiles = res["c1"][0] - res["c0"][0]
    say(f"compiles inside the window: {win_compiles} "
        f"({res['c1'][2] - res['c0'][2]:.3f} s; traces "
        f"{res['c1'][1] - res['c0'][1]})")
    inwin = rec.in_window
    late = [r.late for r in rec.records if r.late is not None]
    say(f"requests: {len(inwin)} scheduled in the window, "
        f"{sum(r.state == 'done' for r in inwin)} done; generator late "
        f"p50 {1e3 * (percentile(late, 50) or 0):.3f} ms, max "
        f"{1e3 * max(late, default=0):.3f} ms over {len(late)} sends")
    n_tok = sum(1 for r in rec.records for t in r.recv
                if 0.0 <= t < rec.window_s)
    say(f"samples: output tokens {n_tok} streamed in the window, "
        f"{sum(l.tokens for l in rec.launches)} made by its "
        f"{len(rec.launches)} launches over {rec.gen_s:.3f} s")
    for name in ("ttft", "tpot"):
        n = sum(getattr(r, name) is not None for r in inwin)
        say(f"samples: {name} {n} requests")
    prio = [r for r in inwin if r.plan.tier == "priority"]
    if prio:
        say(f"samples: priority ttft {len(prio)} requests")
    if rec.rebinds:
        say(f"rebinds in the window: {len(rec.rebinds)}, mean "
            f"{1e3 * sum(d for _, d in rec.rebinds) / len(rec.rebinds):.3f}"
            f" ms")


def as_control(cmp: Dict) -> Dict:
    """The comparison with the fp8 control in the program's place: the
    tokens judged are those the control puts first at each position of
    the same prompts and served tokens."""
    return dict(cmp, served_logit_gap=cmp["control_logit_gap"])


def run(cell, seed: int, seconds: float, trace: bool, out_dir: str,
        t_start: float, control: bool = False) -> Dict:
    """Everything of one run but the chip check and the printing of the
    result line. ``t_start`` is the process's start on the wall clock.
    With ``control`` the fp8 control is judged in the program's place."""
    import jax
    from repro.serving.server import ServeHTTP
    from . import correct
    from . import weights as wts

    counter = system.CompileCounter()
    t_imp = time.time()
    door, loop, probe = system.build(cell, seed)
    engine = door.sched.backend
    jax.block_until_ready(engine.params)
    t_stack = time.time()
    trace_dir = f"{out_dir}/trace" if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    timing: Dict = {}

    async def serve():
        srv = await ServeHTTP(loop).start(HOST, 0)
        gen = loadgen.Generator(HOST, srv.port)
        timing["server"] = time.time()
        try:
            timing["warm"] = await warm_up(gen, door, loop, counter,
                                           cell.traffic_data, seed,
                                           seconds)
            timing["lead"] = time.time()
            return await measure(gen, door, probe, counter,
                                 cell.traffic_data, seed, seconds,
                                 trace_dir)
        finally:
            gen.close()
            await srv.stop()

    res = asyncio.run(serve())
    res["trace_dir"] = trace_dir
    setup_s = res["win_wall"] - t_start
    w = timing["warm"]
    lead_c = res["c0"][0] - res["lead_c0"][0]
    say(f"setup: import {t_imp - t_start:.3f} s, weights+pool+stack "
        f"{t_stack - t_imp:.3f} s, server {timing['server'] - t_stack:.3f}"
        f" s, warm-up replay {w['served_s']:.3f} s ({w['sent']} requests "
        f"sent, {w['done']} done, {w['compiles']} compiles or cache "
        f"loads), settle {w['settle_s']:.3f} s, lead-in "
        f"{res['win_wall'] - timing['lead']:.3f} s ({lead_c} compiles or "
        f"cache loads); compiles in set-up {res['c0'][0]} "
        f"({res['c0'][2]:.3f} s); setup_s {setup_s:.3f} s")
    say(f"kv pool: {engine.geom.num_blocks} blocks x "
        f"{engine.geom.block_base} tokens per chip")
    devs = jax.devices()
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    rec = record_of(res, cell, door, probe, seconds, setup_s, devs)
    report_window(rec, res)
    if probe.layouts:
        say(f"layouts after each rebind: {' -> '.join(probe.layouts)}")
    say(f"programs launched in the window: {len(probe.programs)} "
        f"distinct, {sum(probe.programs.values())} launches")
    fresh = probe.new_in_window()
    if fresh:
        say(f"programs first launched in the window: {fresh}")

    # correctness, once the window is over and its memory peak is read
    system.free_pools(engine)
    sample = correct.pick(rec.records, seed, must=must_cover(rec))
    t_ref = time.time()
    cmp = correct.compare(wts.named(engine.params), cell.config_data,
                          sample, cell.config_data["vocab_size"],
                          control=control)
    if control:
        cmp = as_control(cmp)
    say(f"reference: {cmp['requests_compared']} requests, "
        f"{cmp['tokens_compared']} served tokens compared in "
        f"{time.time() - t_ref:.1f} s ({cmp['argmax_agree']} equal to the "
        f"reference's argmax)")
    return {"rec": rec, "cmp": cmp, "mem_peak": mem_peak,
            "params": engine.params, "door": door, "probe": probe}


def must_cover(rec) -> List:
    """Requests the check must include: priority requests (served on TP
    islands on four chips) and requests that were streaming across a
    rebind (riders)."""
    done = [r for r in rec.records if r.state == "done"]
    out = [r for r in done if r.plan.tier == "priority"][:3]
    riders = [r for r in done if len(r.recv) >= 2 and any(
        r.recv[0] < t0 < r.recv[-1] for t0, _ in rec.rebinds)]
    return out + riders[:3]


def failed(rec, unfinished_ok: bool) -> int:
    """Requests of the window that errored, or that had streamed nothing
    by the end of the wait after the window (where that counts). One
    still streaming then is late, not failed."""
    bad = 0
    for r in rec.in_window:
        if r.state == "done":
            continue
        if r.state in ("pending", "sent") and (unfinished_ok or r.tokens):
            continue
        bad += 1
    return bad


def wrong_counts(rec) -> int:
    """Finished requests whose token count is not their max_tokens."""
    return sum(1 for r in rec.records if r.state == "done"
               and len(r.tokens) != r.plan.max_tokens)


def result(cell, out: Dict, trace: bool, devs) -> Dict:
    """The result line: metrics by their readers, the device, and the
    numbers compared with their limits (also printed on stderr, last)."""
    from .spec import load_reader
    rec, cmp = out["rec"], out["cmp"]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m.name)(rec)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    limit = cell.config_data["correct"]["served_logit_gap"]
    compared = {
        "served_logit_gap": {"value": cmp["served_logit_gap"],
                             "limit": limit},
        "wrong_token_counts": {"value": wrong_counts(rec), "limit": 0},
    }
    ok = (cmp["served_logit_gap"] is not None
          and cmp["served_logit_gap"] <= limit
          and compared["wrong_token_counts"]["value"] == 0)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out["mem_peak"]}
    line = {"correct": bool(ok), "attempted": len(rec.in_window),
            "failed": failed(rec, bool(cell.traffic_data.get(
                "unfinished_ok"))),
            "metrics": metrics, "device": device}
    if trace:
        from . import device as dv
        device["busy_s"] = dv.busy_s(rec)
        device["window_s"] = rec.traced.seconds if rec.traced else None
        if rec.breakdown is not None:
            line["breakdown"] = rec.breakdown
    line["compared"] = compared
    for k, v in compared.items():
        stderr(f"compared {k}: {v['value']} (limit {v['limit']})")
    return line
