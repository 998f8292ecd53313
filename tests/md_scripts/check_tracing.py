"""The program's host spans under the profiler, on 4 forced host devices:
the launcher's real stack (one engine per device, LIVE rebinds) serves
background and priority completions over HTTP while ``jax.profiler``
records; a priority arrival carves a TP island (DP <-> TP rebind).

Checked in the recorded ``.xplane.pb``: every ``fs.*`` span sits on the
serve thread, and they nest as

    fs.tick > fs.step > fs.launch        fs.tick > fs.policy > fs.rebind
    fs.step > fs.wait                    fs.pump > fs.drain > fs.harvest

with ``fs.http.write`` on the same thread, outside every tick. Each
``fs.step`` carries its phase, island and rows. Prints TRACING OK.
"""
import asyncio
import glob
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "perfbench"))
from harness.spans import load  # noqa: E402
from repro.core.policy import FlyingPolicy  # noqa: E402
from repro.core.task_pool import Request  # noqa: E402
from repro.launch.serve import (ServeConfig, build_door,  # noqa: E402
                                build_stack)
from repro.serving.asyncloop import AsyncServeLoop  # noqa: E402
from repro.serving.loadgen import drive_http  # noqa: E402
from repro.serving.server import ServeHTTP  # noqa: E402


def serve_traced(loop, warm, reqs, log_dir):
    """Serve ``warm``, then ``reqs`` while the profiler records; one
    event loop for both."""
    async def main():
        srv = await ServeHTTP(loop).start("127.0.0.1", 0)
        try:
            await drive_http("127.0.0.1", srv.port, warm)
            jax.profiler.start_trace(log_dir)
            try:
                return await drive_http("127.0.0.1", srv.port, reqs)
            finally:
                jax.profiler.stop_trace()
        finally:
            await srv.stop()
    return asyncio.run(main())


def spans(path):
    """fs.* events per (plane, line): (start, end, name, stats), read by
    the benchmark's own reader."""
    out = {}
    for sp in load(path):
        out.setdefault(sp.thread, []).append(
            (sp.start, sp.end, sp.name, sp.args))
    return out


def inside(evs, outer, inner):
    """Some ``inner`` span lies within some ``outer`` span."""
    return any(a <= c and d <= b
               for a, b, n, _ in evs if n == outer
               for c, d, m, _ in evs if m == inner)


def main():
    assert len(jax.devices()) == 4
    cfg = ServeConfig(real=True, arch="stablelm-1.6b-reduced",
                      strategy="live", no_shed=True, stream_buf=4096)
    sched, _, _ = build_stack(cfg)
    sched.policy = FlyingPolicy(live=True)
    loop = AsyncServeLoop(build_door(cfg, sched), pace="wall",
                          stream_buf=cfg.stream_buf)
    bg = [Request(req_id=f"b{i}", arrival=0.0, prompt_len=40 + 30 * i,
                  output_len=12) for i in range(4)]
    prio = [Request(req_id=f"p{i}", arrival=0.5, prompt_len=60,
                    output_len=6, tier="priority") for i in range(2)]
    log_dir = tempfile.mkdtemp()
    again = [Request(req_id=f"{r.req_id}x", arrival=r.arrival,
                     prompt_len=r.prompt_len, output_len=r.output_len,
                     tier=r.tier) for r in bg + prio]
    # the warm-up serves background only: the priority arrivals carve
    # the TP island while the profiler records
    out = serve_traced(loop, bg, again, log_dir)
    assert sched.switches >= 1, "no rebind while tracing"
    assert all(r["state"] == "done" for r in out["records"]), out
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = spans(path)
    assert len(lines) == 1, f"fs.* spans on {len(lines)} threads"
    evs = next(iter(lines.values()))
    names = {n for _, _, n, _ in evs}
    want = {"fs.tick", "fs.policy", "fs.rebind", "fs.step", "fs.launch",
            "fs.wait", "fs.harvest", "fs.drain", "fs.pump",
            "fs.http.write"}
    assert want <= names, want - names
    for outer, inner in (("fs.tick", "fs.step"), ("fs.step", "fs.launch"),
                         ("fs.step", "fs.wait"), ("fs.tick", "fs.policy"),
                         ("fs.policy", "fs.rebind"), ("fs.pump", "fs.drain"),
                         ("fs.drain", "fs.harvest")):
        assert inside(evs, outer, inner), f"no {inner} inside {outer}"
    ticks = [(a, b) for a, b, n, _ in evs if n == "fs.tick"]
    for a, b, n, _ in evs:
        if n in ("fs.http.write", "fs.pump"):
            assert not any(c <= a < d for c, d in ticks), n
    steps = [st for _, _, n, st in evs if n == "fs.step"]
    assert {st["phase"] for st in steps} >= {"prefill", "decode"} \
        or "mixed" in {st["phase"] for st in steps}
    assert any(str(st["island"]).count("/") == 2 and
               str(st["island"]).split("/")[2] != "1" for st in steps), \
        "no step on a TP island"
    print("TRACING OK")


if __name__ == "__main__":
    sys.exit(main())
