"""The program's own spans and build counters (``core/tracing.py``,
``CommunicatorPool.stats``).

* With no profiler recording, serving builds no span object at all;
  a ``start_trace``, or a capture through the profiler server, turns
  the spans on.
* Under the profiler, on the four-device DP <-> TP path, the spans nest
  on the serve thread (``md_scripts/check_tracing.py``).
* A runner call that makes ``jax.jit`` build an executable counts one
  build under its runner key, with its seconds; a repeat counts none.
* ``/metrics`` exports the engine's counters, builds included.
"""
import asyncio
import glob
import json
import os
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData

from conftest import MD_SCRIPTS, REPO
from repro.configs import get_config
from repro.core import communicator_pool, tracing
from repro.core.communicator_pool import CommunicatorPool
from repro.core.engine import FlyingEngine
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import Island, ParallelPlan
from repro.core.scheduler import DynamicScheduler, SchedulerConfig
from repro.core.task_pool import Request
from repro.models.model import build_model
from repro.serving.asyncloop import AsyncServeLoop
from repro.serving.frontdoor import FrontDoor, FrontDoorConfig
from repro.serving.server import ServeHTTP

PLAN = ParallelPlan(engine_rows=1, tp_base=1, data_rows=1)


def _real_loop():
    """A served stack over the real engine on one device, small enough
    to compile in seconds."""
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, jnp.float32)
    geom = PoolGeometry(cfg, PLAN, num_blocks=64, block_base=4)
    eng = FlyingEngine(model, PLAN, geom, model.init(jax.random.key(0)),
                       batch_per_engine=2, max_blocks_per_req=16,
                       prefill_len=8)
    sched = DynamicScheduler(PLAN, geom, eng, SchedulerConfig(
        strategy="hard", max_batch_per_group=2, prefill_chunk=8))
    return AsyncServeLoop(FrontDoor(sched, FrontDoorConfig()),
                          pace="virtual")


async def _http(port, method, path, body=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    data = json.dumps(body).encode() if body is not None else b""
    writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(data)}\r\n\r\n").encode() + data)
    await writer.drain()
    out = (await reader.read()).decode()
    writer.close()
    return out.split("\r\n\r\n", 1)[1]


def _serve(loop, calls):
    """Run ``calls`` (method, path, body) in order against a server."""
    async def main():
        srv = await ServeHTTP(loop).start("127.0.0.1", 0)
        try:
            return [await _http(srv.port, *c) for c in calls]
        finally:
            await srv.stop()
    return asyncio.run(main())


def test_serving_builds_no_span_object_without_a_profiler(monkeypatch,
                                                          tmp_path):
    made = []

    class Counting(tracing.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "TraceAnnotation", Counting)
    assert tracing.span("fs.tick", tick=lambda: 1 / 0) is tracing._NULL
    loop = _real_loop()
    out = _serve(loop, [("POST", "/v1/completions",
                         {"prompt_tokens": 12, "max_tokens": 5,
                          "stream": True})])
    assert out[0].rstrip().endswith("data: [DONE]")
    assert loop.ticks > 0 and made == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("fs.tick", tick=lambda: 7):
            pass
    finally:
        jax.profiler.stop_trace()
    assert made == ["fs.tick"]
    assert tracing.span("fs.tick") is tracing._NULL


_SERVED = """
import sys, time
import jax
from repro.core import tracing
jax.profiler.start_server(int(sys.argv[1]))
print("up", flush=True)
end = time.time() + float(sys.argv[2])
while time.time() < end:
    with tracing.span("fs.tick", tick=1):
        time.sleep(0.005)
    time.sleep(0.005)
"""


def test_a_capture_through_the_profiler_server_turns_spans_on(tmp_path):
    """What ``--profile-port`` relies on: a process that only started
    the profiler server builds its spans while a capture records, and
    the capture holds them."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    served = subprocess.Popen(
        [sys.executable, "-c", _SERVED, str(port), "30"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        assert served.stdout.readline().strip() == "up"
        out = tmp_path / "capture"
        cap = subprocess.run(
            [sys.executable, "-m", "jax.collect_profile", str(port), "1000",
             "--log_dir", str(out), "--no_perfetto_link"],
            capture_output=True, text=True, env=env, timeout=120)
        assert cap.returncode == 0, cap.stderr[-2000:]
    finally:
        served.kill()
        served.wait()
    (path,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "fs.tick" in names


def test_metrics_exports_the_engine_block_and_its_builds():
    loop = _real_loop()
    before, _, after = _serve(loop, [
        ("GET", "/metrics"),
        ("POST", "/v1/completions", {"prompt_tokens": 12, "max_tokens": 5}),
        ("GET", "/metrics")])
    e0, e1 = json.loads(before)["engine"], json.loads(after)["engine"]
    assert set(e0) == {"steps", "drains", "window_waits", "d2h_batched",
                       "compiles", "compile_s", "last_compiled"}
    assert e0["compiles"] == 0 and e0["steps"] == 0
    assert e1["compiles"] > 0 and e1["compile_s"] > 0
    assert e1["steps"] >= 5 and e1["drains"] >= 1
    assert e1["last_compiled"].startswith("fs_")


def test_a_runner_call_that_builds_counts_once(monkeypatch):
    def build(*_a, **_kw):
        return (lambda params, states, batch:
                (batch["tokens"] * 2, states + 1)), None, None
    monkeypatch.setattr(communicator_pool, "build_serve_step", build)
    pool = CommunicatorPool(None, PLAN, None)
    run = pool.runner(Island(0, 1, 1), "decode", sampled=True,
                      batch_bucket=4, seq_bucket=1, mb_bucket=8)
    assert run is pool.runner(Island(0, 1, 1), "decode", sampled=True,
                              batch_bucket=4, seq_bucket=1, mb_bucket=8)
    state = jnp.zeros(4)
    toks, state = run(None, state, {"tokens": jnp.ones((4, 1))})
    s = pool.stats
    assert (s.compiles, s.last_compiled) == (1, "fs_decode_n1m1/b4/s1/mb8")
    assert s.compile_s > 0
    spent = s.compile_s
    toks, state = run(None, state, {"tokens": toks})     # fed back
    assert (s.compiles, s.compile_s) == (1, spent)
    # the same key with its token input uploaded from the host: jax.jit
    # builds another executable, counted under the same program id
    run(None, state, {"tokens": np.ones((4, 1), np.float32)})
    assert s.compiles == 2 and s.compile_s > spent
    assert s.last_compiled == "fs_decode_n1m1/b4/s1/mb8"


def test_spans_nest_on_the_serve_thread_across_a_dp_tp_rebind():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(MD_SCRIPTS, "check_tracing.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "TRACING OK" in proc.stdout
