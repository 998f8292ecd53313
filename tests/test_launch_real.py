"""The launcher's real-engine path (``launch/serve.py`` ``--real``) on the
devices at hand — on CPU, one device and a reduced config picked by
name: the plan comes from the device count, weights and KV pools are
created directly in their sharded placement, and completions streamed
through the HTTP front door end with exactly their ``max_tokens``."""
import asyncio

import jax

from repro.core.modes import FlyingMode, ParallelPlan, mode_mesh
from repro.core.task_pool import Request
from repro.launch.serve import (BLOCK_TOKENS, DECODE_BATCH, MAX_CONTEXT,
                                ServeConfig, build_door, build_stack)
from repro.serving.asyncloop import AsyncServeLoop
from repro.serving.loadgen import drive_http
from repro.serving.server import ServeHTTP


def test_build_stack_real_serves_exact_tokens_over_http():
    cfg = ServeConfig(real=True, arch="stablelm-1.6b-reduced")
    sched, _, _ = build_stack(cfg)
    eng = sched.backend
    n = len(jax.devices())
    assert eng.plan == ParallelPlan(engine_rows=1, tp_base=1, data_rows=n)
    assert eng.cfg.name == "stablelm-1.6b-reduced"
    # no device memory limit reported on CPU: the pool holds what a full
    # batch of max-context requests can address
    assert eng.geom.num_blocks == \
        DECODE_BATCH * (MAX_CONTEXT // BLOCK_TOKENS) + 1
    canon = eng.wm.shardings(eng.params, mode_mesh(FlyingMode(eng.plan, 1)))
    for a, s in zip(jax.tree.leaves(eng.params), jax.tree.leaves(canon)):
        assert a.dtype == jax.numpy.bfloat16
        assert a.sharding.is_equivalent_to(s, a.ndim)
    for a in jax.tree.leaves(eng.states):
        assert a.shape[3:] == eng.geom.pool_shape()
        assert all(s.data.shape[1:3] == (1, 1) for s in a.addressable_shards)

    door = build_door(cfg, sched)
    loop = AsyncServeLoop(door, pace=cfg.pace)
    # a two-chunk prompt, a short one and a three-chunk one
    reqs = [Request(req_id=f"r{i}", arrival=0.0, prompt_len=p, output_len=o)
            for i, (p, o) in enumerate(((300, 5), (40, 9), (600, 4)))]

    async def run():
        srv = await ServeHTTP(loop).start("127.0.0.1", 0)
        try:
            return await drive_http("127.0.0.1", srv.port, reqs)
        finally:
            await srv.stop()

    recs = {r["req_id"]: r for r in asyncio.run(run())["records"]}
    for r in reqs:
        rec = recs[r.req_id]
        assert rec["state"] == "done", rec
        assert rec["n_tokens"] == r.output_len, rec
