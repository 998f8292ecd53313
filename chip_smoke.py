#!/usr/bin/env python3
"""Smoke test of the FlyingEngine serving path on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: live DP<->TP rebinds

One chip: builds the real stack through the launcher's entry points
(``launch/serve.py`` ``build_stack``/``build_door``, ``real=True``,
stablelm-1.6b at its published widths in bf16, random weights from a
seed, the KV pool sized to the HBM the weights leave free), boots the
HTTP server over the async serve loop on an ephemeral localhost port,
streams a mixed batch of completions through it (prefill chunks, mixed
ticks and decode all run) and checks every request ends with exactly
its ``max_tokens``. Then it checks, on the chip, that the Pallas
kernels give the same logits as the jnp reference.

Four chips (``--four-chips``, only this phase): one engine per chip,
starting from DP4; mid-decode the fleet rebinds to [DP2 | TP2] and then
to TP4 under the LIVE strategy, and the logits of the steps after each
rebind are compared with the same requests served without a switch.

Everything runs in this one process; it starts no child. It exits
non-zero, and prints no result, when JAX finds no TPU or any phase
fails. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
# (prompt_tokens, max_tokens): long prompts stream as several 256-token
# prefill chunks while earlier requests decode (mixed ticks)
TRAFFIC = ((2048, 32), (128, 128), (700, 64), (1500, 96),
           (256, 48), (1024, 128), (300, 32), (1800, 80))
# Logits of two engine variants may differ by at most this fraction of
# the reference row's largest |logit|. Both store KV and activations in
# bf16 (unit roundoff 2^-8 = 0.4%) and accumulate in f32, but in a
# different reduction order (kernel: online softmax page by page and
# lane-group matmuls; reference: one dense softmax; TP: partial sums
# combined across chips), so a bf16 rounding can flip at every layer
# boundary and the flips compound over 24 layers. A wrong head, page or
# mask gives O(1) differences.
LOGIT_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what) -> None:
    """A failed check fails the run (asserts would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Seconds XLA spent compiling (or fetching from the persistent
    cache), summed over every program since construction."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


# ---------------------------------------------------------------------------
# one chip: the launcher's serving path over HTTP
# ---------------------------------------------------------------------------

def serve_over_http(arch: str):
    """Serve TRAFFIC through build_stack/build_door + ServeHTTP; returns
    (records, scheduler)."""
    from repro.core.task_pool import Request
    from repro.launch.serve import ServeConfig, build_door, build_stack
    from repro.serving.asyncloop import AsyncServeLoop
    from repro.serving.loadgen import drive_http
    from repro.serving.metrics import RollingTierMetrics
    from repro.serving.server import ServeHTTP

    cfg = ServeConfig(real=True, arch=arch)
    sched, _spec, _inj = build_stack(cfg)
    door = build_door(cfg, sched)
    loop = AsyncServeLoop(
        door, pace=cfg.pace, stream_buf=cfg.stream_buf,
        wall_dilation=cfg.wall_dilation,
        rolling=RollingTierMetrics(window_s=cfg.metrics_window))
    reqs = [Request(req_id=f"s{i}", arrival=0.0, prompt_len=p, output_len=o)
            for i, (p, o) in enumerate(TRAFFIC)]

    async def run():
        srv = await ServeHTTP(loop).start("127.0.0.1", 0)
        try:
            return await drive_http("127.0.0.1", srv.port, reqs)
        finally:
            await srv.stop()

    out = asyncio.run(run())
    door.shutdown(None)
    by_id = {r.req_id: r for r in reqs}
    for rec in out["records"]:
        want = by_id[rec["req_id"]].output_len
        log(f"  {rec['req_id']}: prompt {by_id[rec['req_id']].prompt_len:5d}"
            f"  {rec['state']}  {rec['n_tokens']}/{want} tokens")
    return out, sched


def compiled_step_texts(engine) -> dict:
    """HLO text of the serving decode and prefill executables at the
    island's batch, a 256-token chunk and a 128-block table."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    rt = engine.islands[0]
    B, T, mb = rt.B, engine.prefill_len, 128

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    def rows(*shape):
        spec = P(("pod", "dp"), *([None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(rt.mesh, spec))
    batches = {
        "decode": dict(tokens=rows(B, 1), positions=rows(B, 1),
                       slots=rows(B), block_table=rows(B, mb),
                       context_len=rows(B)),
        "prefill": dict(tokens=rows(B, T), positions=rows(B, T),
                        slots=rows(B, T), block_table=rows(B, mb),
                        prior_len=rows(B), last_pos=rows(B)),
    }
    params = jax.tree.map(like, rt.params)
    states = jax.tree.map(like, rt.states)
    out = {}
    for phase, batch in batches.items():
        out[phase] = engine.pool.precompile(
            rt.island, phase, (params, states, batch), sampled=True,
            donate=True).as_text()
    return out


def release(engine) -> None:
    """Free an engine's KV pools now (its weights are shared)."""
    import jax
    for rt in engine.islands:
        for a in jax.tree.leaves(rt.states):
            a.delete()
    engine.islands = []


# ---------------------------------------------------------------------------
# logits of an engine variant, tick by tick (legacy host-sampling path)
# ---------------------------------------------------------------------------

class ScriptedLayouts:
    """Layout policy: stage i's layout once every request has emitted at
    least ``stages[i][0]`` tokens."""

    def __init__(self, stages):
        self.stages = stages

    def decide(self, sched):
        reqs = list(sched.pool.all.values())
        least = min((r.generated for r in reqs), default=0)
        target = self.stages[0][1]
        for min_tokens, layout in self.stages:
            if least >= min_tokens:
                target = layout
        return target


def serve_logits(model, plan, geom, params, reqs, *, bpe, max_blocks,
                 chunk, policy, use_kernel=None, strategy="hard",
                 check_zero_copy=False):
    """Serve ``reqs`` through the scheduler on an engine that samples on
    the host; returns ({rid: {token index: logits row}}, tokens, layouts
    seen, engine, scheduler)."""
    from repro.core.engine import FlyingEngine
    from repro.core.scheduler import DynamicScheduler, SchedulerConfig
    eng = FlyingEngine(model, plan, geom, params, batch_per_engine=bpe,
                       max_blocks_per_req=max_blocks, prefill_len=chunk,
                       use_kernel=use_kernel, fused_sampling=False,
                       check_zero_copy=check_zero_copy)
    sched = DynamicScheduler(
        plan, geom, eng,
        SchedulerConfig(strategy=strategy, max_batch_per_group=bpe,
                        prefill_chunk=chunk),
        policy=policy)
    for r in reqs:
        sched.submit(copy.deepcopy(r))
    rows = {r.req_id: {} for r in reqs}
    layouts = []
    seen: set = set()
    while True:
        progressed = sched.step()
        desc = sched.layout.describe()
        if not layouts or layouts[-1] != desc:
            layouts.append(desc)
        for rid, row in eng.last_logits.items():
            rows[rid][len(eng.harvested_tokens(rid)) - 1] = row
        if not progressed and not sched.idle_advance(seen):
            break
    sched.drain_backend()
    toks = {r.req_id: list(eng.generated_tokens(r.req_id)) for r in reqs}
    bad = [r.req_id for r in reqs if len(toks[r.req_id]) != r.output_len]
    require(not bad, f"requests without their full output: {bad}")
    return rows, toks, layouts, eng, sched


def compare_logits(test, ref, start=0):
    """Worst max|test-ref| / max|ref| over index-aligned logits rows at
    token index >= ``start``, per request while the greedy streams agree
    (once a near-tie flips a token the inputs differ). Returns (worst,
    rows compared)."""
    import numpy as np
    worst, n = 0.0, 0
    for rid, ref_rows in ref.items():
        for idx in sorted(ref_rows):
            if idx not in test[rid]:
                continue
            a, b = test[rid][idx], ref_rows[idx]
            if idx >= start:
                err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                worst, n = max(worst, err), n + 1
            if a.argmax() != b.argmax():
                break
    return worst, n


def kernel_vs_reference(model, params):
    """Prefill and decode the same requests through a Pallas-kernel
    engine and a jnp-reference engine; compare logits."""
    from repro.core.kv_adaptor import PoolGeometry
    from repro.core.modes import FleetLayout, ParallelPlan
    from repro.core.task_pool import Request
    plan = ParallelPlan(engine_rows=1, tp_base=1, data_rows=1)
    geom = PoolGeometry(model.cfg, plan, num_blocks=129, block_base=16)
    reqs = [Request(req_id=f"k{i}", arrival=0.0, prompt_len=p, output_len=8)
            for i, p in enumerate((200, 77, 300, 130))]
    policy = ScriptedLayouts(((0, FleetLayout.uniform(plan, 1)),))
    runs = {}
    for use_kernel in (True, False):
        rows, _, _, eng, _ = serve_logits(
            model, plan, geom, params, reqs, bpe=4, max_blocks=32,
            chunk=256, policy=policy, use_kernel=use_kernel)
        release(eng)
        runs[use_kernel] = rows
    return compare_logits(runs[True], runs[False])


def one_chip(arch: str = ARCH) -> None:
    import jax
    from repro.kernels.paged_attention.ops import resolve_impl
    clock = CompileClock()
    t0 = time.perf_counter()
    out, sched = serve_over_http(arch)
    engine = sched.backend
    recs = {r["req_id"]: r for r in out["records"]}
    want = {f"s{i}": o for i, (_, o) in enumerate(TRAFFIC)}
    bad = {k: (recs[k]["state"], recs[k]["n_tokens"], w)
           for k, w in want.items()
           if recs[k]["state"] != "done" or recs[k]["n_tokens"] != w}
    require(not bad, f"requests not finished with exactly max_tokens: {bad}")
    log(f"served {len(want)}/{len(want)} requests over HTTP, each with "
        f"exactly its max_tokens, in {out['wall_s']:.1f} s "
        f"({time.perf_counter() - t0:.1f} s with set-up)")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"kv pool blocks: {engine.geom.num_blocks} x "
        f"{engine.geom.block_base} tokens;  peak_bytes_in_use: "
        f"{stats.get('peak_bytes_in_use')} of bytes_limit "
        f"{stats.get('bytes_limit')}")
    log(f"compile: {clock.seconds:.1f} s over {clock.count} programs")
    mode = {None: "auto", True: "force", False: "ref"}[engine.pool.use_kernel]
    impl = resolve_impl(mode)
    log(f"paged-attention impl: {impl}; chunked-prefill impl: {impl}")
    require(impl == "kernel", f"attention impl {impl}")
    texts = compiled_step_texts(engine)
    has = {k: "tpu_custom_call" in t for k, t in texts.items()}
    log(f"tpu_custom_call in compiled decode step: {has['decode']}, "
        f"prefill step: {has['prefill']}")
    require(all(has.values()), f"tpu_custom_call {has}")

    model, params = engine.model, engine.params
    release(engine)
    del sched, engine, out
    gc.collect()
    worst, n = kernel_vs_reference(model, params)
    log(f"kernel vs reference logits: worst max|diff|/max|ref| = "
        f"{worst:.3e} over {n} rows (tolerance {LOGIT_RTOL})")
    require(n > 0 and worst <= LOGIT_RTOL,
            f"kernel vs reference: {worst} over {n} rows")


# ---------------------------------------------------------------------------
# four chips: live DP4 -> [DP2 | TP2] -> TP4
# ---------------------------------------------------------------------------

def four_chips(arch: str = ARCH) -> None:
    import jax
    from repro.configs import get_config
    from repro.core.kv_adaptor import PoolGeometry
    from repro.core.modes import (FleetLayout, FlyingMode, ParallelPlan,
                                  mode_mesh)
    from repro.core.task_pool import Request
    from repro.core.weights_manager import WeightsManager
    from repro.launch.serve import BLOCK_TOKENS, PREFILL_CHUNK, ServeConfig
    from repro.models.model import build_model
    n = len(jax.devices())
    require(n == 4, f"--four-chips needs 4 devices, found {n}")
    cfg = ServeConfig(real=True, arch=arch)
    # the launcher's real plan on four devices: one engine per chip
    plan = ParallelPlan(engine_rows=1, tp_base=1, data_rows=n)
    model = build_model(get_config(arch))
    params = WeightsManager(model.cfg, plan).init_params(
        model, mode_mesh(FlyingMode(plan, 1)), jax.random.key(cfg.seed))
    bpe, chunk, max_blocks = 4, PREFILL_CHUNK, 64
    geom = PoolGeometry(model.cfg, plan, num_blocks=bpe * max_blocks + 1,
                        block_base=BLOCK_TOKENS)
    for m in (1, 2, 4):
        require(geom.live_readable(m), f"merge {m} not live-readable")
    dp4 = FleetLayout.uniform(plan, 1)
    mixed = FleetLayout.of(plan, [(2, 1), (2, 2)])
    tp4 = FleetLayout.uniform(plan, 4)
    reqs = [Request(req_id=f"f{i}", arrival=0.0, prompt_len=p,
                    output_len=32)
            for i, p in enumerate((130, 300, 77, 512, 256, 190, 64, 400))]
    REBIND_AT = (4, 12)     # tokens every request has before each rebind

    ref, ref_toks, _, ref_eng, _ = serve_logits(
        model, plan, geom, params, reqs, bpe=bpe, max_blocks=max_blocks,
        chunk=chunk, policy=ScriptedLayouts(((0, dp4),)))
    live, toks, layouts, eng, sched = serve_logits(
        model, plan, geom, params, reqs, bpe=bpe, max_blocks=max_blocks,
        chunk=chunk, strategy="live", check_zero_copy=True,
        policy=ScriptedLayouts(((0, dp4), (REBIND_AT[0], mixed),
                                (REBIND_AT[1], tp4))))
    log("layouts: " + "  ->  ".join(layouts))
    require(layouts[0] == dp4.describe() and mixed.describe() in layouts
            and layouts[-1] == tp4.describe(), f"layouts {layouts}")
    log(f"rebinds: {sched.switches} (zero-copy asserted on every param "
        f"and pool view: check_zero_copy=True); preempt stats "
        f"{sched.preempt_stats}")
    log(f"all {len(reqs)} requests completed with their 32 tokens "
        f"({sum(len(t) for t in toks.values())} tokens)")

    for name, start in (("[DP2 | TP2]", REBIND_AT[0]),
                        ("TP4", REBIND_AT[1])):
        worst, rows = compare_logits(live, ref, start=start)
        log(f"logits after rebind to {name} (token >= {start}) vs "
            f"no-switch: worst max|diff|/max|ref| = {worst:.3e} over "
            f"{rows} rows (tolerance {LOGIT_RTOL})")
        require(rows > 0 and worst <= LOGIT_RTOL,
                f"{name}: {worst} over {rows} rows")
    # each device holds only its shard: params replicate per engine (the
    # DP base), pools shard one [L, 1, 1, blocks, B_base, W] per device
    for a in jax.tree.leaves(eng.params):
        shard = a.sharding.shard_shape(a.shape)
        require(len(a.addressable_shards) == n and all(
            s.data.shape == shard for s in a.addressable_shards),
            f"param shards of {a.shape}")
    pool_leaves = jax.tree.leaves(eng.islands[0].states)
    for a in pool_leaves:
        require(all(s.data.shape[1:3] == (1, 1)
                    for s in a.addressable_shards),
                f"pool shards of {a.shape}")
    param_b = sum(a.nbytes for a in jax.tree.leaves(eng.params))
    pool_b = sum(a.nbytes for a in pool_leaves) // n
    for d in jax.devices():
        used = d.memory_stats()["bytes_in_use"]
        # this engine's pool, the reference engine's, the weights
        cap = param_b + 2 * pool_b + (256 << 20)
        log(f"  {d}: bytes_in_use {used} (weights {param_b}, own pool "
            f"shard {pool_b}; whole-fleet pool would be {n * pool_b})")
        require(used <= cap, f"{d}: {used} bytes in use > {cap}")
    same = sum(toks[k] == ref_toks[k] for k in toks)
    log(f"greedy streams identical to no-switch: {same}/{len(toks)}")
    del ref_eng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip live-rebind phase")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    n = len(jax.devices())
    log(f"device_kind: {dev.device_kind}; devices: {n}; compile cache: "
        f"{cache}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
