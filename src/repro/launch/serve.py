"""Serving launcher.

Modes:
  --sim  (default): full-scale discrete-event run on the roofline cost
         model — the production mesh geometry, any arch, paper workloads.
  --real: the FlyingEngine on the local devices — the named config at
         its own widths in bf16, one engine per device (one TPU v5e chip
         serves stablelm-1.6b), the KV pool sized to the HBM the weights
         leave free. CPU runs pick a reduced config by name (e.g.
         --arch stablelm-1.6b-reduced) and may emulate a fleet with
         XLA_FLAGS=--xla_force_host_platform_device_count=8.
  --serve: boot the §D13 async serving core — the OpenAI-style HTTP/SSE
         endpoint (`serving/server.py`) over the event-driven
         continuous-batching loop — instead of replaying a trace.

Every knob lives on the :class:`ServeConfig` dataclass and can come
from a JSON file (``--config serve.json``) with CLI flags as overrides,
so deployments pin a config artifact and experiments tweak one flag at
a time:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \
      --requests 500 --strategy hard
  PYTHONPATH=src python -m repro.launch.serve --config serve.json \
      --rate 20
  PYTHONPATH=src python -m repro.launch.serve --serve --port 8000 \
      --frontdoor --forecast
  curl -N localhost:8000/v1/completions -d \
      '{"prompt": "hello", "max_tokens": 16, "stream": true}'
"""
from __future__ import annotations

import argparse
import copy
import json
from dataclasses import dataclass, field, fields, replace
from typing import Tuple


@dataclass
class ServeConfig:
    """Every launcher knob in one place (§D13 satellite: the flag set
    had outgrown argparse). JSON-loadable; unknown keys are errors so a
    typo'd config fails loudly, not silently as a default."""
    arch: str = "llama3-8b"                  # model config name
    real: bool = False                       # real engine vs sim backend
    requests: int = 500                      # trace length (offline)
    strategy: str = "hard"                   # hard|soft|sequential|live
    fixed_merge: int = 0                     # pin the mode; 0 = dynamic
    switch: str = "flying"                   # flying|restart|none
    priority_frac: float = 0.0
    prefix_cache: bool = False               # §D10 content-addressed KV
    prefix_pool: int = 4
    prefix_hit: float = 0.6
    seed: int = 0
    fault: Tuple[str, ...] = field(default_factory=tuple)
    # front door (§D11)
    frontdoor: bool = False
    no_shed: bool = False
    queue_cap: int = 512
    ttft_deadline: float = 0.0               # priority TTFT SLO (0=none)
    tpot_deadline: float = 0.0               # priority TPOT SLO (0=none)
    arrival: str = "phased"                  # phased|poisson|bursty
    rate: float = 10.0
    background_frac: float = 0.0
    cancel_frac: float = 0.0
    diagnostic: str = ""                     # diagnostic JSON path
    # async serving core (§D13)
    serve: bool = False                      # boot the HTTP server
    host: str = "127.0.0.1"
    port: int = 8000
    pace: str = "wall"                       # wall|virtual serve clock
    forecast: bool = False                   # predictive rebind policy
    stream_buf: int = 256                    # per-stream token buffer
    wall_dilation: float = 1.0               # virtual s per wall s
    metrics_window: float = 60.0             # rolling /metrics window
    profile_port: int = 0                    # profiler server; 0 = off

    _CHOICES = {"strategy": ("hard", "soft", "sequential", "live"),
                "switch": ("flying", "restart", "none"),
                "arrival": ("phased", "poisson", "bursty"),
                "pace": ("wall", "virtual")}

    @classmethod
    def load(cls, path: str) -> "ServeConfig":
        with open(path) as f:
            raw = json.load(f)
        known = {f.name for f in fields(cls)}
        bad = set(raw) - known
        if bad:
            raise SystemExit(f"unknown config keys in {path}: "
                             f"{sorted(bad)}")
        if "fault" in raw:
            raw["fault"] = tuple(raw["fault"])
        cfg = cls(**raw)
        cfg.validate()
        return cfg

    def validate(self) -> None:
        for name, opts in self._CHOICES.items():
            v = getattr(self, name)
            if v not in opts:
                raise SystemExit(f"config: {name}={v!r} not in {opts}")

    def policy(self):
        """The layout policy this config asks for (None = pinned)."""
        from repro.core.policy import FlyingPolicy, ForecastPolicy
        if self.fixed_merge:
            return None
        inner = FlyingPolicy()
        return ForecastPolicy(inner=inner) if self.forecast else inner


def _build_parser() -> argparse.ArgumentParser:
    """argparse view over ServeConfig: every field is a flag whose
    DEFAULT is the `unset` sentinel, so only flags the user actually
    passed override a --config file."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="", metavar="serve.json",
                    help="load a ServeConfig JSON; flags override it")
    for f in fields(ServeConfig):
        flag = "--" + f.name.replace("_", "-")
        if f.name == "fault":
            ap.add_argument("--fault", action="append", default=None,
                            metavar="KIND@TICK[:eng,eng...]",
                            help="scripted fault, e.g. kill@40:3 "
                                 "(repeatable)")
        elif f.type == "bool" or f.default is False:
            ap.add_argument(flag, action="store_true", default=None)
        else:
            ap.add_argument(flag, type=type(f.default), default=None,
                            choices=ServeConfig._CHOICES.get(f.name))
    return ap


def parse_config(argv=None) -> ServeConfig:
    args = _build_parser().parse_args(argv)
    cfg = ServeConfig.load(args.config) if args.config else ServeConfig()
    over = {f.name: getattr(args, f.name) for f in fields(ServeConfig)
            if getattr(args, f.name) is not None}
    if "fault" in over:
        over["fault"] = tuple(over["fault"])
    cfg = replace(cfg, **over)
    cfg.validate()
    return cfg


# ---------------------------------------------------------------------------
# stack construction
# ---------------------------------------------------------------------------

# the real engine's deployment shape: 16 concurrent decodes per engine,
# 256-token prefill chunks, requests up to 4096 tokens (prompt + output,
# stablelm-2's context), 16-token KV blocks
DECODE_BATCH = 16
PREFILL_CHUNK = 256
MAX_CONTEXT = 4096
BLOCK_TOKENS = 16
ACT_HEADROOM = 1 << 30     # per-device bytes left for a step's activations


def kv_pool_blocks(model, geom, demand: int) -> int:
    """KV blocks per device: what device memory holds once the (already
    resident) weights and ``ACT_HEADROOM`` are taken out, capped at
    ``demand`` — the blocks a full batch of max-context requests can
    address. Each step program also keeps one layer's K/V pool slice as
    scratch, hence the (1 + 1/layers) factor. A backend that reports no
    memory limit (the CPU) gets the cap."""
    import jax
    import jax.numpy as jnp
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return demand
    leaves = sum(n * (1 if kind[0] == "mla" else 2)
                 for kind_seq, n in model.plan for kind in kind_seq
                 if kind[0] in ("gqa", "gqa_win", "mla"))
    per_block = leaves * geom.block_base * geom.token_width \
        * jnp.dtype(model.dtype).itemsize
    free = stats["bytes_limit"] - stats["bytes_in_use"] - ACT_HEADROOM
    fit = int(free / (per_block * (1 + 1 / model.cfg.num_layers)))
    return max(min(demand, fit), 0)


def build_stack(cfg: ServeConfig):
    """Scheduler + workload spec for this config (sim or real)."""
    from repro.configs import get_config
    from repro.core.faults import FaultInjector, FaultSpec
    from repro.core.kv_adaptor import PoolGeometry
    from repro.core.modes import ParallelPlan
    from repro.core.scheduler import DynamicScheduler, SchedulerConfig
    from repro.serving.workload import WorkloadSpec

    def parse_fault(s: str) -> FaultSpec:
        kind, _, rest = s.partition("@")
        tick, _, engs = rest.partition(":")
        engines = tuple(int(e) for e in engs.split(",")) if engs else ()
        return FaultSpec(kind=kind, tick=int(tick), engines=engines)

    injector = FaultInjector([parse_fault(s) for s in cfg.fault]) \
        if cfg.fault else None

    if cfg.real:
        import jax
        from repro.core.engine import FlyingEngine
        from repro.core.modes import FlyingMode, mode_mesh
        from repro.core.weights_manager import WeightsManager
        from repro.models.model import build_model
        mcfg = get_config(cfg.arch)
        # one engine per device: DP across devices, TP by merging engines
        plan = ParallelPlan(engine_rows=1, tp_base=1,
                            data_rows=len(jax.devices()))
        model = build_model(mcfg)                       # bf16 weights
        params = WeightsManager(mcfg, plan).init_params(
            model, mode_mesh(FlyingMode(plan, 1)), jax.random.key(cfg.seed))
        max_blocks = -(-MAX_CONTEXT // BLOCK_TOKENS)
        blocks = kv_pool_blocks(
            model, PoolGeometry(mcfg, plan, 1, BLOCK_TOKENS),
            demand=DECODE_BATCH * max_blocks + 1)
        geom = PoolGeometry(mcfg, plan, num_blocks=blocks,
                            block_base=BLOCK_TOKENS)
        print(f"kv pool: {blocks} blocks x {BLOCK_TOKENS} tokens per "
              f"device ({blocks * BLOCK_TOKENS} tokens), "
              f"{len(jax.devices())} engine(s)")
        backend = FlyingEngine(model, plan, geom, params,
                               batch_per_engine=DECODE_BATCH,
                               max_blocks_per_req=max_blocks,
                               prefill_len=PREFILL_CHUNK,
                               injector=injector)
        sched = DynamicScheduler(
            plan, geom, backend,
            SchedulerConfig(strategy=cfg.strategy,
                            max_batch_per_group=DECODE_BATCH,
                            prefill_chunk=PREFILL_CHUNK,
                            prefix_cache=cfg.prefix_cache,
                            fixed_merge=cfg.fixed_merge or None),
            policy=cfg.policy())
        if cfg.fixed_merge and cfg.fixed_merge != 1:
            # static baseline: bind the engine (and shared adaptors) to
            # the pinned mode once at startup — the scheduler never
            # issues a transition for fixed_merge runs
            backend.switch(1, cfg.fixed_merge)
        spec = WorkloadSpec(n_requests=cfg.requests, seed=cfg.seed,
                            prompt_range=(128, 2048), output_range=(32, 128),
                            low_rate=(20, 50), burst_rate=(100, 200),
                            phase_seconds=0.5,
                            priority_frac=cfg.priority_frac)
        if cfg.prefix_cache:
            spec.prefix_pool = cfg.prefix_pool
            spec.prefix_hit = cfg.prefix_hit
            spec.prefix_range = (64, 512)
    else:
        mcfg = get_config(cfg.arch)
        plan = ParallelPlan(engine_rows=mcfg.engine_rows, tp_base=16,
                            data_rows=16)
        from repro.serving.simulator import CostModel, SimBackend
        kv_per_tok = mcfg.kv_cache_dims_per_token * mcfg.num_layers * 2 \
            / (plan.engine_rows * plan.tp_base)
        budget = 16e9 - mcfg.num_params() * 2 / (plan.engine_rows * 16) \
            - 2e9
        blocks = max(int(budget / max(kv_per_tok, 1) / 16), 1024)
        geom = PoolGeometry(mcfg, plan, num_blocks=blocks, block_base=16)
        backend = SimBackend(CostModel(mcfg, plan),
                             switch_mode=cfg.switch, injector=injector)
        sched = DynamicScheduler(
            plan, geom, backend,
            SchedulerConfig(strategy=cfg.strategy,
                            prefix_cache=cfg.prefix_cache,
                            fixed_merge=cfg.fixed_merge or None),
            policy=cfg.policy())
        spec = WorkloadSpec(n_requests=cfg.requests, seed=cfg.seed,
                            phase_seconds=30.0,
                            priority_frac=cfg.priority_frac)
        if cfg.prefix_cache:
            spec.prefix_pool = cfg.prefix_pool
            spec.prefix_hit = cfg.prefix_hit
            spec.prefix_range = (512, 2048)

    spec.arrival = cfg.arrival
    spec.rate = cfg.rate
    spec.background_frac = cfg.background_frac
    spec.cancel_frac = cfg.cancel_frac
    return sched, spec, injector


def build_door(cfg: ServeConfig, sched):
    from repro.serving.frontdoor import (FrontDoor, FrontDoorConfig,
                                         SLOClass)
    tiers = (SLOClass("priority", priority=1,
                      deadline_ttft=cfg.ttft_deadline or None,
                      deadline_tpot=cfg.tpot_deadline or None),
             SLOClass("standard"),
             SLOClass("background", sheddable=True))
    return FrontDoor(sched, FrontDoorConfig(
        queue_cap=cfg.queue_cap, shed=not cfg.no_shed,
        enforce_deadlines=not cfg.no_shed, tiers=tiers))


# ---------------------------------------------------------------------------
# --serve: the always-on HTTP server
# ---------------------------------------------------------------------------

def serve_http(cfg: ServeConfig) -> None:
    import asyncio

    from repro.serving.asyncloop import AsyncServeLoop
    from repro.serving.metrics import RollingTierMetrics
    from repro.serving.server import ServeHTTP

    sched, _spec, _inj = build_stack(cfg)
    door = build_door(cfg, sched)
    loop = AsyncServeLoop(
        door, pace=cfg.pace, stream_buf=cfg.stream_buf,
        wall_dilation=cfg.wall_dilation,
        rolling=RollingTierMetrics(window_s=cfg.metrics_window))

    async def main():
        srv = await ServeHTTP(loop).start(cfg.host, cfg.port)
        print(f"serving on http://{cfg.host}:{srv.port}  "
              f"(pace={cfg.pace}, forecast={cfg.forecast}, "
              f"arch={cfg.arch}, backend="
              f"{'real' if cfg.real else 'sim'})")
        print("  POST /v1/completions | /v1/chat/completions   "
              "GET /metrics /healthz")
        try:
            await srv.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await srv.stop()
            door.shutdown(cfg.diagnostic or None)

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        print("\nshutdown")


# ---------------------------------------------------------------------------
# offline trace replay (the original mode)
# ---------------------------------------------------------------------------

def run_offline(cfg: ServeConfig) -> None:
    from repro.core.scheduler import SchedulerWedged
    from repro.serving.metrics import summarize, tier_report
    from repro.serving.workload import generate

    sched, spec, injector = build_stack(cfg)

    def write_diag(diag: dict):
        if cfg.diagnostic:
            with open(cfg.diagnostic, "w") as f:
                json.dump(diag, f, indent=2, sort_keys=True, default=str)
                f.write("\n")
            print(f"  diagnostic    : {cfg.diagnostic}")

    frontdoor = None
    if cfg.frontdoor:
        frontdoor = build_door(cfg, sched)
        try:
            for r in generate(spec):
                frontdoor.submit(copy.deepcopy(r))
            frontdoor.run()
        except SchedulerWedged as w:
            print(f"WEDGED: {w.args[0]}")
            write_diag(frontdoor.diagnostic("wedged"))
            raise
    else:
        for r in generate(spec):
            sched.submit(copy.deepcopy(r))
        try:
            sched.run()
        except SchedulerWedged as w:
            print(f"WEDGED: {w.args[0]}")
            write_diag(w.diagnostic.to_dict()
                       if w.diagnostic is not None else {})
            raise
    m = summarize(sched.pool.all.values())
    print(f"arch={cfg.arch} strategy={cfg.strategy} "
          f"fixed_merge={cfg.fixed_merge or 'dynamic'}")
    print(f"  requests done : "
          f"{sum(1 for r in sched.pool.all.values() if r.state == 'done')}"
          f"/{len(sched.pool.all)}")
    print(f"  mean TTFT     : {m.mean_ttft * 1e3:9.1f} ms")
    print(f"  P90 TTFT      : {m.p90_ttft * 1e3:9.1f} ms")
    print(f"  P90 queue     : {m.p90_queue * 1e3:9.1f} ms")
    print(f"  median TPOT   : {m.median_tpot * 1e3:9.2f} ms")
    print(f"  peak tput     : {m.peak_throughput:9.0f} tok/s")
    print(f"  mode switches : {sched.switches}")
    print(f"  preempts      : {sched.preempt_stats}")
    if cfg.prefix_cache and sched.prefix_cache is not None:
        s = sched.prefix_cache.stats
        tot = s["hit_requests"] + s["miss_requests"]
        print(f"  prefix cache  : {s['hit_requests']}/{tot} hits "
              f"({s['hit_tokens']} tokens), "
              f"{s['inserted_blocks']} blocks inserted, "
              f"{s['evictions']} evicted")
    if injector is not None or sched.quarantined or sched.incidents:
        print(f"  quarantined   : {sorted(sched.quarantined)}")
        print(f"  recovered     : {sched.preempt_stats['recovered']} reqs, "
              f"{sched.preempt_stats['recomputed_tokens']} tokens "
              f"recomputed")
        print(f"  degraded ticks: {sched.preempt_stats['degraded_ticks']}"
              f"  rollbacks: {sched.preempt_stats['rollbacks']}")
        for inc in sched.incidents:
            extra = {k: v for k, v in inc.items()
                     if k not in ("t", "tick", "kind", "snapshot")}
            print(f"    incident t={inc['t']:.3f} tick={inc['tick']} "
                  f"{inc['kind']}: {extra}")
    if frontdoor is not None:
        print(f"  lifecycle     : {sched.lifecycle} "
              f"rejected={frontdoor.counters['rejected']}")
        for tier, row in tier_report(
                list(frontdoor.requests.values())).items():
            print(f"  tier {tier:<10}: n={row['n']} done={row['done']} "
                  f"shed={row['shed']} expired={row['expired']} "
                  f"p99_ttft={row['p99_ttft_s'] * 1e3:.1f}ms "
                  f"goodput={row['goodput']:.2f}")
        # graceful drain: admission is already empty here, so this just
        # emits the structured shutdown artifact
        diag = frontdoor.shutdown(cfg.diagnostic or None)
        if cfg.diagnostic:
            print(f"  diagnostic    : {cfg.diagnostic}")
        del diag
    elif cfg.diagnostic:
        write_diag(sched._diagnostic().to_dict())


def main(argv=None):
    cfg = parse_config(argv)
    if cfg.real:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    if cfg.profile_port:
        # a live server a profiler can capture host spans and device
        # activity from (TensorBoard's or XProf's "capture profile"); the
        # program's spans are on while a capture records
        import jax
        jax.profiler.start_server(cfg.profile_port)
    if cfg.serve:
        serve_http(cfg)
    else:
        run_offline(cfg)


if __name__ == "__main__":
    main()
