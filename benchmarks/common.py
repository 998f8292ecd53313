"""Shared benchmark harness: run one serving system over one workload on
the simulation backend (roofline cost model; same offered load across
systems — paper §6.2)."""
from __future__ import annotations

import copy
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.configs import get_config
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import ParallelPlan
from repro.core.policy import FlyingPolicy
from repro.core.scheduler import (HARD, LIVE, DynamicScheduler,
                                  SchedulerConfig)
from repro.serving.metrics import Summary, summarize
from repro.serving.simulator import CostModel, SimBackend
from repro.serving.workload import WorkloadSpec, generate

# paper evaluation models (§6.1.2) mapped to our registered configs
PAPER_MODELS = {
    "Llama-3-70B": "paper-llama3-70b",
    "GPT-OSS-120B": "paper-gpt-oss-120b",
    "Nemotron-8B": "paper-nemotron-8b",
}

SYSTEMS = ("static-DP", "static-TP", "shift-parallelism", "flying",
           "flying-island", "flying-live")


def build_sched(arch: str, system: str, *, strategy: str = HARD,
                blocks: Optional[int] = None):
    cfg = get_config(arch)
    plan = ParallelPlan(engine_rows=cfg.engine_rows, tp_base=16,
                        data_rows=16)
    if blocks is None:
        kv_tok = max(cfg.kv_cache_dims_per_token * cfg.num_layers * 2
                     / (plan.engine_rows * 16), 1)
        budget = 16e9 - cfg.num_params() * 2 / (plan.engine_rows * 16) - 1e9
        blocks = max(int(budget / kv_tok / 16), 2048)
    # flying-live pairs the LIVE transition strategy with the striped
    # pool layout (Eq. 3 — and tag-readability — hold universally there,
    # docs/PERF.md §D8); the other systems keep the paper's head layout
    layout = "striped" if system == "flying-live" else "head"
    geom = PoolGeometry(cfg, plan, num_blocks=blocks, block_base=16,
                        layout=layout)
    cost = CostModel(cfg, plan)
    fixed = None
    policy = None
    switch = "flying"
    penalty = 1.0
    if system == "static-DP":
        fixed = 1
    elif system == "static-TP":
        fixed = plan.valid_merges()[-1]
    elif system == "shift-parallelism":
        # proxy for [39]: dynamic TP<->SP switching; near-zero switch cost
        # but its throughput mode (SP) pays a sequence-parallel overhead
        # and it cannot serve MoE (paper footnote 5)
        if cfg.moe is not None:
            return None
        policy = FlyingPolicy(islands=False)
        penalty = 0.8
    elif system == "flying":
        # the paper's uniform modes: fleet-wide merges, full HARD pauses
        policy = FlyingPolicy(islands=False)
    elif system == "flying-live":
        # uniform modes WITHOUT the pause: in-flight requests ride
        # merge-ups in place (zero paused, zero recomputed — §D8)
        policy = FlyingPolicy(islands=False, live=True)
        strategy = LIVE
    else:  # flying-island: per-island DP/TP coexistence, partial rebinds
        policy = FlyingPolicy()
    be = SimBackend(cost, switch_mode=switch,
                    dp_throughput_penalty=penalty)
    sched = DynamicScheduler(plan, geom, be,
                             SchedulerConfig(strategy=strategy,
                                             fixed_merge=fixed),
                             policy=policy)
    return sched


def run_workload(arch: str, system: str, spec: WorkloadSpec, *,
                 strategy: str = HARD) -> Optional[Dict]:
    sched = build_sched(arch, system, strategy=strategy)
    if sched is None:
        return None
    for r in generate(spec):
        sched.submit(copy.deepcopy(r))
    sched.run()
    m = summarize(sched.pool.all.values())
    mp = summarize(sched.pool.all.values(), priority_only=True)
    return {"summary": m, "priority": mp, "switches": sched.switches,
            "sched": sched}


def csv_row(bench: str, name: str, value, derived: str = "") -> str:
    return f"{bench},{name},{value},{derived}"


def cpu_rehearsal_env(n_devices: int) -> Dict[str, str]:
    """Environment for a child process that rehearses a multi-device path
    on ``n_devices`` emulated CPU devices. JAX is pinned to the CPU: the
    parent may hold the chip, and a child reaching for it would fail or
    hang. The device-count flag is appended to the caller's XLA_FLAGS."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count=" \
            f"{n_devices}".strip()
    env["XLA_FLAGS"] = flags
    return env
