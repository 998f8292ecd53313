"""Live-switch microbench (docs/PERF.md §D8 — the PR's acceptance guard).

Real execution on emulated host devices: a fleet of single-device
engines decodes a batch of requests under merge=1; mid-decode a scripted
policy merges the fleet to TP2. Three runs over the SAME trace:

  - ``live``  (strategy=live): the rebind carries every in-flight decode
    across in place — ZERO paused requests, ZERO recomputed tokens, and
    the token streams are IDENTICAL to the never-switched reference.
  - ``hard``  (strategy=hard): the same rebind pauses the in-flight
    cohort until the opportunistic resume carves their groups back.
  - ``ref``   (fixed merge=1): the no-switch reference for token
    identity.

The TTFT-disruption guard compares the worst inter-token gap of the
in-flight cohort across the switch: LIVE must stay within 0.5x of
HARD's (in practice it is far below — HARD's gap spans the whole pause).

Run standalone (forces 4 host devices BEFORE jax imports):

    PYTHONPATH=src python benchmarks/live_switch.py

``benchmarks/run.py --smoke`` and table2 invoke it as a subprocess so
the device-count env var can take effect regardless of the parent
process's jax state.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N_REQ = 6
PROMPT = 8
OUTPUT = 24
PRIO_OUTPUT = 32     # the TP-bound request the paused cohort waits behind
INJECT_AFTER = 4     # background tokens decoded before the priority lands


class OneShotMerge:
    """Scripted UC2 policy: merge the fleet up exactly once, when the
    priority request appears, then hold whatever layout the scheduler
    settles on (so HARD's resume carves are not fought)."""

    def __init__(self, target):
        self.target = target
        self.fired_at = None

    def decide(self, sched):
        prio = any(r.priority > 0 for r in sched.waiting) or \
            any(r.priority > 0 for r in sched.pool.peek_arrived(sched.now))
        if self.fired_at is None and prio and sched.running:
            self.fired_at = sched.now
            return self.target
        return sched.layout


def _drive(eng, plan, geom, strategy, *, switch: bool):
    from repro.core.modes import FleetLayout
    from repro.core.scheduler import DynamicScheduler, SchedulerConfig
    from repro.core.task_pool import Request

    policy = OneShotMerge(FleetLayout.uniform(plan, 2)) if switch else None
    sched = DynamicScheduler(
        plan, geom, eng,
        SchedulerConfig(strategy=strategy, max_batch_per_group=4,
                        prefill_chunk=PROMPT,
                        fixed_merge=None if switch else 1),
        policy=policy)
    bg = [Request(req_id=f"r{i}", arrival=0.0, prompt_len=PROMPT,
                  output_len=OUTPUT) for i in range(N_REQ)]
    for r in bg:
        sched.submit(r)
    prio = Request(req_id="prio", arrival=0.0, prompt_len=PROMPT,
                   output_len=PRIO_OUTPUT, priority=1)
    injected = False
    for _ in range(5000):
        progressed = sched.step()
        if not injected and bg and \
                min(r.generated for r in bg) >= INJECT_AFTER:
            # the priority request lands mid-decode — schedule-
            # deterministic (token-count gated), identical across the
            # live / hard / reference runs
            prio.arrival = sched.now
            sched.submit(prio)
            injected = True
        if all(r.state == "done" for r in bg) and \
                (not injected or prio.state == "done"):
            break
        if not progressed and sched.pool.next_arrival() is None \
                and not (sched.waiting or sched.running or sched.paused):
            break
    eng.drain()
    toks = {r.req_id: list(eng.generated_tokens(r.req_id))
            for r in bg + [prio]}
    return sched, toks, policy


def _run_one(strategy, model, params, cfg, *, switch: bool):
    import jax.numpy as jnp  # noqa: F401  (keeps jax initialized first)
    from repro.core.engine import FlyingEngine
    from repro.core.kv_adaptor import PoolGeometry
    from repro.core.modes import ParallelPlan

    plan = ParallelPlan(engine_rows=1, tp_base=1, data_rows=4)
    geom = PoolGeometry(cfg, plan, num_blocks=64, block_base=4)
    eng = FlyingEngine(model, plan, geom, params, batch_per_engine=2,
                       prefill_len=PROMPT)
    # warm-up pass: populate the Communicator Pool's executable cache
    # (incl. the live-variant programs) exactly as the §4.3 startup
    # precompile would — the measured pass then sees the paper's O(1)
    # lookup at every rebind, not a cold XLA compile
    _drive(eng, plan, geom, strategy, switch=switch)
    eng.drain()
    eng._token_buf.clear()
    for a in eng.adaptors:
        for rid in list(a.table):
            a.release(rid)
    eng.rebind(1)
    for rt in eng.islands:
        # the measured pass reuses the warm-up's request ids: drop the
        # per-island decode caches so stale (released) entries cannot
        # satisfy the membership key
        rt.steady = None
    return _drive(eng, plan, geom, strategy, switch=switch)


def _max_token_gap(sched, t_switch):
    """Worst inter-token interval, across the switch, of the requests
    already decoding when the rebind fired."""
    worst = 0.0
    for r in sched.pool.all.values():
        ts = [t for t in r.token_times]
        if not ts or r.first_token_t is None or r.first_token_t > t_switch:
            continue
        for a, b in zip(ts, ts[1:]):
            if b >= t_switch >= a - 1e-9:
                worst = max(worst, b - a)
    return worst


def run(guard: bool = True):
    import jax
    import jax.numpy as jnp
    from benchmarks.common import csv_row
    from repro.configs import get_config
    from repro.models.model import build_model

    assert len(jax.devices()) >= 4, \
        "run standalone (the script forces 4 host devices) or via " \
        "benchmarks/run.py --smoke"
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))

    rows = []
    live, live_toks, live_pol = _run_one("live", model, params, cfg,
                                         switch=True)
    hard, hard_toks, hard_pol = _run_one("hard", model, params, cfg,
                                         switch=True)
    ref, ref_toks, _ = _run_one("hard", model, params, cfg, switch=False)

    done = sum(1 for r in live.pool.all.values() if r.state == "done")
    bg_keys = [f"r{i}" for i in range(N_REQ)]
    # acceptance: the in-flight cohort's streams are identical to the
    # never-switched reference (the priority request itself decodes
    # under TP2 vs the reference's merge-1 — same math, checked too)
    ident = all(live_toks[k] == ref_toks[k] for k in bg_keys) \
        and live_toks["prio"] == ref_toks["prio"]
    gap_live = _max_token_gap(live, live_pol.fired_at)
    gap_hard = _max_token_gap(hard, hard_pol.fired_at)
    ratio = gap_live / max(gap_hard, 1e-9)

    rows.append(csv_row("live_switch", "live/switches", str(live.switches)))
    rows.append(csv_row("live_switch", "live/done",
                        f"{done}/{len(live.pool.all)}"))
    rows.append(csv_row("live_switch", "live/paused_requests",
                        str(live.preempt_stats["paused"])))
    rows.append(csv_row("live_switch", "live/recomputed_tokens",
                        str(live.preempt_stats["recomputed_tokens"])))
    rows.append(csv_row("live_switch", "live/riders",
                        str(live.preempt_stats["live_riders"])))
    rows.append(csv_row("live_switch", "hard/paused_requests",
                        str(hard.preempt_stats["paused"])))
    rows.append(csv_row("live_switch", "live/token_identity_vs_noswitch",
                        "PASS" if ident else "FAIL"))
    rows.append(csv_row("live_switch", "live/max_token_gap_ms",
                        f"{gap_live * 1e3:.1f}"))
    rows.append(csv_row("live_switch", "hard/max_token_gap_ms",
                        f"{gap_hard * 1e3:.1f}"))
    rows.append(csv_row("live_switch", "live_vs_hard_gap", f"{ratio:.3f}",
                        "guard: <= 0.5"))
    if guard:
        assert live.switches >= 1 and live_pol.fired_at is not None
        assert live.preempt_stats["paused"] == 0, live.preempt_stats
        assert live.preempt_stats["recomputed_tokens"] == 0
        assert live.preempt_stats["live_riders"] >= N_REQ, \
            live.preempt_stats
        assert hard.preempt_stats["paused"] > 0, \
            "HARD baseline did not pause anyone: trace too easy"
        assert done == len(live.pool.all)
        assert ident, {k: (live_toks[k], ref_toks[k])
                       for k in live_toks if live_toks[k] != ref_toks[k]}
        assert ratio <= 0.5, \
            f"LIVE token gap {gap_live * 1e3:.1f}ms not <= 0.5x HARD's " \
            f"{gap_hard * 1e3:.1f}ms"
        rows.append(csv_row("live_switch", "guard", "PASS"))
    return rows


def run_subprocess():
    """Invoke this module in a fresh interpreter on 4 emulated CPU
    devices (a CPU rehearsal, never the chip) and return its CSV rows."""
    import subprocess

    from benchmarks.common import cpu_rehearsal_env, csv_row
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=cpu_rehearsal_env(4), capture_output=True, text=True,
        timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(
            f"live_switch microbench failed:\n{out.stdout}\n{out.stderr}")
    return [ln for ln in out.stdout.splitlines()
            if ln.startswith("live_switch,")] + [
        csv_row("live_switch", "backend", "cpu",
                "4 emulated CPU devices: a rehearsal, not a chip run")]


if __name__ == "__main__":
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=4").strip()
    for row in run(guard=True):
        print(row)
    print("LIVE SWITCH OK")
