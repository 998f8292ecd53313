"""Elastic sequence parallelism (docs/PERF.md §D12), single device:
placement-tag algebra on islands and layouts, round-robin SP block
allocation (conservation, transactionality, cursor continuity),
cross-shard LSE-combine parity against a dense reference on both
kernel dispatch impls, and the scheduler/policy/front-door gating —
UC3 carving an SP island for a context no merge group can pool, served
live with zero pauses."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.kv_adaptor import KVCacheAdaptor, PoolGeometry, bind_fleet
from repro.core.modes import FleetLayout, Island, ParallelPlan
from repro.core.policy import FlyingPolicy
from repro.core.scheduler import (LIVE, DynamicScheduler, SchedulerConfig,
                                  SchedulerWedged)
from repro.core.task_pool import Request
from repro.kernels.paged_attention.ops import paged_attention_with_lse
from repro.serving.frontdoor import FrontDoor
from repro.serving.simulator import CostModel, SimBackend

PLAN = ParallelPlan(engine_rows=1, tp_base=16, data_rows=16)


def geom_for(blocks=64, base=16, arch="stablelm-1.6b", layout="head"):
    return PoolGeometry(get_config(arch), PLAN, num_blocks=blocks,
                        block_base=base, layout=layout)


# ---------------------------------------------------------------------
# placement-tag algebra
# ---------------------------------------------------------------------
def test_island_sp_identity():
    isl = Island(0, 4, 4, sp=4)
    assert isl.write_tag == 1
    assert isl.group_of(2) == (0, 4, 4)
    assert "SP" in isl.describe()
    # sp is part of the group identity: an SP-degree-only change is a
    # rebind for the island's engines, and nothing else
    a = FleetLayout.of(PLAN, [(4, 4, 4), (4, 1), (8, 1)])
    b = FleetLayout.of(PLAN, [(4, 4, 2), (4, 1), (8, 1)])
    assert a.island_of(0).sp == 4 and b.island_of(0).sp == 2
    assert a.changed_engines(b) == frozenset(range(4))
    # carve preserves neighbors; dissolved() drops SP back to DP
    c = a.carve(8, 4, 4, sp=4)
    assert c.island_of(0).sp == 4 and c.island_of(8).sp == 4
    assert all(i.sp == 1 for i in a.dissolved().islands)


def test_island_sp_validation():
    with pytest.raises(ValueError):
        Island(0, 4, 4, sp=3)        # not a pow2
    with pytest.raises(ValueError):
        Island(0, 4, 2, sp=4)        # sp must divide merge


def test_max_context_scales_with_sp():
    g = geom_for()
    ad = KVCacheAdaptor(g)
    one = ad.max_context_tokens(1)
    # pure SP pools s engines' block budgets at write tag 1: capacity
    # scales with engine COUNT even where head-splitting is exhausted
    for s in (2, 4, 8):
        assert ad.max_context_tokens(s, sp=s) == s * one


# ---------------------------------------------------------------------
# round-robin SP allocation
# ---------------------------------------------------------------------
def sp_fleet(blocks=8, sp=4):
    g = geom_for(blocks=blocks, base=16)
    ads = [KVCacheAdaptor(g) for _ in range(16)]
    rest = [(4, 1)] * ((16 - sp) // 4)
    layout = FleetLayout.of(PLAN, [(sp, sp, sp)] + rest)
    bind_fleet(ads, layout)
    return g, ads, layout


def test_sp_alloc_round_robins_and_conserves():
    g, ads, _ = sp_fleet()
    cap = g.capacity(1)
    free0 = [a.free_blocks() for a in ads[:4]]
    ads[0].append_slots("r", 6 * cap)        # 6 blocks over a 4-ring
    ent = ads[0].table["r"]
    assert all(s.shard >= 0 and len(s.ids) == 1 for s in ent.segments)
    spread = {}
    for s in ent.segments:
        spread[s.shard] = spread.get(s.shard, 0) + 1
    assert spread == {0: 2, 1: 2, 2: 1, 3: 1}
    assert ent.sp_cursor == 6
    # owners are the shard's write-tag group, disjoint token ranges
    starts = sorted(s.start for s in ent.segments)
    assert starts == [i * cap for i in range(6)]
    ads[0].release("r")
    assert [a.free_blocks() for a in ads[:4]] == free0


def test_sp_alloc_transactional_on_shard_exhaustion():
    g, ads, _ = sp_fleet(blocks=4)           # 3 usable blocks per shard
    cap = g.capacity(1)
    before = [a.free_blocks() for a in ads[:4]]
    assert not ads[0].can_allocate(16 * cap)
    with pytest.raises(MemoryError, match="SP shard"):
        ads[0].append_slots("big", 16 * cap)  # 4 blocks on some shard
    # the failed allocation took NOTHING from any shard
    assert [a.free_blocks() for a in ads[:4]] == before
    assert "big" not in ads[0].table


def test_sp_truncate_rolls_cursor_back():
    g, ads, _ = sp_fleet()
    cap = g.capacity(1)
    ads[0].append_slots("r", 5 * cap)
    assert ads[0].table["r"].sp_cursor == 5
    ads[0].truncate("r", 2 * cap)
    ent = ads[0].table["r"]
    assert len(ent.segments) == 3 and ent.sp_cursor == 3
    # the next block continues the rotation where the pop left it
    ads[0].append_slots("r", cap)
    assert ent.segments[-1].shard == 3


def test_sp_slot_math_matches_segment_placement():
    g, ads, _ = sp_fleet()
    cap = g.capacity(1)
    slots = ads[0].append_slots("r", 3 * cap)
    ent = ads[0].table["r"]
    want = []
    for s in ent.segments:
        want.extend(s.ids[0] * cap + k for k in range(cap))
    assert list(slots) == want


# ---------------------------------------------------------------------
# cross-shard LSE combine parity
# ---------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_cross_shard_lse_merge_matches_dense(impl):
    """Per-shard partial attention over disjoint token ranges, combined
    with the flash-style LSE merge, equals one dense sweep over the
    whole context — the §D12 correctness core."""
    B, H, KV, hd, page, nb = 2, 4, 2, 8, 4, 8
    ctx = 26
    key = jax.random.key(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)
    # physical pools [nb, page, KV*hd]
    k_pool = jax.random.normal(kk, (nb, page, KV * hd), jnp.float32)
    v_pool = jax.random.normal(kv_, (nb, page, KV * hd), jnp.float32)
    bt = jnp.tile(jnp.arange(nb, dtype=jnp.int32)[None], (B, 1))
    clen = jnp.full((B,), ctx, jnp.int32)
    full, _ = paged_attention_with_lse(q, k_pool, v_pool, bt, clen,
                                       impl=impl)

    # shard the BLOCKS round-robin over 2 "engines": each sweep sees
    # only its own blocks, compacted into a private table
    outs, lses = [], []
    for j in range(2):
        blocks = [b for b in range(nb) if b % 2 == j]
        tok = []
        for b in blocks:
            tok.extend(range(b * page, min((b + 1) * page, ctx)))
        n_live = sum(1 for t in tok if t < ctx)
        bt_j = jnp.tile(jnp.asarray(blocks, jnp.int32)[None], (B, 1))
        cl_j = jnp.full((B,), n_live, jnp.int32)
        o, l = paged_attention_with_lse(q, k_pool, v_pool, bt_j, cl_j,
                                        impl=impl)
        outs.append(np.asarray(o))
        lses.append(np.asarray(l))
    m = np.maximum(lses[0], lses[1])
    w = [np.exp(l - m) for l in lses]
    merged = ((outs[0] * w[0][..., None] + outs[1] * w[1][..., None])
              / (w[0] + w[1])[..., None])
    np.testing.assert_allclose(merged, np.asarray(full),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------
# scheduler / policy / front door gating
# ---------------------------------------------------------------------
CFG = get_config("llama3-8b")


# tp_base=8 on llama3-8b (8 KV heads): ONE kv head per engine, so the
# head-split capacity saturates at merge 1 — exactly the regime where
# sequence parallelism is the only way to grow per-request context —
# while tag-1 pools stay live-readable (§D8), so SP rides are available
SP_PLAN = ParallelPlan(engine_rows=1, tp_base=8, data_rows=16)


def make_sched(sp=True, blocks=20):
    geom = PoolGeometry(CFG, SP_PLAN, num_blocks=blocks, block_base=16,
                        layout="head")
    be = SimBackend(CostModel(CFG, SP_PLAN))
    sc = SchedulerConfig(strategy=LIVE)
    return DynamicScheduler(SP_PLAN, geom, be, sc,
                            policy=FlyingPolicy(live=True, sp=sp))


def merge_cap(s):
    widest = SP_PLAN.valid_merges()[-1]
    return s.geom.capacity(widest) * (s.geom.num_blocks - 1)


def test_uc3_carves_sp_island_and_serves_live():
    """A context beyond the widest merge's pool is admitted by carving
    a pure-SP island — served to completion with ZERO pauses and zero
    recomputation while background traffic keeps flowing."""
    s = make_sched()
    need = merge_cap(s) + 500
    s.submit(Request(req_id="long", arrival=0.0,
                     prompt_len=need - 32, output_len=32))
    for i in range(6):
        s.submit(Request(req_id=f"bg{i}", arrival=0.01 * i,
                         prompt_len=128, output_len=16))
    s.run()
    states = {r.req_id: r.state for r in s.pool.all.values()}
    assert all(v == "done" for v in states.values()), states
    assert s.preempt_stats["paused"] == 0
    assert s.preempt_stats["recomputed_tokens"] == 0
    assert any(isl.sp > 1 for isl in s.layout.islands)


def test_without_sp_long_context_wedges_loudly():
    s = make_sched(sp=False)
    need = merge_cap(s) + 500
    r = Request(req_id="long", arrival=0.0, prompt_len=need - 32,
                output_len=32)
    s.submit(r)
    # no SP: nothing in the fleet can ever hold it. The scheduler
    # surfaces the strand instead of spinning forever — the FRONT DOOR
    # is the structural guard (kv_never_fits, tested below)
    with pytest.raises(SchedulerWedged):
        s.run()


def test_frontdoor_structural_reject_and_sp_route():
    widest = SP_PLAN.valid_merges()[-1]

    def door(sp):
        s = make_sched(sp=sp)
        return FrontDoor(s), s

    fd, s = door(False)
    need = merge_cap(s) + 100
    assert not fd.submit(Request(req_id="huge", arrival=0.0,
                                 prompt_len=need, output_len=8))
    assert fd.reject_reasons["huge"] == "kv_never_fits"

    fd2, s2 = door(True)
    r = Request(req_id="huge", arrival=0.0, prompt_len=need, output_len=8)
    assert fd2.submit(r)          # SP-capable: routes instead
    sp_cap = widest * s2.geom.capacity(1) * (s2.geom.num_blocks - 1)
    assert not fd2.submit(Request(req_id="nofit", arrival=0.0,
                                  prompt_len=sp_cap + 100, output_len=8))
    assert fd2.reject_reasons["nofit"] == "kv_never_fits"
    fd2.run()
    assert fd2.state_of("huge") == "DONE"
    assert any(isl.sp > 1 for isl in s2.layout.islands)


def spin_until_decoding(s, r, steps=200):
    for _ in range(steps):
        s.step()
        if r in s.running and r.prefilled >= r.prompt_len:
            return
    raise AssertionError(f"{r.req_id} never started decoding")


def test_live_sp_degree_rebind_rides():
    """Widening an SP island's degree mid-decode is a LIVE ride:
    write_tag stays 1, the old shard segments remain readable, so the
    rebind pauses nothing and recomputes nothing."""
    s = make_sched()
    need = merge_cap(s) + 500
    r = Request(req_id="long", arrival=0.0, prompt_len=need - 32,
                output_len=64)
    s.submit(r)
    spin_until_decoding(s, r)
    isl = s.layout.island_of(0)
    assert isl.sp > 1 and isl.sp < 16
    assert s._transition(s.layout.carve(0, 16, 16, sp=16))
    assert r in s.running, "SP scale-up paused the rider"
    assert s.preempt_stats["live_riders"] >= 1
    assert s.preempt_stats["paused"] == 0
    s.run()
    assert r.state == "done"
    assert s.preempt_stats["recomputed_tokens"] == 0


def test_sp_island_dissolve_pauses_then_restores_placement():
    """Dissolving an SP island HARD-pauses its request (SP-placed KV is
    unreadable on plain DP groups — the _live_ok placement gate); the
    resume carve restores the SAME write placement (sp preserved) and
    the request finishes with zero recomputation."""
    s = make_sched()
    need = merge_cap(s) + 500
    r = Request(req_id="long", arrival=0.0, prompt_len=need - 32,
                output_len=64)
    s.submit(r)
    spin_until_decoding(s, r)
    gen0 = len(getattr(r, "tokens", [])) or r.prefilled
    assert s._transition(s.layout.dissolved())
    assert r not in s.running and r in s.paused, \
        "dissolve must pause an SP-placed request (no cross-placement ride)"
    assert s.preempt_stats["paused"] == 1
    # the minimal resume carve restores the SP placement verbatim
    target = s._resume_layout(r)
    isl = target.island_of(0)
    assert isl.sp > 1 and isl.write_tag == 1
    s.run()
    assert r.state == "done"
    assert s.preempt_stats["recomputed_tokens"] == 0
    assert r.prefilled >= gen0
