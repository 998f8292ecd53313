"""Live cross-layout KV reads (docs/PERF.md §D8): CPU units.

Covers the adaptor's per-segment contract (group-aware allocation,
pending-slot retag, owner-scoped release, the two admission/table
bugfixes), the per-segment partial-attention math against a dense
reference (both ranks of a merge-2 group simulated on one device, on
the jnp ref and the interpret-mode Pallas kernel), and the scheduler's
LIVE gating plus the stranded-paused run() fix."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.configs import get_config
from repro.core.kv_adaptor import (KVCacheAdaptor, PoolGeometry,
                                   bind_fleet)
from repro.core.modes import FleetLayout, ParallelPlan
from repro.core.scheduler import (HARD, LIVE, DynamicScheduler,
                                  SchedulerConfig)
from repro.core.task_pool import Request
from repro.serving.simulator import CostModel, SimBackend

PLAN = ParallelPlan(engine_rows=1, tp_base=1, data_rows=8)


def geom_for(blocks=32, base=4, arch="stablelm-1.6b"):
    return PoolGeometry(get_config(arch).reduced(), PLAN,
                        num_blocks=blocks, block_base=base)


# ---------------------------------------------------------------------------
# adaptor: segments, group allocation, retag
# ---------------------------------------------------------------------------

def test_group_allocation_never_clobbers_sibling_blocks():
    """After a merge, group allocations must skip block ids a member's
    live (old-tag) requests still hold — the merged group writes every
    member's pool at the allocated id."""
    g = geom_for()
    ads = [KVCacheAdaptor(g) for _ in range(8)]
    L1 = FleetLayout.uniform(PLAN, 1)
    bind_fleet(ads, L1)
    ads[0].append_slots("a", 10)
    ads[1].append_slots("b", 6)      # same pop order -> same ids as a's
    bind_fleet(ads, L1.carve(0, 2, 2))
    ads[0].append_slots("a", 5)      # new tag-2 segment, group allocation
    held_b = set(ads[1].table["b"].block_ids)
    new_seg = ads[0].table["a"].segments[-1]
    assert new_seg.tag == 2
    assert not set(new_seg.ids) & held_b, \
        "group allocation reused a block the sibling's request holds"
    # group-free accounting agrees on both members
    assert ads[0].free_blocks() == ads[1].free_blocks()


def test_release_returns_segments_to_their_owners():
    g = geom_for()
    ads = [KVCacheAdaptor(g) for _ in range(8)]
    bind_fleet(ads, FleetLayout.uniform(PLAN, 1))
    free_a0, free_b0 = len(ads[0]._free_set), len(ads[1]._free_set)
    ads[0].append_slots("a", 10)
    bind_fleet(ads, FleetLayout.uniform(PLAN, 1).carve(0, 2, 2))
    ads[0].append_slots("a", 9)      # tag-2 segment owned by (0, 1)
    ads[0].release("a")
    assert len(ads[0]._free_set) == free_a0
    assert len(ads[1]._free_set) == free_b0


def test_retag_tail_moves_pending_slot_to_new_segment():
    g = geom_for(base=4)
    ad = KVCacheAdaptor(g)
    ad.append_slots("r", 9)          # 8 written + 1 pending, cap 4
    ad.switch_mode(2)
    ad.retag_tail("r")
    e = ad.table["r"]
    assert e.tags() == (1, 2)
    assert e.seg_tokens(0) == 8 and e.seg_tokens(1) == 1
    assert e.length == 9
    # rolling back freed the tag-1 block the pending slot had opened
    assert len(e.segments[0].ids) == 2
    # idempotent once the tail is already current-tag
    ad.retag_tail("r")
    assert e.tags() == (1, 2) and e.length == 9


def test_retag_tail_drops_emptied_segment():
    g = geom_for(base=4)
    ad = KVCacheAdaptor(g)
    ad.append_slots("r", 5)          # 4 in block 0, pending in block 1
    ad.switch_mode(2)
    ad.retag_tail("r")               # [1 (4 tok), 2 (1 tok)]
    ad.switch_mode(4)
    ad.retag_tail("r")               # tag-2 segment empties -> dropped
    e = ad.table["r"]
    assert e.tags() == (1, 4)
    assert e.length == 5


# ---------------------------------------------------------------------------
# satellite bugfixes: can_allocate mirror + block-table overflow
# ---------------------------------------------------------------------------

def test_can_allocate_counts_partial_block_space():
    """Bugfix: can_allocate must mirror allocate's need math — blocks
    the request already holds and free space in its last partial block
    count toward the need (the seed version refused admissions that
    allocate would have satisfied)."""
    g = geom_for(blocks=3, base=4)
    ad = KVCacheAdaptor(g)
    ad.append_slots("r", 6)          # 2 blocks (free pool now empty)
    assert ad.free_blocks() == 0
    assert ad.can_allocate(2, req_id="r")        # fits the partial block
    assert not ad.can_allocate(3, req_id="r")    # would need a 3rd block
    # without req_id the seed-era conservative answer remains
    assert not ad.can_allocate(2)
    # and allocate agrees with the mirror
    ad.append_slots("r", 2)
    with pytest.raises(MemoryError):
        ad.append_slots("r", 1)


def test_block_table_overflow_raises_instead_of_truncating():
    """Bugfix: silently truncating a block list drops the context tail
    from attention; the builders must raise, naming the request."""
    g = geom_for(blocks=32, base=4)
    ad = KVCacheAdaptor(g)
    ad.append_slots("big", 20)       # 5 blocks
    with pytest.raises(ValueError, match="big"):
        ad.block_table("big", 4)
    with pytest.raises(ValueError, match="big"):
        ad.block_table_batch(["big"], 4)
    # exact fit is fine
    assert ad.block_table("big", 5).shape == (5,)


# ---------------------------------------------------------------------------
# per-segment partial attention == dense reference (both ranks simulated)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_cross_tag_read_matches_dense_reference(impl):
    """A merge-2 group reading a request whose KV spans a tag-1 segment
    (all heads on rank 0's pool) and a tag-2 segment (head-split across
    both ranks): per-tag sweeps + scatter + LSE merges must equal dense
    attention over the concatenated context. Runs the exact helper
    stack the LiveDecodeBackend uses, with both ranks simulated
    sequentially on one device."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.models.cache import _merge_sweeps, _seg_scatter

    rng = np.random.default_rng(7)
    H = KV = 4
    hd = 64
    bb, nb = 4, 8
    L1, L2 = 6, 3                   # tag-1 / tag-2 token counts
    B = 1
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k_ctx = rng.normal(size=(L1 + L2, KV, hd)).astype(np.float32)
    v_ctx = rng.normal(size=(L1 + L2, KV, hd)).astype(np.float32)

    # per-rank pool bytes, [nb, bb*KV*hd] (filled through the views)
    flat = [np.zeros((nb, bb * KV * hd), np.float32) for _ in range(2)]
    flat_v = [np.zeros((nb, bb * KV * hd), np.float32) for _ in range(2)]
    # tag-1 segment: blocks 0-1 on rank 0 (owner engine), view
    # [nb, bb, KV, hd]
    ids1 = [0, 1]
    for t in range(L1):
        blk, off = ids1[t // bb], t % bb
        flat[0].reshape(nb, bb, KV, hd)[blk, off] = k_ctx[t]
        flat_v[0].reshape(nb, bb, KV, hd)[blk, off] = v_ctx[t]
    # tag-2 segment: block 2 on BOTH ranks, view [nb, 2*bb, KV//2, hd];
    # rank v holds heads [v*2, v*2+2)
    ids2 = [2]
    for t in range(L2):
        blk, off = ids2[t // (2 * bb)], t % (2 * bb)
        for v_rank in range(2):
            sl = slice(v_rank * 2, v_rank * 2 + 2)
            flat[v_rank].reshape(nb, 2 * bb, KV // 2, hd)[blk, off] = \
                k_ctx[L1 + t, sl]
            flat_v[v_rank].reshape(nb, 2 * bb, KV // 2, hd)[blk, off] = \
                v_ctx[L1 + t, sl]

    segs = [  # (tag, ids, seg_len, owner_offset)
        (1, ids1, L1, 0),
        (2, ids2, L2, 0),
    ]
    rank_parts = []
    for v_rank in range(2):
        partials = []
        for tag, ids, ln, own in segs:
            Hq = H // tag
            # the stored pool [nb, bb, KV*hd], read under head split tag
            phys_k = jnp.asarray(flat[v_rank]).reshape(nb, bb, KV * hd)
            phys_v = jnp.asarray(flat_v[v_rank]).reshape(nb, bb, KV * hd)
            ok = own <= v_rank < own + tag
            eff = jnp.asarray([ln if ok else 0], jnp.int32)
            v_old = int(np.clip(v_rank - own, 0, tag - 1))
            q_sub = q[:, v_old * Hq:(v_old + 1) * Hq]
            bt = np.zeros((B, len(ids)), np.int32)
            bt[0, :] = ids
            out_t, lse_t = pa_ops.paged_attention_with_lse(
                q_sub, phys_k, phys_v, jnp.asarray(bt), eff, hs=tag,
                softmax_scale=hd ** -0.5, impl=impl)
            partials.append(_seg_scatter(
                out_t, lse_t, jnp.asarray([v_old]),
                jnp.asarray([ok and ln > 0]), H, 1))
        m_loc, ws, l_loc = _merge_sweeps(partials)
        acc = sum(o * w[..., None] for (o, _), w in zip(partials, ws))
        rank_parts.append((np.asarray(acc), np.asarray(l_loc),
                           np.asarray(m_loc)))

    # cross-rank LSE merge (what ctx.lse_merge(axes=('merge',)) does)
    m_g = np.maximum(rank_parts[0][2], rank_parts[1][2])
    num = sum(a * np.exp(m - m_g)[..., None] for a, _, m in rank_parts)
    den = sum(l * np.exp(m - m_g) for _, l, m in rank_parts)
    merged = num / np.maximum(den[..., None], 1e-30)

    # dense reference over the concatenated context, all heads
    from repro.models.cache import attention_with_lse
    kd = jnp.asarray(k_ctx)[None]
    vd = jnp.asarray(v_ctx)[None]
    mask = jnp.ones((B, 1, 1, L1 + L2), bool)
    want, _ = attention_with_lse(q[:, None], kd, vd, mask, hd ** -0.5)
    np.testing.assert_allclose(merged, np.asarray(want[:, 0]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# scheduler: LIVE gating + stranded-paused run() fix
# ---------------------------------------------------------------------------

def _sim_sched(strategy, geom=None, merges=None):
    cfg = get_config("stablelm-1.6b").reduced()
    geom = geom or PoolGeometry(cfg, PLAN, num_blocks=256, block_base=4)
    be = SimBackend(CostModel(cfg, PLAN))
    return DynamicScheduler(PLAN, geom, be,
                            SchedulerConfig(strategy=strategy))


def _admit_running(sched, n, out_len=64):
    for i in range(n):
        sched.submit(Request(req_id=f"r{i}", arrival=0.0, prompt_len=8,
                             output_len=out_len))
    for _ in range(6):
        sched.step()
    assert sched.running


def test_live_merge_up_returns_empty_incompatible():
    """§D8: for a tag-readable architecture a merge-up's incompatible
    set is EMPTY — in-flight requests ride; the same transition under
    HARD pauses them."""
    sched = _sim_sched(LIVE)
    _admit_running(sched, 6)
    target = FleetLayout.uniform(PLAN, 2)
    assert sched._incompatible(target) == []
    assert sched._transition(target)
    assert sched.preempt_stats["paused"] == 0
    assert sched.preempt_stats["live_riders"] >= 1
    # riders' pending slots were re-issued under the new tag
    for r in sched.running:
        e = sched._entry(r)
        assert e.segments[-1].tag == 2, e.tags()


def test_live_merge_down_still_pauses():
    """Merge-downs are never live (the owner engines fall outside the
    narrower group): tag-2 requests pause exactly as under HARD."""
    sched = _sim_sched(LIVE)
    _admit_running(sched, 4)
    sched._transition(FleetLayout.uniform(PLAN, 2))
    for r in sched.running:
        sched._retag_or_recompute(r)
    down = FleetLayout.uniform(PLAN, 1)
    inc = sched._incompatible(down)
    assert inc, "tag-2 requests must be incompatible with merge-down"
    sched._transition(down)
    assert sched.preempt_stats["paused"] >= len(inc)


def test_live_gate_respects_architecture():
    """MQA-style head layouts (single KV head) are not tag-readable:
    LIVE degrades to HARD for them."""
    cfg = get_config("llama3-8b").reduced()   # reduced => kv=1 (MQA)
    geom = PoolGeometry(cfg, PLAN, num_blocks=256, block_base=4)
    assert not geom.live_readable(2)
    be = SimBackend(CostModel(cfg, PLAN))
    sched = DynamicScheduler(PLAN, geom, be,
                             SchedulerConfig(strategy=LIVE))
    _admit_running(sched, 4)
    inc = sched._incompatible(FleetLayout.uniform(PLAN, 2))
    assert inc, "non-readable architecture must keep the HARD behavior"


def test_run_force_resumes_stranded_paused():
    """Bugfix: run(until_drained=True) used to hit the 'nothing runnable
    but work exists' branch and silently return with paused requests
    stranded; it must now force the minimal resume transition and
    finish the work."""
    sched = _sim_sched(HARD)
    _admit_running(sched, 2, out_len=8)
    # pause everything via a merge-up, then empty the queue so nothing
    # ever becomes runnable without a resume
    sched._transition(FleetLayout.uniform(PLAN, 2))
    assert sched.paused and not sched.running
    # block the opportunistic resume path by marking every island busy
    # for the first few steps (simulates the mid-rebind window)
    sched._busy_islands = set(sched.layout.islands)
    sched.run(until_drained=True, max_steps=500)
    assert not sched.paused
    done = sum(1 for r in sched.pool.all.values() if r.state == "done")
    assert done == len(sched.pool.all)


def test_run_raises_when_wedged():
    """If even the forced resume cannot release a paused request, run()
    must surface a RuntimeError instead of silently dropping work."""
    sched = _sim_sched(HARD)
    r = Request(req_id="ghost", arrival=0.0, prompt_len=8, output_len=8)
    r.state = "paused"
    r.engine_group = 1
    r.prefilled = 8
    # a tag-2 entry whose lead engine (1) can never LEAD a merge-2
    # group: _group_restored stays False for every carve
    ads = sched.adaptors
    bind_fleet(ads, FleetLayout.uniform(PLAN, 2))
    ads[1].append_slots("ghost", 8)
    bind_fleet(ads, FleetLayout.uniform(PLAN, 1))
    sched.paused.append(r)
    with pytest.raises(RuntimeError, match="paused"):
        sched.run(until_drained=True, max_steps=50)
