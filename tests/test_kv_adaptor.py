"""KV Cache Adaptor property tests (paper §4.2 invariants)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.kv_adaptor import KVCacheAdaptor, PoolGeometry
from repro.core.modes import ParallelPlan

PLAN = ParallelPlan(engine_rows=1, tp_base=16, data_rows=16)


def geom_for(arch="stablelm-1.6b", layout="head", blocks=64, base=16):
    return PoolGeometry(get_config(arch), PLAN, num_blocks=blocks,
                        block_base=base, layout=layout)


def test_block_bytes_invariant_across_modes():
    """Paper Eq. 2: M_block constant; Eq. 3: B(p) = p * B_base (while
    heads split; striped: always)."""
    g = geom_for("stablelm-1.6b")  # kv=32 -> kvh_dev=2 at tp16
    elems0 = g.block_elems
    for m in (1, 2, 4):
        vs = g.view_shape(m)
        assert np.prod(vs[1:]) * 1 == elems0  # per-block elems constant
    assert g.capacity(1) == 16
    assert g.capacity(2) == 32          # head split 2 available
    assert g.capacity(4) == 32          # saturates at kvh_dev=2
    assert g.capacity_scales(2) and not g.capacity_scales(4)

    s = geom_for("llama3-8b", layout="striped")
    assert s.capacity(1) == 16 * 16     # full TP degree
    assert s.capacity(4) == 16 * 64
    for m in (1, 2, 4):
        assert s.capacity_scales(m)
        assert np.prod(s.view_shape(m)[1:]) == s.block_elems


def test_mla_capacity_does_not_head_scale():
    g = geom_for("deepseek-v2-236b")
    assert g.capacity(1) == g.capacity(4) == g.block_base
    s = geom_for("deepseek-v2-236b", layout="striped")
    # PLAN has engine_rows=1: stripe factor = merge * 1 * tp_base
    assert s.capacity(2) == g.block_base * 2 * 1 * 16


@given(st.lists(st.tuples(st.integers(1, 40), st.sampled_from([1, 2])),
                min_size=1, max_size=12))
@settings(max_examples=50, deadline=None)
def test_alloc_release_conservation(ops):
    """Allocating and releasing arbitrary requests conserves the block
    pool and never double-assigns a block."""
    g = geom_for()
    ad = KVCacheAdaptor(g)
    total = ad.free_blocks()
    live = {}
    for i, (toks, m) in enumerate(ops):
        if ad.table and i % 3 == 2:
            victim = next(iter(ad.table))
            ad.release(victim)
            live.pop(victim, None)
        rid = f"r{i}"
        if ad.can_allocate(toks):
            ad.append_slots(rid, toks)
            live[rid] = toks
        # no block shared between requests
        seen = set()
        for e in ad.table.values():
            for b in e.block_ids:
                assert b not in seen
                seen.add(b)
        assert ad.free_blocks() + len(seen) == total
    for rid in list(ad.table):
        ad.release(rid)
    assert ad.free_blocks() == total


@given(st.integers(1, 200), st.sampled_from([1, 2, 4]))
@settings(max_examples=50, deadline=None)
def test_slots_unique_and_in_range(n_tokens, merge):
    g = geom_for("stablelm-1.6b", blocks=256)
    ad = KVCacheAdaptor(g)
    ad.switch_mode(merge)
    slots = ad.append_slots("r0", n_tokens)
    assert len(set(slots.tolist())) == n_tokens
    cap = g.capacity(merge)
    assert slots.max() < (g.num_blocks - 1) * cap
    assert slots.min() >= 0
    # appending more continues without overlap
    more = ad.append_slots("r0", 7)
    assert not set(more.tolist()) & set(slots.tolist())


def test_mode_switch_opens_new_segment():
    """The seed-era hard assert (blocks only readable under the mode
    that wrote them) became the per-segment contract (§D8): appending
    after a mode switch freezes the old segment in place and opens a
    new one under the new capacity — no pause, no recompute."""
    ad = KVCacheAdaptor(geom_for())
    ad.append_slots("r0", 10)        # merge=1, cap=16 -> 1 block
    ad.switch_mode(2)
    slots = ad.append_slots("r0", 1)
    e = ad.table["r0"]
    assert e.tags() == (1, 2)
    assert e.max_tag == 2 and e.mode_tag == 2
    assert e.seg_tokens(0) == 10 and e.seg_tokens(1) == 1
    # the new segment's first slot is segment-local under B(2)
    cap2 = ad.geom.capacity(2)
    assert slots[0] == e.segments[1].ids[0] * cap2
    # the flat concat view still lists every block in write order
    assert e.block_ids == e.segments[0].ids + e.segments[1].ids


def test_drop_for_recompute_returns_tokens_and_blocks():
    ad = KVCacheAdaptor(geom_for())
    free0 = ad.free_blocks()
    ad.append_slots("r0", 40)
    assert ad.free_blocks() < free0
    assert ad.drop_for_recompute("r0") == 40
    assert ad.free_blocks() == free0


def test_scratch_slot_reserved():
    g = geom_for(blocks=8)
    ad = KVCacheAdaptor(g)
    # last block is never allocatable (parked-write scratch)
    assert ad.free_blocks() == 7


# ---------------------------------------------------------------------------
# vectorized batch builders == per-request reference (§Perf D3)
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(1, 70), min_size=1, max_size=9),
       st.sampled_from([1, 2, 4]),
       st.sampled_from(["head", "striped"]),
       st.sampled_from(["stablelm-1.6b", "llama3-8b", "deepseek-v2-236b"]))
@settings(max_examples=40, deadline=None)
def test_append_slots_batch_matches_per_request(ntoks, merge, layout, arch):
    """Batched slot/table builders must be bit-identical to the
    per-request reference across merge modes, layouts, and block
    boundaries (chunk sizes straddle capacity multiples)."""
    g = geom_for(arch, layout=layout, blocks=512, base=4)
    ad_ref, ad_bat = KVCacheAdaptor(g), KVCacheAdaptor(g)
    ad_ref.switch_mode(merge)
    ad_bat.switch_mode(merge)
    rids = [f"r{i}" for i in range(len(ntoks))]
    # two rounds: the second appends to existing entries (mid-block
    # continuation + block-boundary crossings)
    for _ in range(2):
        ref = [ad_ref.append_slots(rid, n) for rid, n in zip(rids, ntoks)]
        bat = ad_bat.append_slots_batch(rids, ntoks)
        assert bat.shape == (len(rids), max(ntoks))
        for i, (rid, n) in enumerate(zip(rids, ntoks)):
            np.testing.assert_array_equal(bat[i, :n], ref[i])
            assert (bat[i, n:] == -1).all()
        # width 64 >= worst case (2 rounds x 70 tokens / cap 4): the
        # builders now RAISE on overflow instead of silently truncating
        for rid in rids:
            np.testing.assert_array_equal(
                ad_bat.block_table(rid, 64),
                ad_ref.block_table(rid, 64))
        np.testing.assert_array_equal(
            ad_bat.block_table_batch(rids, 64),
            np.stack([ad_ref.block_table(r, 64) for r in rids]))
    np.testing.assert_array_equal(
        ad_bat.lengths_batch(rids),
        np.asarray([ad_ref.table[r].length for r in rids]))


def test_append_slots_batch_scalar_tokens_and_reused_out():
    g = geom_for()
    ad = KVCacheAdaptor(g)
    rids = ["a", "b", "c"]
    slots = ad.append_slots_batch(rids, 5)
    assert slots.shape == (3, 5)
    assert (slots >= 0).all()
    out = np.full((8, 4), 99, np.int32)
    bt = ad.block_table_batch(rids, 4, out=out)
    assert bt.shape == (3, 4)
    assert bt.base is out  # persistent-buffer reuse, no realloc


def test_ids_np_cache_tracks_growth():
    ad = KVCacheAdaptor(geom_for(base=4))
    ad.append_slots("r0", 3)
    e = ad.table["r0"]
    first = e.ids_np()
    assert first is e.ids_np()          # cached while unchanged
    ad.append_slots("r0", 8)            # crosses a block boundary
    np.testing.assert_array_equal(e.ids_np(), np.asarray(e.block_ids))
