"""TPContext shard-math unit + property tests (host-side, no devices)."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.views import TPContext, pow2_shards, v2


def make_ctx(tp, view_m):
    return TPContext(tp=tp, view_m=view_m,
                     tp_axes=("merge", "ed", "model"),
                     view_axes=("merge",))


@given(st.integers(1, 4096), st.sampled_from([1, 2, 4, 8, 16, 32, 64, 128,
                                              256]))
def test_pow2_shards_divides(n, tp):
    w = pow2_shards(n, tp)
    assert n % w == 0
    assert tp % w == 0
    assert w <= tp


@given(st.sampled_from([8, 14, 32, 80, 96, 128, 160]),
       st.sampled_from([(2, 1), (4, 2), (8, 2), (16, 4), (32, 2)]))
def test_slice_cover_exactly(n, tp_vm):
    """Every compute slice of an n-unit dim is owned by >=1 rank and the
    ownership counts are balanced (replication = tp/want everywhere)."""
    tp, vm = tp_vm
    ctx = make_ctx(tp, vm)
    want = ctx.compute_shards(n)
    counts = [0] * want
    for r in range(tp):
        s = ctx.slice_of_rank(r, n)
        assert 0 <= s < want
        counts[s] += 1
    assert all(c == tp // want for c in counts)


@given(st.sampled_from([8, 32, 96, 128]),
       st.sampled_from([(4, 2), (8, 2), (16, 4)]))
def test_replication_scaling_consistent(n, tp_vm):
    tp, vm = tp_vm
    ctx = make_ctx(tp, vm)
    assert ctx.compute_shards(n) * ctx.replication(n) == tp
    assert ctx.local_units(n) * ctx.compute_shards(n) == n


def test_stored_shards_rule():
    ctx = make_ctx(tp=32, view_m=2)  # storage = 16
    assert ctx.storage_shards == 16
    assert ctx.stored_shards(32) == 16   # divisible -> tile-sharded
    assert ctx.stored_shards(8) == 1     # kv heads < storage -> replicated
    assert ctx.stored_shards(14) == 1


def test_single_context_is_identity():
    from repro.core.views import SINGLE
    import jax.numpy as jnp
    w = jnp.ones((4, 8))
    assert SINGLE.activate(w, 1, 8) is w
    assert SINGLE.psum(w, 8) is w
