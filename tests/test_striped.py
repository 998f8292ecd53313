"""Striped (context-parallel) cache unit/property tests — host-side math
plus single-device degenerate equivalence (distributed equivalence is
covered by tests/test_multidevice.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.striped import stripe_counts, stripe_write_slot


@given(st.integers(1, 4096), st.sampled_from([1, 2, 4, 8, 16, 128]))
@settings(max_examples=60, deadline=None)
def test_stripe_counts_partition_context(n_tokens, F):
    """Every token in [0, n) is owned by exactly one stripe."""
    total = sum(int(stripe_counts(jnp.array([n_tokens]), s, F)[0])
                for s in range(F))
    assert total == n_tokens


@given(st.integers(1, 200), st.sampled_from([1, 2, 4, 8]),
       st.sampled_from([4, 8, 16]))
@settings(max_examples=40, deadline=None)
def test_stripe_write_slots_bijective_per_stripe(n_tokens, F, page):
    """Within a stripe, slots are unique and dense in [0, count*...);
    across stripes, ownership is disjoint."""
    mb = -(-n_tokens // (F * page)) + 1
    bt = jnp.arange(mb)[None, :]  # identity block table
    pos = jnp.arange(n_tokens)[None, :]
    owned = np.zeros(n_tokens, np.int32)
    for s in range(F):
        slots = np.asarray(stripe_write_slot(pos, s, F, bt, page))[0]
        mine = slots >= 0
        owned[mine] += 1
        got = slots[mine]
        assert len(set(got.tolist())) == mine.sum()  # unique slots
    assert (owned == 1).all()


def test_mla_absorbed_equals_naive_expansion():
    """The absorbed MLA score path (used by the striped backend) equals
    the naive up-projection expansion."""
    key = jax.random.key(0)
    B, T, H, R, Dn = 2, 6, 4, 32, 16
    ks = jax.random.split(key, 3)
    q_nope = jax.random.normal(ks[0], (B, H, Dn))
    wuk = jax.random.normal(ks[1], (R, H, Dn)) * 0.2
    c = jax.random.normal(ks[2], (B, T, R))
    # naive: expand k then dot
    k_nope = jnp.einsum("btr,rhd->bthd", c, wuk)
    s_naive = jnp.einsum("bhd,bthd->bht", q_nope, k_nope)
    # absorbed: fold wuk into q
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope, wuk)
    s_abs = jnp.einsum("bhr,btr->bht", q_abs, c)
    np.testing.assert_allclose(np.asarray(s_abs), np.asarray(s_naive),
                               rtol=1e-5, atol=1e-5)


def test_striped_backend_single_device_degenerate():
    """With tp=1 the striped decode backend reduces to ordinary paged
    decode (stripe 0 owns everything)."""
    from repro.core.views import SINGLE
    from repro.models.cache import paged_attention_ref
    from repro.models.striped import StripedDecodeBackend
    key = jax.random.key(1)
    B, H, KV, hd, page, nblk = 2, 4, 2, 16, 4, 8
    ks = jax.random.split(key, 4)
    kp = jax.random.normal(ks[0], (nblk, page, KV, hd))
    vp = jax.random.normal(ks[1], (nblk, page, KV, hd))
    q = jax.random.normal(ks[2], (B, 1, H, hd))
    k_new = jax.random.normal(ks[3], (B, 1, KV, hd))
    v_new = k_new * 0.5
    bt = jnp.array([[0, 1], [2, 3]])
    ctx = jnp.array([7, 5])  # incl. the new token
    be = StripedDecodeBackend(ctx=SINGLE, block_table=bt, context_len=ctx,
                              n_q_heads=H, n_kv_heads=KV)
    pos = (ctx - 1)[:, None]
    out, (kp2, vp2) = be.attend((kp, vp), q, k_new, v_new, positions=pos)
    ref = paged_attention_ref(q[:, 0], kp2, vp2, bt, ctx)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_capacity_universality_vs_head_layout():
    """Striped capacity scales with full TP for every assigned arch;
    head layout saturates at the arch's kv-head budget."""
    from repro.configs import ASSIGNED_ARCHS, get_config
    from repro.core.kv_adaptor import PoolGeometry
    from repro.core.modes import ParallelPlan
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        if cfg.family == "ssm":
            continue
        plan = ParallelPlan(engine_rows=cfg.engine_rows, tp_base=16,
                            data_rows=16)
        s = PoolGeometry(cfg, plan, num_blocks=16, block_base=16,
                         layout="striped")
        for m in plan.valid_merges():
            assert s.capacity(m) == 16 * s.stripe_factor(m), arch
            assert s.capacity_scales(m)
