"""MoE layer: capacity dispatch vs dense oracle; routing properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.views import SINGLE
from repro.models.moe import (_positions_in_expert, dense_moe_ref, init_moe,
                              moe_ffn, route)


def test_moe_matches_dense_ref_when_capacity_ample():
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()  # cf=4 => no drops
    p = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.5
    y, aux = moe_ffn(cfg, p, x, SINGLE)
    yr, auxr = dense_moe_ref(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux), float(auxr), rtol=1e-5)


def test_shared_experts_path():
    cfg = get_config("deepseek-v2-236b").reduced()
    assert cfg.moe.num_shared_experts == 1
    p = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model)) * 0.5
    y, _ = moe_ffn(cfg, p, x, SINGLE)
    yr, _ = dense_moe_ref(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)


@given(st.integers(2, 64), st.integers(2, 16))
@settings(max_examples=30, deadline=None)
def test_positions_in_expert_are_dense_ranks(M, E):
    e = jax.random.randint(jax.random.key(M * E), (M,), 0, E)
    pos = _positions_in_expert(e, E)
    en = np.asarray(e)
    pn = np.asarray(pos)
    for ex in range(E):
        got = sorted(pn[en == ex].tolist())
        assert got == list(range(len(got)))


def test_router_weights_normalized_topk():
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    p = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (8, cfg.d_model))
    e, w, aux = route(p["router"], x, cfg.moe.top_k)
    assert e.shape == (8, cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert float(aux) >= 1.0 - 1e-5  # switch aux loss lower bound is 1


def test_capacity_drops_bounded():
    """With tiny capacity the dispatch drops tokens but stays finite and
    the output is a damped version of the reference (no NaNs/garbage)."""
    import dataclasses
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    p = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    y, _ = moe_ffn(cfg, p, x, SINGLE)
    assert not bool(jnp.any(jnp.isnan(y)))
