"""Cost-model sanity properties (the simulator is the benchmark
substrate, so its monotonicities must hold)."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.modes import ParallelPlan
from repro.serving.simulator import CostModel

PLAN = ParallelPlan(engine_rows=1, tp_base=16, data_rows=16)


@pytest.fixture(scope="module")
def cm():
    return CostModel(get_config("llama3-8b"), PLAN)


def test_decode_faster_with_more_tp(cm):
    ts = [cm.decode_step(m, 8, 2048) for m in (1, 2, 4, 8, 16)]
    assert ts[0] > ts[-1]
    assert all(t > 0 for t in ts)


def test_decode_slower_with_more_context(cm):
    assert cm.decode_step(1, 8, 32768) > cm.decode_step(1, 8, 1024)


def test_prefill_scales_with_tokens(cm):
    assert cm.prefill_step(1, 8192) > 1.8 * cm.prefill_step(1, 4096)


def test_cold_restart_orders_of_magnitude_slower(cm):
    """Paper Table 2: 15 ms live vs 146-292 s cold."""
    assert cm.cold_restart(16) / cm.flying_switch() > 1e3


@given(st.sampled_from([1, 2, 4, 8, 16]), st.integers(1, 64),
       st.integers(128, 65536))
@settings(max_examples=40, deadline=None)
def test_decode_time_positive_and_finite(cm, merge, batch, ctx):
    t = cm.decode_step(merge, batch, ctx)
    assert 0 < t < 60


def test_moe_uses_active_params():
    dense = CostModel(get_config("llama3-8b"), PLAN)
    plan_moe = ParallelPlan(engine_rows=2, tp_base=16, data_rows=16)
    moe = CostModel(get_config("phi3.5-moe-42b-a6.6b"), plan_moe)
    # phi-3.5-moe activates ~6.6B params; per-chip weight traffic at
    # equal tp should be comparable to an 8B dense model, far below 42B
    assert moe.n_active < 0.25 * moe.n_total
