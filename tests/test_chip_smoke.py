"""The one-chip smoke's check of the compiled serving steps
(``chip_smoke.py::compiled_step_texts``), on the CPU at a reduced size:
it compiles the pool's decode and prefill step programs at the serving
island's shapes."""
import importlib.util
import os

import jax
import jax.numpy as jnp

from conftest import REPO
from repro.configs import get_config
from repro.core.engine import FlyingEngine
from repro.core.kv_adaptor import PoolGeometry
from repro.core.modes import ParallelPlan
from repro.models.model import build_model


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compiled_step_texts_compiles_the_pools_step_programs():
    cfg = get_config("stablelm-1.6b-reduced")
    plan = ParallelPlan(engine_rows=1, tp_base=1, data_rows=1)
    model = build_model(cfg, jnp.float32)
    geom = PoolGeometry(cfg, plan, num_blocks=64, block_base=4)
    eng = FlyingEngine(model, plan, geom, model.init(jax.random.key(0)),
                       batch_per_engine=2, max_blocks_per_req=16,
                       prefill_len=8)
    texts = _smoke().compiled_step_texts(eng)
    assert texts["decode"].startswith("HloModule jit_fs_decode_n1m1,")
    assert texts["prefill"].startswith("HloModule jit_fs_prefill_n1m1,")
    assert eng.pool.stats.compiles == 2
