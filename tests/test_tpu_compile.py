"""Ahead-of-time compiles of the serving kernels for a TPU v5e chip.

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
only the chip's compiler (Mosaic) checks that they lower at all: block
shapes against the (8, 128) tiling, in-kernel reshapes and scoped VMEM.
Each kernel here is compiled with ``interpret=False`` against a
described (not attached) v5e chip at stablelm-1.6b widths — 32 heads of
64, KV blocks of 16 tokens, a 16-request decode batch, 256-token prefill
chunks — and must contain its ``tpu_custom_call``, named by the
kernel's ``name`` (the instruction a chip trace shows). ``hs`` is the
head split a merged (TP) mode reads the pool with. A decode step program
compiled the same way carries its phase and island shape as its module
name.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

H, KV, HD = 32, 32, 64          # stablelm-1.6b attention widths
PAGE, NBLK, MB = 16, 2048, 256  # KV block tokens, pool blocks, table width
B, T = 16, 256                  # decode batch, prefill chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described chip, with the persistent compile cache off: an
    entry written for a described device cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, chip, name, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text), name


def _pool():
    return ((NBLK, PAGE, KV * HD), jnp.bfloat16)


def _i32(*shape):
    return (shape, jnp.int32)


@pytest.mark.parametrize("hs", [1, 4])
@pytest.mark.parametrize("return_lse", [False, True])
def test_paged_attention_kernel(chip, hs, return_lse):
    from repro.kernels.paged_attention.kernel import paged_attention_kernel
    dt = jnp.float32 if return_lse else jnp.bfloat16
    _compile(lambda q, k, v, t, c: paged_attention_kernel(
        q, k, v, t, c, hs=hs, return_lse=return_lse), chip, "paged_decode",
        ((B, H // hs, HD), dt), _pool(), _pool(), _i32(B, MB), _i32(B))


@pytest.mark.parametrize("hs", [1, 4])
def test_paged_append_token_kernel(chip, hs):
    from repro.kernels.paged_attention.kernel import \
        paged_append_token_kernel
    new = ((B, KV // hs, HD), jnp.bfloat16)
    _compile(lambda k, v, kn, vn, s: paged_append_token_kernel(
        (k, v), (kn, vn), s, hs=hs), chip, "paged_append_token",
        _pool(), _pool(), new, new, _i32(B))


@pytest.mark.parametrize("hs", [1, 4])
def test_paged_append_chunk_kernel(chip, hs):
    from repro.kernels.paged_attention.kernel import \
        paged_append_chunk_kernel
    new = ((2, T, KV // hs, HD), jnp.bfloat16)
    _compile(lambda k, v, kn, vn, s: paged_append_chunk_kernel(
        (k, v), (kn, vn), s, hs=hs), chip, "paged_append_chunk",
        _pool(), _pool(), new, new, _i32(2, T))


@pytest.mark.parametrize("hs", [1, 4])
@pytest.mark.parametrize("return_lse", [False, True])
def test_paged_flash_prefill_kernel(chip, hs, return_lse):
    from repro.kernels.flash_prefill.kernel import (
        paged_flash_prefill_kernel, q_block)
    dt = jnp.float32 if return_lse else jnp.bfloat16
    _compile(lambda q, k, v, t, p: paged_flash_prefill_kernel(
        q, k, v, t, p, hs=hs, blk_q=q_block(128, T, H // hs),
        prior_only=return_lse, return_lse=return_lse), chip,
        "paged_flash_prefill", ((2, H // hs, T, HD), dt), _pool(), _pool(), _i32(2, MB), _i32(2))


def test_flash_prefill_kernel(chip):
    from repro.kernels.flash_prefill.kernel import flash_prefill_kernel
    x = ((1, H, 2048, HD), jnp.bfloat16)
    _compile(flash_prefill_kernel, chip, "flash_prefill", x, x, x)


def test_decode_step_program_is_named_for_its_phase_and_island(chip):
    """The pool's decode step for a one-engine DP island compiles as
    module ``jit_fs_decode_n1m1`` with its operations under the scope
    of the same name, and counts as one build."""
    from repro.configs import get_config
    from repro.core.communicator_pool import CommunicatorPool
    from repro.core.kv_adaptor import PoolGeometry
    from repro.core.modes import FlyingMode, Island, ParallelPlan
    from repro.launch.dryrun import abstract_states
    from repro.models.model import build_model
    cfg = get_config("stablelm-1.6b-reduced")
    plan = ParallelPlan(engine_rows=1, tp_base=1, data_rows=1)
    geom = PoolGeometry(cfg, plan, num_blocks=64, block_base=PAGE)
    model = build_model(cfg)
    pool = CommunicatorPool(model, plan, geom)
    on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=chip)
    params = jax.tree.map(on_chip, model.param_specs())
    states = jax.tree.map(on_chip, abstract_states(
        model, geom, FlyingMode(plan, 1), 4))
    batch = {k: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
             for k, s in (("tokens", (4, 1)), ("positions", (4, 1)),
                          ("slots", (4,)), ("block_table", (4, 8)),
                          ("context_len", (4,)))}
    text = pool.precompile(Island(0, 1, 1), "decode",
                           (params, states, batch), sampled=True,
                           donate=True).as_text()
    assert text.startswith("HloModule jit_fs_decode_n1m1,")
    assert 'op_name="jit(fs_decode_n1m1)/fs_decode_n1m1/' in text
    assert pool.stats.compiles == 1
    assert pool.stats.last_compiled == "fs_decode_n1m1/b4/s1/mb8"
