"""RG-LRU linear-recurrence Pallas TPU kernel.

The recurrence h_t = a_t h_{t-1} + g_t is elementwise per channel —
VPU work, no MXU. Parallelism comes from lanes: grid = (B, C/blk_c,
T/blk_t) with time innermost; each step runs a log2(blk_t) Blelloch-style
*associative scan* over the time tile entirely in VMEM/registers
(composition (a1,g1)∘(a2,g2) = (a1a2, a2 g1 + g2)), carrying h across
tiles in scratch. This replaces the GPU formulation's thread-sequential
scan with a lane-parallel one — the TPU-native adaptation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, g_ref, y_ref, hT_ref, h_ref, *, blk_t: int, n_t: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)   # [blk_t, blk_c]
    g = g_ref[0].astype(jnp.float32)

    # associative inclusive scan over time (log2 blk_t rounds)
    av, gv = a, g
    off = 1
    while off < blk_t:
        a_sh = jnp.concatenate([jnp.ones((off, av.shape[1]), jnp.float32),
                                av[:-off]], axis=0)
        g_sh = jnp.concatenate([jnp.zeros((off, gv.shape[1]), jnp.float32),
                                gv[:-off]], axis=0)
        gv = gv + av * g_sh
        av = av * a_sh
        off *= 2
    # include carry h: y_t = gv_t + av_t * h_in
    h_in = h_ref[...]                   # [1, blk_c]
    ys = gv + av * h_in
    y_ref[0] = ys.astype(y_ref.dtype)
    h_ref[...] = ys[-1:][...]

    @pl.when(t == n_t - 1)
    def _fin():
        hT_ref[0] = h_ref[0].astype(hT_ref.dtype)


def rglru_scan_kernel(a, g, *, blk_t: int = 128, blk_c: int = 128,
                      interpret: bool = False):
    """a/g [B,T,C] -> (y [B,T,C] fp32, hT [B,C] fp32); h0 = 0."""
    B, T, C = a.shape
    blk_t = min(blk_t, T)
    blk_c = min(blk_c, C)
    assert T % blk_t == 0 and C % blk_c == 0
    n_t = T // blk_t
    kern = functools.partial(_kernel, blk_t=blk_t, n_t=n_t)
    y, hT = pl.pallas_call(
        kern,
        name="rglru_scan",
        grid=(B, C // blk_c, n_t),
        in_specs=[
            pl.BlockSpec((1, blk_t, blk_c), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, blk_t, blk_c), lambda b, c, t: (b, t, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_t, blk_c), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, blk_c), lambda b, c, t: (b, c)),
        ],
        scratch_shapes=[pltpu.VMEM((1, blk_c), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, C), jnp.float32),
            jax.ShapeDtypeStruct((B, C), jnp.float32),
        ],
        interpret=interpret,
    )(a, g)
    return y, hT
