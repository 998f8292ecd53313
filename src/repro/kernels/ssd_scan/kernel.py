"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

State-space duality: within a chunk the output is a (masked, decay-
weighted) attention-like matmul — MXU work; across chunks a small state
[H, hd, S] recurrence carries in VMEM scratch. grid = (batch, heads,
num_chunks) with chunks innermost (sequential; Pallas TPU grids execute
in order, so the scratch state is the inter-chunk carry). chunk=128
aligns the intra-chunk matmuls to the MXU; hd/S are 64/128-lane shaped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, y_ref, hT_ref, h_ref, *,
            chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)       # [chunk, hd]
    dt = dt_ref[0, :, 0].astype(jnp.float32)     # [chunk, 1] -> [chunk]
    dt = dt.reshape(chunk)
    A = A_ref[0]                                  # scalar for this head
    Bm = B_ref[0].astype(jnp.float32)            # [chunk, S]
    Cm = C_ref[0].astype(jnp.float32)            # [chunk, S]

    loga = dt * A                                 # [chunk] (<= 0)
    s = jnp.cumsum(loga)                          # [chunk]
    # intra-chunk: L[i,j] = exp(s_i - s_j) for i >= j
    li = s[:, None] - s[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(li), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    xd = x * dt[:, None]                          # [chunk, hd]
    y_intra = jax.lax.dot_general(cb * L, xd, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y += (C exp(s)) @ h_prev
    h_prev = h_ref[...]                           # [hd, S]
    y_inter = jax.lax.dot_general(Cm * jnp.exp(s)[:, None], h_prev,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, :, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(s_last) h + sum_j exp(s_last - s_j) dt_j x_j B_j^T
    decay_out = jnp.exp(s[-1] - s)                # [chunk]
    xw = xd * decay_out[:, None]                  # [chunk, hd]
    S_new = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_ref[...] = h_prev * jnp.exp(s[-1]) + S_new

    @pl.when(c == n_chunks - 1)
    def _fin():
        hT_ref[0, 0] = h_ref[...].astype(hT_ref.dtype)


def ssd_scan_kernel(x, dt, A, B, C, *, chunk: int = 128,
                    interpret: bool = False):
    """x [Bs,T,H,hd]; dt [Bs,T,H] fp32; A [H]; B/C [Bs,T,S] (h0 = 0).
    Returns (y [Bs,T,H,hd] fp32, hT [Bs,H,hd,S] fp32)."""
    Bs, T, H, hd = x.shape
    S = B.shape[-1]
    assert T % chunk == 0
    nc = T // chunk
    kern = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, hT = pl.pallas_call(
        kern,
        name="ssd_scan",
        grid=(Bs, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, S), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, S), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, hd, S), lambda b, h, c: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hd, S), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((Bs, T, H, hd), jnp.float32),
            jax.ShapeDtypeStruct((Bs, H, hd, S), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, A, B, C)
    return y, hT
