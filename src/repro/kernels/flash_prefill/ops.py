"""Flash-prefill dispatch layer.

``flash_prefill`` is the dense (non-paged) causal kernel wrapper: layout
transform [B,T,H,hd]->[B,H,T,hd], GQA repeat, T padding to the block
size, CPU interpret dispatch.

``paged_flash_prefill`` is the serving chunked-prefill op (docs/PERF.md
§D6): fused multi-token chunk append (aliased row writes, never a
full-pool scatter) followed by one paged flash pass whose K loop sweeps
the scalar-prefetched block table — in-chunk causal attention and
attention over prior pages are the same mb-bucket-bounded sweep.
``impl`` follows the paged-decode tri-state (``kernel|interpret|ref``,
resolved by ``kernels/paged_attention/ops.resolve_impl``): the jnp
reference appends with the scatter oracle and attends via the gathered
oracle over the logical pool view; the kernel path reads the physical
pool in place and never materializes the gathered context or a dense
[B,H,Tq,Tk] score tensor.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.kernel import (flash_prefill_kernel,
                                                paged_flash_prefill_kernel,
                                                q_block)
from repro.kernels.flash_prefill.ref import (flash_prefill_ref,
                                             paged_flash_prefill_ref,
                                             paged_prefill_sweep_with_lse_ref)
from repro.kernels.paged_attention.ref import pool_view


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("window", "blk"))
def flash_prefill(q, k, v, *, window: Optional[int] = None, blk: int = 128):
    """q [B,T,H,hd]; k/v [B,T,KV,hd] -> [B,T,H,hd], causal (+window)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    blk_eff = min(blk, T)
    pad = (-T) % blk_eff
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = flash_prefill_kernel(qt, kt, vt, window=window, blk_q=blk_eff,
                               blk_k=blk_eff, interpret=_interpret())
    out = out[:, :, :T]
    return jnp.moveaxis(out, 2, 1)


def _padded_rows(q, blk_q: int):
    """q [B,T,H,hd] -> ([B,H,T',hd] padded to the kernel's q block, the
    q block). Padded rows attend garbage positions past the chunk; their
    outputs are sliced off by the caller (masked rows keep l>0 via the
    guarded divide), so they never reach a real row."""
    B, T, H, hd = q.shape
    blk = q_block(blk_q, T, H)
    qt = jnp.moveaxis(q, 1, 2)                       # [B,H,T,hd]
    pad = (-T) % blk
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return qt, blk


def paged_flash_prefill(q, k_new, v_new, k_pool, v_pool, slots, block_table,
                        prior_len, *, hs: int = 1,
                        window: Optional[int] = None,
                        softmax_scale: Optional[float] = None,
                        blk_q: int = 128, impl: Optional[str] = None):
    """Fused chunk append + paged flash-prefill attention.

    q [B,T,H,hd] (row i at absolute position prior_len[b] + i);
    k_new/v_new [B,T,KV,hd] the chunk's fresh K/V, written at view
    ``slots`` [B,T] (negative => parked) before attending; pools
    [nblk,page,W] physical, read under head split ``hs``; block_table
    [B,MB] covers prior pages AND the chunk's own pages; prior_len [B].
    Returns (out [B,T,H,hd], k_pool, v_pool).

    Called from inside the compiled serve step (no inner jit, same as
    the decode ops — an extra jit boundary would break pool donation).
    """
    from repro.kernels.paged_attention.ops import (paged_append_chunk,
                                                   resolve_impl)
    impl = resolve_impl(impl)
    k_pool, v_pool = paged_append_chunk((k_pool, v_pool), (k_new, v_new),
                                        slots, hs=hs, impl=impl)
    T, hd = q.shape[1], q.shape[3]
    if impl == "ref":
        out = paged_flash_prefill_ref(
            q, pool_view(k_pool, hs, hd), pool_view(v_pool, hs, hd),
            block_table, prior_len, window=window,
            softmax_scale=softmax_scale)
        return out, k_pool, v_pool
    qt, blk = _padded_rows(q, blk_q)
    out = paged_flash_prefill_kernel(
        qt, k_pool, v_pool, block_table.astype(jnp.int32),
        prior_len.astype(jnp.int32), hs=hs, window=window,
        softmax_scale=softmax_scale, blk_q=blk,
        interpret=(impl == "interpret"))
    return jnp.moveaxis(out[:, :, :T], 2, 1), k_pool, v_pool


def paged_prefill_sweep_with_lse(q, k_pool, v_pool, block_table, prior_len,
                                 *, hs: int = 1, prior_only: bool = False,
                                 window: Optional[int] = None,
                                 softmax_scale: Optional[float] = None,
                                 blk_q: int = 128,
                                 impl: Optional[str] = None):
    """Partial chunked-prefill attention over ONE block segment with LSE
    (§D8 live cross-layout reads). q [B,T,H,hd]; physical pools read
    under head split ``hs``; the segment's pages in ``block_table``;
    ``prior_len`` [B] = tokens of the segment each chunk row may attend
    (for ``prior_only`` segments: the frozen segment's token count;
    otherwise the causal current-segment sweep). Returns (out
    [B,T,H,hd] fp32, lse [B,H,T] fp32); rows/heads with nothing to
    attend get lse = -inf so an LSE merge ignores them. No append — the
    live backend writes the chunk separately under the current view."""
    from repro.kernels.paged_attention.ops import resolve_impl
    impl = resolve_impl(impl)
    T, hd = q.shape[1], q.shape[3]
    if impl == "ref":
        return paged_prefill_sweep_with_lse_ref(
            q, pool_view(k_pool, hs, hd), pool_view(v_pool, hs, hd),
            block_table, prior_len, prior_only=prior_only, window=window,
            softmax_scale=softmax_scale)
    qt, blk = _padded_rows(q.astype(jnp.float32), blk_q)
    out, lse = paged_flash_prefill_kernel(
        qt, k_pool, v_pool, block_table.astype(jnp.int32),
        prior_len.astype(jnp.int32), hs=hs, window=window,
        softmax_scale=softmax_scale, blk_q=blk, prior_only=prior_only,
        return_lse=True, interpret=(impl == "interpret"))
    return (jnp.moveaxis(out[:, :, :T], 2, 1).astype(jnp.float32),
            lse[:, :, :T])


__all__ = ["flash_prefill", "flash_prefill_ref", "paged_flash_prefill",
           "paged_flash_prefill_ref", "paged_prefill_sweep_with_lse"]
